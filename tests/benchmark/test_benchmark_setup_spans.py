"""The seven ``setup_*`` per-layer metrics that read the program's kept
set-up spans (``benchmark/setup_trace.py``, PR 37) at no chip time: the cut
to the run's set-up and the per-thread union on records made by hand, one
traced rehearsal of a one-chip cell and of the four-chip cell through
``run_cell``, and a program whose tracer keeps nothing."""
import os
import types

import pytest

from benchmark import cells, run, setup_trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = cells.load_manifest(ROOT)
EVERY_CELL = ["setup_init_s", "setup_init_draw_s", "setup_trace_lower_s",
              "setup_cache_load_s", "setup_cost_capture_s",
              "setup_attributed_share"]
NEW_METRICS = EVERY_CELL + ["setup_place_model_s"]


@pytest.fixture(autouse=True)
def _own_registry(monkeypatch):
    """A rehearsal's steps go to a metrics registry of their own (as in
    ``test_benchmark_harness``)."""
    import deeplearning4j_tpu.monitor.registry as registry
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())


def _read(name, run_):
    return cells.module("layer_metrics", name).read(run_)


def _record(name, start, end, tid=1, span_id=0, parent=0, **args):
    return {"name": name, "cat": "setup", "start": start, "end": end,
            "tid": tid, "trace_id": 1, "span_id": span_id,
            "parent_span_id": parent, "args": args}


def _run_over(monkeypatch, kept, t0=100.0, setup_s=20.0):
    """A run whose window starts at ``t0`` after ``setup_s`` of set-up, in a
    process whose tracer kept ``kept``."""
    import deeplearning4j_tpu.monitor as monitor
    monkeypatch.setattr(monitor, "get_tracer",
                        lambda: types.SimpleNamespace(kept=lambda: kept))
    return types.SimpleNamespace(window=types.SimpleNamespace(t0=t0),
                                 setup_s=setup_s, extras={})


def test_the_manifest_gives_every_cell_six_and_the_four_chip_cell_seven():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    every = [w["name"] for w in MANIFEST["workloads"]]
    for name in NEW_METRICS:
        entry = entries[name]
        assert entry["source"] == "program_span" and entry["moves"] == \
            "setup_s"
        assert entry["workloads"] == (
            ["resnet50_pw4_b1024_resident"] if name == "setup_place_model_s"
            else every)
    assert len(MANIFEST["per_layer"]) == 46


def test_records_are_cut_to_the_runs_set_up(monkeypatch):
    """The process's earlier work, the reference check's network and a
    one-device run's come before and after: none of them is set-up."""
    kept = [
        _record("init", 70.0, 75.0, span_id=1),            # an earlier run's
        _record("init", 80.0, 91.0, span_id=2),
        _record("init/params", 80.5, 90.0, parent=2, draw_s=7.0, place_s=1.0),
        _record("init/params", 70.5, 74.0, parent=1, draw_s=3.0),
        _record("compile/cg/step", 93.0, 99.0),
        _record("jitwatch/cost_capture", 95.0, 99.5, tid=2),
        _record("jitwatch/cost_capture", 99.0, 101.0, tid=2),  # ends after t0
        _record("init", 120.0, 131.0, span_id=3),          # the check's
        _record("init/params", 120.5, 130.0, parent=3, draw_s=7.0)]
    run_ = _run_over(monkeypatch, kept)
    assert [r["start"] for r in setup_trace.records(run_)] == [
        80.0, 80.5, 93.0, 95.0]
    assert _read("setup_init_s", run_) == 11.0
    assert _read("setup_init_draw_s", run_) == 7.0
    assert _read("setup_cost_capture_s", run_) == 4.5
    assert _read("setup_place_model_s", run_) == 0.0


def test_phases_count_once_per_thread_and_only_on_the_fit_threads(
        monkeypatch):
    kept = [
        _record("init", 81.0, 85.0),
        # what init compiles itself is init's
        _record("jax/trace", 82.0, 82.5),
        _record("jax/backend_compile", 82.5, 84.0),
        # a program compiled while another is traced; the cache's read
        # inside the backend's span
        _record("jax/trace", 86.0, 90.0),
        _record("jax/trace", 87.0, 88.0),
        _record("jax/lower", 87.0, 87.5),
        _record("jax/lower", 90.0, 91.0),
        _record("jax/backend_compile", 87.5, 88.0),
        _record("jax/backend_compile", 91.0, 94.0),
        _record("jax/cache_retrieval", 91.5, 93.5),
        _record("compile/cg/step", 86.0, 94.5),
        # jitwatch's worker lowers the step again: its own, not set-up's
        _record("jitwatch/cost_capture", 94.5, 99.0, tid=2),
        _record("jax/trace", 94.6, 96.0, tid=2),
        _record("jax/backend_compile", 96.0, 99.0, tid=2)]
    run_ = _run_over(monkeypatch, kept)
    assert _read("setup_trace_lower_s", run_) == pytest.approx(4.5)
    assert _read("setup_cache_load_s", run_) == pytest.approx(3.5)
    # init 4 s + compile/cg/step 8.5 s of the 20: the worker's claim nothing
    assert _read("setup_attributed_share", run_) == pytest.approx(62.5)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_that_keeps_nothing_gives_every_reader_none(
        metric, monkeypatch):
    """The commit before PR 37: its tracer has no ``kept``."""
    import deeplearning4j_tpu.monitor as monitor
    monkeypatch.setattr(monitor, "get_tracer", types.SimpleNamespace)
    run_ = types.SimpleNamespace(window=types.SimpleNamespace(t0=100.0),
                                 setup_s=20.0, extras={})
    assert _read(metric, run_) is None
    # and one that keeps spans but ran no init inside the set-up
    other = _run_over(monkeypatch, [_record("init", 0.0, 5.0)])
    assert _read(metric, other) is None


@pytest.mark.parametrize("workload,fits", [
    ("granite_l10_b1_t8192_resident", 0),
    ("resnet50_pw4_b1024_resident", 8)])
def test_a_traced_rehearsal_reports_the_set_up_metrics(
        workload, fits, tmp_path, monkeypatch):
    """One whole traced run on the CPU (four of its virtual devices for the
    data-parallel cell): every new metric of the cell, and none of what the
    run builds after its window in them."""
    from deeplearning4j_tpu.monitor import get_tracer
    runs, metrics_of = [], run._metrics
    monkeypatch.setattr(run, "_metrics", lambda run_, trace: (
        runs.append(run_), metrics_of(run_, trace))[1])
    get_tracer().clear()
    notes = []
    result = run.run_cell(MANIFEST, ROOT, workload, seed=2**31 + 13,
                          seconds=0.3, trace=True, rehearse=True,
                          note=notes.append, trace_root=str(tmp_path))
    # (the toy ResNet50's gradients miss the reference's by 4 % here, at the
    # parent commit too: PERF.md section 7)
    assert all(": ok" in n for n in notes if n.startswith("check ")
               and not n.startswith("check reference")), notes
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    wanted = NEW_METRICS if fits else EVERY_CELL
    assert set(NEW_METRICS) & set(metrics) == set(wanted)
    assert all(metrics[name] >= 0 for name in wanted)
    assert result["metrics"]["setup_init_s"]["unit"] == "s"
    assert result["metrics"]["setup_attributed_share"]["unit"] == "%"
    (run_,) = runs
    assert metrics["setup_init_s"] + metrics["setup_trace_lower_s"] + \
        metrics["setup_cache_load_s"] <= run_.setup_s
    assert metrics["setup_init_draw_s"] <= metrics["setup_init_s"]
    assert 0 < metrics["setup_attributed_share"] <= 100
    # the check's network, and the one-device run's, were built after the
    # window: kept, and in no metric
    kept = get_tracer().kept()
    inits = [r for r in kept if r["name"] == "init"]
    assert len(inits) == (3 if fits else 2)
    assert metrics["setup_init_s"] == pytest.approx(
        inits[0]["end"] - inits[0]["start"])
    assert all(r["start"] > run_.window.t0 for r in inits[1:])
    placed = [r for r in kept if r["name"] == "pw/place_model"]
    if fits:        # one per warm-up fit; the two windows' are not set-up
        before = [r for r in placed if r["end"] <= run_.window.t0]
        assert (len(before), len(placed)) == (fits, fits + 2)
        assert metrics["setup_place_model_s"] == pytest.approx(sum(
            r["end"] - r["start"] for r in before))
    else:
        assert not placed
    # a bounded number per network: nothing per leaf, per op or per step
    # (the check's eager gradient compiles an op at a time, after the
    # window, and may fill the list: the first records win)
    assert len(setup_trace.records(run_)) < 200

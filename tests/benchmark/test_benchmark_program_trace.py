"""The readers of the program's own spans and scopes (``program_trace.py``
and the ``layer_metrics/`` files of PR 23): on a trace and a program text
written down by hand, where every answer can be worked out on paper, and on
one recorded on a v5e (``fixtures/charrnn_v5e_3fits_pr23.*``: three ``fit``s
of the char-RNN at 64 x 200, four segments each, monitor off, with the
scanned program's HLO text)."""
import gzip
import json
import os
import shutil
import types

import pytest

from benchmark import cells, program_trace, xplane
from benchmark.xplane import Device, Trace, event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
US = 1e3                                 # the hand-written trace counts in µs

#: two layers and an output layer: the scopes are "0", "1", "2"
CONFIG = {"builder": "file:builders.graves_lstm_charrnn:build",
          "builder_kwargs": {"vocab": 8, "width": 8, "layers": 2, "tbptt": 4}}

STEP_TEXT = '''HloModule jit_step, is_scheduled=true

%fused_computation.3 (p: f32[8]) -> (f32[8], f32[8]) {
  %p = f32[8]{0} parameter(0)
  %copy.7 = f32[8]{0} copy(f32[8]{0} %p)
  %mul.2 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(step)/transpose(jvp(1))/mul" stack_frame_id=4}
  ROOT %tuple.1 = (f32[8]{0:T(8,128)S(1)}, f32[8]{0}) tuple(f32[8]{0} %copy.7, f32[8]{0} %mul.2)
}

%fused_computation.4 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %dot.1 = f32[8]{0} multiply(f32[8]{0} %p.1, f32[8]{0} %p.1), metadata={op_name="jit(step)/transpose(jvp(0))/tbh,tbg->hg/dot_general"}
  ROOT %sub.1 = f32[8]{0} subtract(f32[8]{0} %p.1, f32[8]{0} %dot.1), metadata={op_name="jit(step)/updater/sub"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="params['0']['W']"}
  %while.9 = f32[8]{0} while(f32[8]{0} %a), condition=%c, body=%b, metadata={op_name="jit(step)/while"}
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(0)/tanh"}
  %lstm_cell_fwd.2 = f32[8]{0} custom-call(f32[8]{0} %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(0)/lstm_cell_fwd/pallas_call"}
  %fusion.3 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jvp(loss)/reduce_sum"}
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %a), kind=kOutput, calls=%fused_computation.4, metadata={op_name="jit(step)/transpose(jvp(0))/tbh,tbg->hg/dot_general"}
  ROOT %copy.5 = f32[8]{0} copy(f32[8]{0} %fusion.4)
}
'''
#: a second live program of the same name (the one-device step beside the
#: sharded one): it knows fewer of the trace's ops and is not chosen
OTHER_TEXT = '''HloModule jit_step, is_scheduled=true

ENTRY %main (a: f32[8]) -> f32[8] {
  ROOT %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/updater/sub"}
}
'''
RESHAPE_TEXT = '''HloModule jit_reshape, is_scheduled=true

ENTRY %main (a: f32[8]) -> f32[8] {
  ROOT %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f, metadata={op_name="jit(reshape)/jvp(0)/reshape"}
}
'''

OPS = {"while.9": "%while.9 = f32[8]{0} while(f32[8]{0} %a), body=%b",
       "fusion.1": "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop",
       "lstm_cell_fwd.2": ('%lstm_cell_fwd.2 = f32[8]{0} custom-call(%f), '
                           'custom_call_target="tpu_custom_call"'),
       "fusion.3": "%fusion.3 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %a)",
       "fusion.4": "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %a), kind=kOutput",
       "copy.5": "%copy.5 = f32[8]{0} copy(f32[8]{0} %fusion.4)"}


def _step_ops(at):
    """One execution of the step from ``at``: a while over 40 µs holding a
    forward fusion (10), the forward kernel (10), a fusion whose root is a
    tuple of an unnamed copy and the backward of layer 1 (10), a fusion
    stamped with layer 0's weight-gradient product whose root is the
    updater's (6) and a copy with no metadata (4)."""
    cuts = [("fusion.1", 0, 10), ("lstm_cell_fwd.2", 10, 20),
            ("fusion.3", 20, 30), ("fusion.4", 30, 36), ("copy.5", 36, 40)]
    return [event(OPS["while.9"], at * US, (at + 40) * US)] + [
        event(OPS[name], (at + s) * US, (at + e) * US) for name, s, e in cuts]


def _hand_written(pw=False):
    """The step runs 0-40 and 60-100; between them a small ``reshape``
    program 50-55 whose one op is called ``fusion.1`` too; the device is
    idle 40-50 and 55-60. The fit thread: next 1-3, prepare 3-8, dispatch
    8-12 (the runtime's execute call 9-11 inside); next 41-43, prepare
    43-49, dispatch 49-58 (the execute call 50-57: the device's queue was
    full). A prefetch worker: transform 2-4, put-ahead 4-9, the benchmark's
    own next 44-47 with its barrier 45-46. ``pw`` renames the fit thread's
    spans to ParallelWrapper's and adds its two loss fetches."""
    ops = _step_ops(0) + [event(OPS["fusion.1"], 50 * US, 55 * US)] \
        + _step_ops(60)
    modules = [event("jit_step(11)", 0, 40 * US),
               event("jit_reshape(22)", 50 * US, 55 * US),
               event("jit_step(11)", 60 * US, 100 * US)]
    nxt, prep, step = (("pw/group", "pw/global_batch", "pw/step") if pw
                       else ("fit/next_batch", "fit/prepare", "step"))
    fit = [event("bench/fit", 0, 100 * US), event("epoch", 1 * US, 99 * US),
           event(nxt, 1 * US, 3 * US), event(prep, 3 * US, 8 * US),
           event(step, 8 * US, 12 * US),
           event(nxt, 41 * US, 43 * US), event(prep, 43 * US, 49 * US),
           event(step, 49 * US, 58 * US)]
    if pw:
        fit += [event("pw/resolve_score", 12 * US, 20 * US),
                event("pw/resolve_score", 58 * US, 70 * US)]
    runtime = [event(program_trace.RUNTIME_EXECUTE, 4 * US, 5 * US),
               event(program_trace.RUNTIME_EXECUTE, 9 * US, 11 * US),
               event(program_trace.RUNTIME_EXECUTE, 50 * US, 57 * US)]
    worker = [event("input/transform", 2 * US, 4 * US),
              event("input/put_ahead", 4 * US, 9 * US),
              event("bench/input_next", 44 * US, 47 * US),
              event("bench/run_ahead_barrier", 45 * US, 46 * US)]
    return Trace([Device(0, ops, [], modules)],
                 [("python3", sorted(fit, key=lambda ev: ev.start)),
                  ("main/7", runtime), ("python3", worker)])


def _run(trace, steps, config=CONFIG):
    return types.SimpleNamespace(
        trace=trace, trace_window=types.SimpleNamespace(steps=steps),
        cell=types.SimpleNamespace(config=config), extras={}, devices=None)


def _read(metric, run):
    return cells.module("layer_metrics", metric).read(run)


def test_op_names_fusions_speak_for_their_roots():
    names = program_trace.op_names(STEP_TEXT)
    assert names["fusion.1"] == "jit(step)/jvp(0)/tanh"  # no such computation
    # the root is a tuple: its first element with a name
    assert names["fusion.3"] == "jit(step)/transpose(jvp(1))/mul"
    # stamped with the product inside, but its result is the updater's
    assert names["fusion.4"] == "jit(step)/updater/sub"
    assert names["lstm_cell_fwd.2"].endswith("lstm_cell_fwd/pallas_call")
    assert "copy.5" not in names and "copy.7" not in names


@pytest.mark.parametrize("op_name,kind", [
    ("jit(step)/jvp(0)/tanh", "forward"),
    ("jit(scanned)/while/body/closed_call/jvp(loss)/jit(log_softmax)/exp",
     "forward"),
    ("jit(step)/transpose(jvp(1))/tbh,tbg->hg/dot_general", "backward"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/2/dot",
     "backward"),
    ("jit(step)/transpose(jvp(loss))/broadcast_in_dim;jit(step)/updater/sub",
     "backward"),
    ("jit(scanned)/while/body/closed_call/updater/sub", "optimizer"),
    ("jit(step)/jvp()/mul", None), ("jit(step)/while/body/dynamic_slice", None),
    ("params['0']['W']", None), ("jit(step)/jvp(7)/tanh", None), ("", None)])
def test_kind_of_an_op_name(op_name, kind):
    assert program_trace.kind(op_name, frozenset("012")) == kind


def test_layer_scopes_come_from_the_configuration():
    assert program_trace.layer_scopes(CONFIG) == {"0", "1", "2"}
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "resnet50_imagenet.json")) as fh:
        scopes = program_trace.layer_scopes(json.load(fh))
    assert {"s0b0-a-conv", "s0b0-add", "gap", "output"} <= scopes


def test_device_time_by_scope_on_the_hand_written_trace():
    run = _run(_hand_written(), steps=2)
    found = program_trace.scoped_ms_per_step(
        run, [RESHAPE_TEXT, OTHER_TEXT, STEP_TEXT])
    # per step: forward the fusion and the kernel, backward the tuple-rooted
    # fusion, optimizer the updater-rooted one; unscoped the copy, the
    # while's own time (none) and half of the reshape program's 5 µs
    assert found == {"forward": pytest.approx(20e-3),
                     "backward": pytest.approx(10e-3),
                     "optimizer": pytest.approx(6e-3),
                     "unscoped": pytest.approx(6.5e-3)}
    # the four are the device's busy time per step
    assert sum(found.values()) == pytest.approx(
        xplane.busy_seconds(run.trace) * 1e3 / 2)
    assert _read("forward_ms_per_step", run) == pytest.approx(20e-3)
    assert _read("backward_ms_per_step", run) == pytest.approx(10e-3)
    assert _read("optimizer_ms_per_step", run) == pytest.approx(6e-3)
    assert _read("scoped_device_time_share", run) == pytest.approx(
        100 * 36 / 42.5)


def test_host_work_per_step_on_the_hand_written_trace():
    run = _run(_hand_written(), steps=2)
    # prepare 5 + 6; dispatch 4 + 9 less the runtime's execute call inside
    # it, 2 + 7 (the call at 4-5 is inside no dispatch and stays)
    assert program_trace.runtime_hold(run.trace) == [
        (9 * US, 11 * US), (50 * US, 57 * US)]
    assert _read("fit_host_ms_per_step", run) == pytest.approx(7.5e-3)
    assert _read("hostfed_fit_host_ms_per_step", run) == pytest.approx(7.5e-3)
    assert _read("put_ahead_ms_per_step", run) == pytest.approx(3.5e-3)
    assert _read("pw_host_ms_per_step", run) is None
    assert _read("pw_resolve_idle_ms_per_step", run) is None
    pw = _run(_hand_written(pw=True), steps=2)
    # group 2 + 2, global batch 5 + 6, dispatch 2 + 2
    assert _read("pw_host_ms_per_step", pw) == pytest.approx(9.5e-3)
    # the fetch 12-20 sits over a busy device; 58-70 over its idle 58-60
    assert _read("pw_resolve_idle_ms_per_step", pw) == pytest.approx(1e-3)
    assert _read("fit_host_ms_per_step", pw) is None


def test_idle_attributed_share_on_the_hand_written_trace():
    run = _run(_hand_written(), steps=2)
    # idle 40-50 and 55-60; the benchmark's own next claims 44-47; of the
    # 12 µs left the fit thread's spans cover 41-44, 47-50 and 55-58
    assert program_trace.idle_under(
        run.trace, program_trace.PROGRAM_SPANS, fit_thread=True,
        less=program_trace.OWN_SPANS) == (9 * US, 12 * US)
    assert _read("idle_attributed_share", run) == pytest.approx(75.0)
    assert _read("hostfed_idle_attributed_share", run) == pytest.approx(75.0)
    # ParallelWrapper's spans and its fetch cover 58-60 too
    assert _read("idle_attributed_share",
                 _run(_hand_written(pw=True), 2)) == pytest.approx(
                     100 * 11 / 12)


def test_a_program_without_spans_or_scopes_reads_as_nothing():
    """The commit before PR 23: the benchmark's spans and JAX's only, the
    compiler's names only. No reader raises, none reports."""
    trace = _hand_written()
    bare = Trace(trace.devices, [
        (name, [ev for ev in evs if ev.name.startswith("bench/")
                or ev.name == "epoch"]) for name, evs in trace.host])
    run = _run(bare, steps=2)
    plain = STEP_TEXT.replace("updater", "x").replace("jvp(", "jvp(x")
    assert program_trace.scoped_ms_per_step(run, [plain]) is None
    for entry in cells.load_manifest(ROOT)["per_layer"]:
        if entry["source"] == "program_span" or entry["name"] in (
                "forward_ms_per_step", "backward_ms_per_step",
                "optimizer_ms_per_step", "scoped_device_time_share"):
            assert _read(entry["name"], run) is None, entry["name"]
    assert _read("fit_host_ms_per_step", _run(None, steps=2)) is None
    assert _read("idle_attributed_share", _run(None, steps=2)) is None


# ------------------------------------------------------------- recorded
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    fixtures = os.path.join(HERE, "fixtures")
    path = tmp_path_factory.mktemp("pr23") / "charrnn.xplane.pb"
    with gzip.open(os.path.join(
            fixtures, "charrnn_v5e_3fits_pr23.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(
            fixtures, "charrnn_v5e_3fits_pr23.jit_scanned.hlo.txt.gz"),
            "rt") as fh:
        text = fh.read()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "graves_lstm_charrnn.json")) as fh:
        config = json.load(fh)
    return _run(xplane.load(str(path)), 3, config), text


def test_recorded_trace_holds_the_programs_spans_and_kernel_names(recorded):
    run, _ = recorded
    names = {ev.name for _, evs in run.trace.host for ev in evs}
    assert {"epoch", "fit/next_batch", "fit/prepare", "step",
            "input/transform", "input/put_ahead"} <= names
    assert "fit/resolve" not in names            # the monitor was off
    # three fits, two prepare spans each (the batch, the segment stacking)
    assert len(program_trace.spans(run.trace, ("step",), True)) == 3
    assert len(program_trace.spans(run.trace, ("fit/prepare",), True)) == 6
    top = [label for label, _ in xplane.top_ops(run.trace, n=4)]
    assert sorted(top) == [f"lstm_cell_{d}.{n} custom-call (pallas)"
                           for d in ("bwd", "fwd") for n in (28, 29)]


def test_recorded_trace_device_time_by_scope(recorded):
    run, text = recorded
    found = program_trace.scoped_ms_per_step(run, [text])
    assert found == {"forward": pytest.approx(0.7564, abs=1e-4),
                     "backward": pytest.approx(0.9716, abs=1e-4),
                     "optimizer": pytest.approx(0.5400, abs=1e-4),
                     "unscoped": pytest.approx(0.7201, abs=1e-4)}
    assert sum(found.values()) == pytest.approx(
        xplane.busy_seconds(run.trace) * 1e3 / 3, rel=1e-6)
    assert _read("scoped_device_time_share", run) == pytest.approx(
        75.902, abs=1e-3)
    # the kernels sit under their layers: forward under jvp(0) / jvp(1)
    names = program_trace.op_names(text)
    assert program_trace.kind(names["lstm_cell_fwd.28"],
                              frozenset("012")) == "forward"
    assert program_trace.kind(names["lstm_cell_bwd.29"],
                              frozenset("012")) == "backward"
    # the three Adam passes ride in weight-gradient fusions
    assert program_trace.kind(names["divide_subtract_fusion.28"],
                              frozenset("012")) == "optimizer"


def test_recorded_trace_host_work_and_idle_attribution(recorded):
    run, _ = recorded
    # at 2.9 ms of device work per fit the host is the bound (PERF.md §4)
    assert _read("fit_host_ms_per_step", run) == pytest.approx(5.7467,
                                                               abs=1e-3)
    assert len(program_trace.runtime_hold(run.trace)) == 3
    assert _read("put_ahead_ms_per_step", run) == pytest.approx(0.0598,
                                                                abs=1e-3)
    assert _read("idle_attributed_share", run) == pytest.approx(99.908,
                                                                abs=1e-2)
    assert _read("pw_host_ms_per_step", run) is None
    assert _read("pw_resolve_idle_ms_per_step", run) is None


# ------------------------------------------------------------ rehearsal
FIXTURE_CELL = {"resnet50_b256_resident": None,
                "resnet50_b256_hostfed": "lenet_hostfed",
                "charrnn_b64_t5000_tbptt50_pool20": "charrnn_resident",
                "resnet50_pw4_b1024_resident": "lenet_pw4"}


def _rehearsal_manifest():
    """The fixtures' manifest with this PR's per-layer entries laid over
    its three tiny cells."""
    fixtures = os.path.join(HERE, "fixtures")
    manifest = cells.load_manifest(fixtures)
    known = {m["name"] for m in manifest["per_layer"]}
    for entry in cells.load_manifest(ROOT)["per_layer"]:
        if entry["name"] not in known and (
                entry["source"] == "program_span"
                or entry["name"].endswith("_ms_per_step")
                or entry["name"] == "scoped_device_time_share"):
            manifest["per_layer"].append(dict(entry, workloads=[
                FIXTURE_CELL[w] for w in entry["workloads"]
                if FIXTURE_CELL.get(w)]))   # a cell not listed has no twin
    return fixtures, manifest


@pytest.mark.parametrize("workload,spans,absent", [
    ("charrnn_resident", ["fit_host_ms_per_step"],
     ["pw_host_ms_per_step", "put_ahead_ms_per_step"]),
    ("lenet_hostfed", ["hostfed_fit_host_ms_per_step",
                       "put_ahead_ms_per_step"], ["fit_host_ms_per_step"]),
    ("lenet_pw4", ["pw_host_ms_per_step"], ["fit_host_ms_per_step"])])
def test_rehearsal_reports_the_span_metrics_and_no_device_metric(
        workload, spans, absent, tmp_path, monkeypatch):
    """One traced run of each kind of cell on the CPU: the program's spans
    reach the trace with the monitor off and the span readers report; the
    CPU's trace has no device plane, so no device metric is made up."""
    import deeplearning4j_tpu.monitor.registry as registry
    from benchmark import run as bench_run
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())
    fixtures, manifest = _rehearsal_manifest()
    notes = []
    result = bench_run.run_cell(manifest, fixtures, workload, seed=5,
                                seconds=0.3, trace=True, rehearse=True,
                                note=notes.append, trace_root=str(tmp_path))
    assert result["correct"] is True, notes
    metrics = result["metrics"]
    for name in spans:
        assert metrics[name]["value"] > 0 and metrics[name]["unit"] == "ms"
    for name in absent + ["forward_ms_per_step", "backward_ms_per_step",
                          "optimizer_ms_per_step", "idle_attributed_share",
                          "scoped_device_time_share",
                          "pw_resolve_idle_ms_per_step"]:
        assert name not in metrics, name

"""Unit tests for bench.py: the harness (a jax-free parent running one
``--one`` child per config, the device named in every record, chip configs
that refuse any other platform, a failed child failing the run) and the
CPU-harness configs' latched comparison blocks."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_children(monkeypatch, records):
    """Stand-in for the ``--one`` children: ``records`` maps a config name
    to the record its child prints, or to a non-zero exit code. Returns the
    list of names asked for, in order."""
    asked = []

    def run(cmd, **kw):
        assert cmd[-2] == "--one", cmd
        name = cmd[-1]
        asked.append(name)
        rec = records.get(name, 1)
        if isinstance(rec, int):
            return subprocess.CompletedProcess(cmd, rec, b"", None)
        out = ("# noise\n" + json.dumps(dict(rec, one=name)) + "\n").encode()
        return subprocess.CompletedProcess(cmd, 0, out, None)

    monkeypatch.setattr(subprocess, "run", run)
    return asked


_TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


def _record(value, unit="images/sec"):
    return {"value": value, "unit": unit, "device": _TPU,
            "monitor": None, "jitwatch": None}


def test_parent_runs_one_child_per_config_and_relays_the_headline(
        bench, monkeypatch, capsys):
    asked = _fake_children(monkeypatch, {
        n: _record(10.0 + i, u) for i, (n, u, _) in
        enumerate(bench.ALL_BENCHES)})
    assert bench.main(["--all"]) == 0
    assert asked == [n for n, _, _ in bench.ALL_BENCHES]   # one each, in order
    [line] = capsys.readouterr().out.strip().splitlines()
    doc = json.loads(line)
    assert doc["metric"] == bench.HEADLINE
    assert doc["value"] == 10.0 + asked.index(bench.HEADLINE)
    assert doc["device"] == _TPU                 # the headline names its device

    asked.clear()
    assert bench.main([]) == 0                   # default: the headline only
    assert asked == [bench.HEADLINE]


def test_failed_child_fails_the_run_and_nothing_unmeasured_is_printed(
        bench, monkeypatch, capsys):
    ok = {n: _record(1.0, u) for n, u, _ in bench.ALL_BENCHES}
    asked = _fake_children(monkeypatch,
                           dict(ok, vgg16_imagenet_images_per_sec=3))
    assert bench.main(["--all"]) == 1
    assert len(asked) == len(bench.ALL_BENCHES)  # the rest still ran
    out = capsys.readouterr()
    assert "vgg16_imagenet_images_per_sec" in out.err
    assert json.loads(out.out)["value"] == 1.0   # the headline WAS measured

    # the headline's own child fails: no number at all, non-zero exit
    _fake_children(monkeypatch, {})
    assert bench.main([]) == 1
    assert capsys.readouterr().out == ""

    # a child that exits 0 without a record is a failure too
    monkeypatch.setattr(subprocess, "run", lambda cmd, **kw:
                        subprocess.CompletedProcess(cmd, 0, b"hello\n", None))
    assert bench.main([]) == 1
    assert capsys.readouterr().out == ""


def test_one_record_names_the_device(bench, monkeypatch, capsys):
    monkeypatch.setattr(bench, "ALL_BENCHES",
                        [("stub_per_sec", "things/sec", lambda: 12.34)])
    bench.run_one("stub_per_sec")
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["one"] == "stub_per_sec" and doc["value"] == 12.3
    assert doc["unit"] == "things/sec"
    assert doc["device"] == {"platform": "cpu", "device_kind": "cpu",
                             "device_count": 8}


def test_chip_config_refuses_the_cpu(bench):
    """The seven chip cells measure the chip or nothing: on any other
    platform ``--one`` exits non-zero, naming what it found, before
    anything is built."""
    assert len(bench.CHIP_CONFIGS) == 7
    assert bench.CHIP_CONFIGS <= {n for n, _, _ in bench.ALL_BENCHES}
    with pytest.raises(SystemExit) as exc:
        bench.run_one("resnet50_imagenet_images_per_sec")
    assert "'cpu'" in str(exc.value.code) and "not a TPU" in str(exc.value.code)


def test_importing_bench_does_not_import_jax():
    """The parent must leave the chip to its children."""
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys, bench; sys.exit('jax' in sys.modules)"],
        cwd=REPO, capture_output=True, timeout=60)
    assert p.returncode == 0, p.stderr


def test_roofline_scripts_refuse_an_unknown_device_kind(bench):
    assert "TPU v5 lite" in bench.DEVICE_PEAKS
    with pytest.raises(SystemExit) as exc:     # the CPU has no published peaks
        bench.device_peaks()
    assert "'cpu'" in str(exc.value.code)


def test_paramserver_bench_cuts_wire_bytes(bench):
    """Acceptance (PR 7): the paramserver bench must show the N-server
    delta wire moving >= 2x fewer bytes per step than the 1-server
    full-vector baseline, and latch the {steps/sec, wire bytes/step}
    comparison for the --one record. (The steps/sec >= dense criterion is
    latched by the real bench record — at the full 1M-param size; this
    harness run is shrunk for test time, so only sanity-bound it here.)"""
    value = bench.bench_paramserver(steps=12, n_in=128, hidden=256,
                                    batch=16)
    stats = bench.PARAMSERVER_STATS
    assert value > 0
    assert stats["num_servers"] == 3
    assert stats["wire_reduction"] >= 2.0
    assert stats["delta_wire_bytes_per_step"] < \
        stats["dense_wire_bytes_per_step"]
    assert stats["dense_steps_per_sec"] > 0
    assert stats["speedup"] > 0.3


def test_paramserver_overlap_bench_latches_comparison(bench):
    """Acceptance (ISSUE 15): the overlap bench latches the sync-vs-
    overlap steps/sec comparison under an injected ≥5 ms push delay,
    with exact per-phase means for both modes — and overlap must not
    lose to sync, with its wall step time sitting below the stacked
    phases (proof the comms hid under the compute). (The ≥1.5× speedup
    criterion is latched by the real bench record at the full shape;
    this harness run is shrunk for test time, so only sanity-bound it
    here.)"""
    value = bench.bench_paramserver_overlap(steps=8, n_in=128, hidden=128,
                                            batch=2048)
    stats = bench.PARAMSERVER_OVERLAP_STATS
    assert value > 0
    assert stats["push_delay_ms"] >= 5.0
    assert set(stats["phase_ms"]) == {"sync", "overlap"}
    for mode in ("sync", "overlap"):
        assert set(stats["phase_ms"][mode]) == {"compute", "d2h",
                                                "encode", "push"}
    assert stats["steps_per_sec_sync"] > 0
    assert stats["steps_per_sec_overlap"] > 0
    assert stats["speedup"] >= 1.0
    assert stats["wall_ms_overlap"] < sum(
        stats["phase_ms"]["overlap"].values())
    assert stats["hidden_ms_per_step"] > 0


def test_parallel_memory_bench_grid_shape_and_memory_win(bench):
    """Acceptance (ISSUE 13): the parallel_memory bench latches the
    {replicated, ws, fsdp} × {1-D, 2-D} grid into the --one record, and
    ZeRO's memory claim is a measured point — fsdp state bytes per device
    STRICTLY below replicated (both mesh ranks), ws in between or equal,
    with the peak gauge comparison riding along wherever the backend
    reports memory stats (the CPU harness reports None)."""
    value = bench.bench_parallel_memory(steps=4, n_in=64, hidden=128,
                                        classes=8, batch=32)
    stats = bench.PARALLEL_MEMORY_STATS
    assert value > 0
    grid = stats["grid"]
    assert set(grid) == {"replicated_1d", "ws_1d", "fsdp_1d",
                         "replicated_2d", "ws_2d", "fsdp_2d"}
    for cell in grid.values():
        assert cell["steps_per_sec"] > 0
        assert cell["state_bytes_per_device"] > 0
        assert set(cell) == {"steps_per_sec", "state_bytes_per_device",
                             "bytes_in_use", "peak_bytes"}
    for rank in ("1d", "2d"):
        repl = grid[f"replicated_{rank}"]["state_bytes_per_device"]
        ws = grid[f"ws_{rank}"]["state_bytes_per_device"]
        fsdp = grid[f"fsdp_{rank}"]["state_bytes_per_device"]
        assert fsdp < ws <= repl, (rank, fsdp, ws, repl)
        # ZeRO-1 shards 2/3 of the Adam state (m, v): a real dent,
        # not a rounding artifact
        assert ws < 0.7 * repl, (rank, ws, repl)
        # backend peak gauge: compared only where the backend reports it
        # (None on the CPU harness — the bench records, never fakes)
        p_repl = grid[f"replicated_{rank}"]["peak_bytes"]
        p_fsdp = grid[f"fsdp_{rank}"]["peak_bytes"]
        if p_repl is not None and p_fsdp is not None:
            assert p_fsdp <= p_repl
    assert 0.0 < stats["fsdp_vs_replicated_state_ratio"] < 0.6
    assert stats["model_extent"] == 2 and stats["devices"] == 8
    # under the conftest 8-device mesh the grid runs inline; the
    # virtual-CPU-mesh child path is the single-chip --one fallback
    assert stats["virtual_cpu_mesh"] is False


def test_serving_latency_bench_reports_tail_at_two_qps_points(bench):
    """Acceptance (ISSUE 9): the open-loop load generator drives the
    HTTP endpoint at two offered-QPS points and latches
    {p50_ms, p99_ms, achieved_qps, reject_rate, mean_batch_size} per
    point into the --one record's serving block. ISSUE 11: the same run
    latches a ``variants`` sub-block comparing {f32-nocache, bf16,
    bf16+cache (Zipfian mix)} at the SAME offered-QPS points."""
    value = bench.bench_serving_latency(qps_points=(30.0, 90.0),
                                        duration_s=1.0, pool_workers=16,
                                        cold_start=False)
    stats = bench.SERVING_STATS
    assert value > 0
    assert [p["offered_qps"] for p in stats["points"]] == [30.0, 90.0]
    for p in stats["points"]:
        assert p["sent"] > 0 and p["achieved_qps"] > 0
        assert 0.0 < p["p50_ms"] <= p["p99_ms"]
        assert 0.0 <= p["reject_rate"] <= 1.0
        assert p["mean_batch_size"] >= 1.0
        # ISSUE 10: every point latches which SLO rules were FIRING
        assert isinstance(p["alerts_fired"], list)
    # the bench's own contract: only the LOWEST point must be alert-free
    # (a loaded CI box may legitimately trip p99 at the high point)
    assert stats["points"][0]["alerts_fired"] == []
    assert stats["buckets"] == [1, 2, 4, 8, 16, 32]
    assert "serving_p99_breach/bench" in stats["alert_rules"]

    # ---- ISSUE 11 variants sub-block: shape pinned, same QPS points
    variants = stats["variants"]
    assert [v["variant"] for v in variants] == ["f32-nocache", "bf16",
                                                "bf16-cache"]
    for v in variants:
        assert v["precision"] in ("f32", "bf16")
        assert [p["offered_qps"] for p in v["points"]] == [30.0, 90.0]
        for p in v["points"]:
            for key in ("p50_ms", "p99_ms", "achieved_qps",
                        "cache_hit_rate", "mean_batch_size"):
                assert key in p, (v["variant"], key)
            assert p["achieved_qps"] > 0
            assert 0.0 < p["p50_ms"] <= p["p99_ms"]
    # f32-nocache IS the main sweep (one harness, one comparison basis)
    assert variants[0]["points"] is stats["points"]
    assert variants[0]["cache_hit_rate"] is None
    assert variants[1]["cache_size"] is None        # bf16, no cache
    # the Zipfian mix over a pool smaller than the cache must hit >0.5 —
    # every distinct payload misses at most once across the whole sweep
    assert variants[2]["zipfian"] is True
    assert variants[2]["cache_hit_rate"] > 0.5
    assert variants[2]["points"][-1]["cache_hit_rate"] > 0.5


def test_input_pipeline_bench_hides_etl(bench):
    """Acceptance (PR 6): the input-bound bench must show etl_ms reduced
    >= 5x with prefetch + device-put-ahead vs the synchronous path, and
    latch the comparison for the --one record."""
    value = bench.bench_input_pipeline(batch=32, n_batches=24,
                                       delay_ms=20.0, workers=8)
    stats = bench.INPUT_PIPELINE_STATS
    assert value > 0
    assert stats["etl_ms_sync"] >= 15.0          # the source really is slow
    assert stats["etl_reduction"] >= 5.0
    assert 0.0 < stats["overlap_ratio"] <= 1.0
    assert stats["prefetch_images_per_sec"] > stats["sync_images_per_sec"]


def test_control_loop_bench_latches_chaos_drill(bench):
    """Acceptance (ISSUE 16): the control-loop bench runs the chaos
    drill — slow served model + killed shard, both policies on the
    control plane's daemon — and latches {time_to_recover_s,
    actions_taken, alerts_fired} for the --one record, with the system
    actually back to an alert-free steady state (admission restored,
    shard restarted) with zero human intervention."""
    value = bench.bench_control_loop(timeout_s=45.0)
    stats = bench.CONTROL_LOOP_STATS
    assert value > 0
    assert stats["recovered"] is True
    assert stats["admission_restored"] is True
    assert stats["time_to_recover_s"] == round(value, 3)
    # at least: admission step + shard restart + admission restore
    assert stats["actions_taken"] >= 3
    assert stats["alerts_fired"] >= 1
    assert stats["time_to_admission_step_s"] > 0
    assert stats["time_to_shard_restart_s"] > 0


def test_cold_start_block_cold_vs_warm_cache_dir(bench):
    """ISSUE 12: the serving bench's cold-start mode runs the warmup in
    a child process twice against one shared compile-cache dir and
    latches {cold_compile_s, warm_compile_s, speedup} — the block the
    --one record embeds as ``cold_start``. The warm child's persistent-hit
    count equals its compile count (every warmup compile was a hit); the
    two children's wall clocks are reported, not ordered (two CPU children
    on a shared host: the chip's ``setup_s`` is where the saving shows)."""
    stats = bench._measure_cold_start(n_in=32, hidden=96, classes=10,
                                      buckets=(1, 2, 4))
    assert stats is bench.COLD_START_STATS       # the --one latch
    for key in ("cold_compile_s", "warm_compile_s", "speedup",
                "cold_persistent_hits", "warm_persistent_hits",
                "compiles", "buckets"):
        assert key in stats, key
    assert stats["buckets"] == [1, 2, 4]
    assert stats["compiles"] == 3                # one per bucket
    assert stats["cold_persistent_hits"] == 0    # fresh dir: all misses
    assert stats["warm_persistent_hits"] == 3    # all disk hits
    assert stats["cold_compile_s"] > 0 and stats["warm_compile_s"] > 0
    assert stats["speedup"] == pytest.approx(
        stats["cold_compile_s"] / stats["warm_compile_s"], rel=0.05)


def test_cold_start_children_are_cpu_pinned_and_a_failure_raises(
        bench, monkeypatch):
    """The cold/warm probe's children never contend for the chip
    (``JAX_PLATFORMS=cpu``) and never see a cache placed from outside
    (``JAX_COMPILATION_CACHE_DIR`` would warm the "cold" leg); a child
    that fails fails the config instead of quietly dropping its block."""
    seen = []

    def fail(cmd, env=None, **kw):
        seen.append(env)
        return subprocess.CompletedProcess(cmd, 1, "", "boom")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    monkeypatch.setattr(subprocess, "run", fail)
    bench.COLD_START_STATS.clear()
    with pytest.raises(RuntimeError, match="cold-start cold child failed"):
        bench._measure_cold_start()
    assert bench.COLD_START_STATS == {}
    [env] = seen
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "JAX_COMPILATION_CACHE_DIR" not in env
    assert env["DL4J_TPU_COMPILE_CACHE_DIR"]


def test_fleet_scrape_bench_latches_scrape_plane_stats(bench):
    """ISSUE 17: the scrape-plane bench polls K in-process replicas over
    real HTTP and latches {scrape_ms_p50, scrape_ms_p99, targets,
    merged_series, tick_overhead_ms, scrape_errors} — the ``--one``
    record's ``fleet_scrape`` block. At steady state against live
    loopback replicas every scrape must succeed."""
    value = bench.bench_fleet_scrape(replicas=2, ticks=6, warm_requests=2)
    stats = bench.FLEET_SCRAPE_STATS
    assert stats["scrape_ms_p99"] == value
    assert 0 < stats["scrape_ms_p50"] <= stats["scrape_ms_p99"]
    assert stats["targets"] == 2
    assert stats["scrape_errors"] == 0
    # the merged dump carries both replicas' serving series plus the
    # synthesized liveness and scrape-observability families
    assert stats["merged_series"] >= 3
    assert stats["tick_overhead_ms"] >= 0.0


def test_probe_overhead_bench_latches_interference_grid(bench):
    """ISSUE 19: the probe_overhead bench serves real traffic with the
    prober off, then per probe-QPS point with a live prober firing
    golden-set probes at the same replica, and latches {p50_off_ms,
    p99_off_ms, requests_per_point, points, max_p99_overhead_pct} — the
    ``--one`` record's ``probe_overhead`` block. Probes must have
    actually run (and come back ``ok``), and at the default ~1-4 probe
    QPS against local serving the p99 interference must stay under the
    5% budget (one retry absorbs scheduler noise on a loaded box)."""
    for attempt in (1, 2):
        value = bench.bench_probe_overhead(requests=2000,
                                           probe_qps=(2.0,))
        stats = bench.PROBE_OVERHEAD_STATS
        assert stats["max_p99_overhead_pct"] == value
        assert 0 < stats["p50_off_ms"] <= stats["p99_off_ms"]
        assert stats["requests_per_point"] == 2000
        [point] = stats["points"]
        assert point["probe_qps"] == 2.0
        assert point["probes"] >= 5             # the prober really fired
        assert point["last_outcome"] == "ok"    # and the answers matched
        assert 0 < point["p50_ms"] <= point["p99_ms"]
        if value < 5.0:
            break
        if attempt == 2:
            assert value < 5.0, stats
    # cache purity under load: real traffic's single entry, zero probe
    # entries (every probe bypassed the live response cache)
    assert stats["cache_entries_after"] == 1


def test_incident_overhead_bench_latches_capture_stats(bench):
    """ISSUE 20: the incident_overhead bench runs the chaos-drill shape
    twice — bare, then with a live IncidentRecorder capturing at the
    fire edge and persisting at resolve — and latches {p99_off_ms,
    p99_on_ms, overhead_pct, capture_ms_p99, bundle_bytes, incidents}
    — the ``--one`` record's ``incident_overhead`` block. The drill
    must really fire and resolve, its merged edges must persist as
    exactly ONE ``.dl4jinc`` bundle, and the recorder's serving-p99
    cost must stay inside the 1% acceptance budget (one retry absorbs
    scheduler noise on a loaded box)."""
    import glob
    import os
    for attempt in (1, 2):
        value = bench.bench_incident_overhead(requests=400)
        stats = bench.INCIDENT_OVERHEAD_STATS
        assert stats["overhead_pct"] == value
        assert 0 < stats["p50_off_ms"] <= stats["p99_off_ms"]
        assert 0 < stats["p50_on_ms"] <= stats["p99_on_ms"]
        assert stats["requests_per_phase"] == 400
        assert stats["fired"] and stats["resolved"]
        # the drill's merged edges are ONE incident, ONE bundle
        assert stats["incidents"] == 1
        assert stats["bundle_bytes"] > 0
        assert len(glob.glob(os.path.join(stats["dump_dir"],
                                          "*.dl4jinc"))) == 1
        assert stats["capture_ms_p99"] > 0   # a capture really ran
        if value <= 1.0:
            break
        if attempt == 2:
            assert value <= 1.0, stats


def test_lint_full_bench_latches_linter_cost(bench):
    """ISSUE 18: the lint_full bench times a whole-package tpulint run
    (all rules, shipped baseline) and latches {wall_s, files, rules,
    findings_new, findings_baselined} — the ``--one`` record's
    ``lint_full`` block, so linter cost regressions show up in the
    trajectory. The shipped package must come back clean (new == 0)."""
    value = bench.bench_lint_full(repeats=1)
    stats = bench.LINT_FULL_STATS
    assert stats["wall_s"] == value
    assert value > 0
    assert stats["files"] > 100             # the whole package, not a slice
    assert stats["rules"] == 14             # the full registry ran
    assert stats["findings_new"] == 0       # tier-1 invariant restated
    assert stats["findings_baselined"] >= 1  # the ratchet is in force

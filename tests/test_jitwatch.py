"""Compilation & memory observability (docs/OBSERVABILITY.md
"Compilation & memory"): monitored_jit accounting, the retrace-storm
detector (shape churn trips it, padded shapes don't), device-memory
gauges, the /profile step-anatomy report, and the ProfilerListener
close-on-error regression."""
import json
import threading
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                DataSet, Sgd)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.monitor import (TrainingHealthListener,
                                        TrainingHealthError, get_health,
                                        get_flight_recorder,
                                        get_jit_registry, get_registry,
                                        get_tracer, monitored_jit,
                                        profile_report, render_profile_text,
                                        sample_device_memory)
from deeplearning4j_tpu.monitor.jitwatch import wait_cost_captures


@pytest.fixture(autouse=True)
def _clean_monitor_state():
    """Storm/problem/flight state is process-global — isolate each test."""
    get_health().reset()
    get_flight_recorder().clear()
    get_jit_registry().drain_storms()
    yield
    get_health().reset()
    get_flight_recorder().clear()
    get_jit_registry().drain_storms()


def _net(seed=1):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=0.1)).activation("tanh").list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _ds(batch, rng):
    return DataSet(rng.normal(size=(batch, 4)).astype(np.float32),
                   np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])


# ---------------------------------------------------------- compile phases
def _kept(prefix, since=0, thread=None):
    return [r for r in get_tracer().kept()[since:]
            if r["name"].startswith(prefix)
            and thread in (None, r["tid"])]


class TestCompilePhases:
    """``watch_compile_phases``: every jax compile by phase, as kept spans
    (docs/OBSERVABILITY.md "Start-up")."""

    @pytest.fixture(autouse=True)
    def _monitor_off(self):
        import deeplearning4j_tpu.monitor as monitor
        monitor.set_enabled(False)
        # an earlier test's cost capture still queued on the worker would
        # land its span in this test's kept list
        assert wait_cost_captures(60.0)
        get_tracer().clear()
        yield
        monitor.set_enabled(True)

    def test_first_call_leaves_its_phases_under_compile_and_a_second_none(
            self):
        f = monitored_jit(lambda x: jnp.tanh(x) @ x.T, name="test/phases")
        f(jnp.ones((3, 5)))
        (compile_,) = _kept("compile/test/phases")
        assert compile_["cat"] == "compile" and compile_["args"][
            "persistent_hit"] is False
        assert len(get_tracer()) == 0          # the ring obeys the switch
        inside = [r for r in _kept("jax/")
                  if r["tid"] == compile_["tid"]
                  and compile_["start"] <= r["start"]
                  and r["end"] <= compile_["end"] + 1e-3]
        assert {r["name"] for r in inside} == {
            "jax/trace", "jax/lower", "jax/backend_compile"}
        assert all(r["cat"] == "compile" and r["args"]["fun_name"]
                   for r in inside)
        assert sum(r["end"] - r["start"] for r in inside) <= \
            compile_["end"] - compile_["start"] + 1e-3
        # spans made after the fact are siblings under the caller's span
        assert {r["parent_span_id"] for r in inside} == {
            compile_["parent_span_id"]}
        # the first call's cost capture is the worker's span, not the
        # second call's: let it land before counting
        assert wait_cost_captures(60.0)
        before = len(get_tracer().kept())
        f(jnp.ones((3, 5)))
        assert get_tracer().kept()[before:] == []

    def test_a_function_jitted_inside_a_jitted_function_is_traced_once(self):
        import jax
        inner = jax.jit(lambda x: jnp.sin(x) * 2)
        outer = monitored_jit(lambda x: inner(x) + jnp.cos(x),
                              name="test/nested")
        x = jnp.ones((7,))
        since = len(get_tracer().kept())
        outer(x)
        # not the inner function's trace, nor one per jnp op it calls
        me = threading.get_ident()   # the cost worker traces it once more
        (trace,) = _kept("jax/trace", since, me)
        (compile_,) = _kept("compile/test/nested", since)
        assert compile_["start"] <= trace["start"] < trace["end"] \
            <= compile_["end"]
        assert len(_kept("jax/backend_compile", since, me)) == 1

    def test_counters_move_only_with_a_compile(self, monkeypatch):
        from deeplearning4j_tpu.monitor import jitwatch
        monkeypatch.setattr(jitwatch, "_COST_CAPTURE", False)  # its own compile
        reg = get_registry()
        compiles = reg.counter("jax_compiles_total")
        seconds = {p: reg.counter("jax_compile_phase_seconds_total", phase=p)
                   for p in ("trace", "lower", "backend_compile")}
        f = monitored_jit(lambda x: x * 5 - 1, name="test/phase_counters")
        x = jnp.ones((9,))
        n, was = compiles.value, {p: c.value for p, c in seconds.items()}
        f(x)
        assert compiles.value == n + 1
        assert all(seconds[p].value > was[p] for p in seconds)
        n, was = compiles.value, {p: c.value for p, c in seconds.items()}
        f(x)
        assert compiles.value == n
        assert all(seconds[p].value == was[p] for p in seconds)

    def test_cost_capture_is_a_span_of_the_worker_thread(self):
        from deeplearning4j_tpu.monitor.jitwatch import wait_cost_captures
        f = monitored_jit(lambda x: x @ x, name="test/cost_span")
        f(jnp.ones((4, 4)))
        assert wait_cost_captures(30.0)
        (capture,) = _kept("jitwatch/cost_capture")
        assert capture["cat"] == "setup" and capture["args"] == {
            "fn": "test/cost_span"}
        assert capture["tid"] != threading.get_ident()
        # what it lowers again is on its thread and under its span
        again = [r for r in _kept("jax/") if r["tid"] == capture["tid"]]
        assert again and all(r["parent_span_id"] == capture["span_id"]
                             for r in again)

    def test_profile_startup_block_names_all_of_it(self):
        from deeplearning4j_tpu.monitor.jitwatch import wait_cost_captures
        rng = np.random.default_rng(0)
        dropped = get_tracer().kept_dropped   # the process's: clear() keeps it
        net = _net()
        net.fit(_ds(8, rng))
        assert wait_cost_captures(30.0)
        startup = profile_report()["startup"]
        (row,) = startup["inits"]
        assert row["network"] == "mln" and row["parameters"] == \
            net.num_params() and row["leaves"] == 4
        assert 0 < row["draw_s"] + row["place_s"] <= row["params_s"] + 1e-5
        assert row["params_s"] + row["updater_state_s"] <= row["seconds"]
        assert set(startup["compile_phase_s"]) == {
            "trace", "lower", "backend_compile", "cache_retrieval"}
        assert startup["compile_phase_s"]["trace"] > 0
        assert startup["compiles"] >= 1 and startup["cost_capture_s"] > 0
        assert startup["kept"] == len(get_tracer().kept())
        assert startup["kept_dropped"] == dropped
        assert startup["init_to_first_step_compiled_s"] >= row["seconds"]
        text = render_profile_text(profile_report())
        assert "# startup" in text and "init mln:" in text
        assert "first init to first step compiled" in text
        get_tracer().clear()
        assert profile_report()["startup"] == {}


# ------------------------------------------------------------ monitored_jit
class TestMonitoredJit:
    def test_counts_compiles_vs_calls_and_registry_series(self):
        f = monitored_jit(lambda x: x * 3, name="test/triple")
        for _ in range(4):
            f(jnp.ones((4,)))
        assert f.calls == 4 and f.compiles == 1
        f(jnp.ones((6,)))             # new shape -> second variant
        assert f.compiles == 2 and f.calls == 5
        assert f.cache_miss_ratio == pytest.approx(0.4)
        reg = get_registry()
        assert reg.counter("jit_calls_total", fn="test/triple").value == 5
        assert reg.counter("jit_compiles_total", fn="test/triple").value == 2
        # histogram observed one sample per compile
        _, _, n = reg.histogram("jit_compile_seconds",
                                fn="test/triple").state()
        assert n == 2

    def test_compile_span_lands_on_trace_with_delta(self):
        f = monitored_jit(lambda x: x + 1, name="test/span_fn")
        f(jnp.ones((3,)))
        f(jnp.ones((5,)))
        evs = [e for e in get_tracer().events()
               if e["name"] == "compile/test/span_fn"]
        assert len(evs) >= 2
        assert evs[0]["args"]["signature_delta"] == "first compile"
        assert "float32[3]" in evs[1]["args"]["signature_delta"]
        assert "float32[5]" in evs[1]["args"]["signature_delta"]

    def test_cost_analysis_captured_per_variant(self):
        from deeplearning4j_tpu.monitor.jitwatch import wait_cost_captures
        f = monitored_jit(lambda a, b: a @ b, name="test/matmul")
        f(jnp.ones((8, 8)), jnp.ones((8, 8)))
        assert wait_cost_captures()    # capture is async by design
        row = get_jit_registry().table()["test/matmul"]
        assert row["flops"] > 0
        assert row["variants"] == 1

    def test_decorator_factory_form_and_wraps(self):
        @monitored_jit(name="test/deco", donate_argnums=(0,))
        def bump(x):
            """bump doc"""
            return x + 1
        out = bump(jnp.zeros((2,)))
        assert float(out.sum()) == 2.0
        assert bump.compiles == 1
        assert bump.__doc__ == "bump doc"

    def test_results_identical_to_plain_call(self):
        f = monitored_jit(lambda x: (x ** 2).sum(), name="test/sq")
        x = jnp.arange(5.0)
        assert float(f(x)) == float((x ** 2).sum())


# ------------------------------------------------------- retrace detection
class TestRetraceStorm:
    def test_shape_churn_fit_trips_storm_and_flight_event(self):
        net = _net()
        health = TrainingHealthListener(action="warn")
        net.set_listeners(health)
        rng = np.random.default_rng(0)
        for batch in (16, 17, 18, 19):   # ragged tails: 4 compiles
            net.fit(_ds(batch, rng))
        assert net._jit_step.compiles == 4
        problems = get_health().snapshot()["problems"]
        assert any("retrace" in p and "mln/step" in p for p in problems)
        storms = [e for e in get_flight_recorder().events()
                  if e["event"] == "retrace_storm" and e["fn"] == "mln/step"]
        assert storms, "no retrace_storm flight event"
        # the forensic payload: the delta names the argument whose shape
        # churned (the feature/label batch dimension)
        assert "->" in storms[0]["signature_delta"]
        assert "float32[1" in storms[0]["signature_delta"]
        # the listener drained the storm and applied its action
        assert any(kind == "retrace" for kind, _, _ in health.triggered)

    def test_padded_fit_records_exactly_one_compile_and_no_storm(self):
        net = _net(seed=2)
        net.set_listeners(TrainingHealthListener(action="warn"))
        rng = np.random.default_rng(1)
        for _ in range(4):               # fixed shape: bucketed/padded
            net.fit(_ds(16, rng))
        assert net._jit_step.compiles == 1
        assert net._jit_step.calls == 4
        problems = get_health().snapshot()["problems"]
        assert not any("mln/step" in p for p in problems)
        assert not [e for e in get_flight_recorder().events()
                    if e["event"] == "retrace_storm"
                    and e["fn"] == "mln/step"]

    def test_raise_action_applies_to_drained_storm(self):
        lst = TrainingHealthListener(action="raise")   # armed first:
        # listeners only act on storms that fire while they watch
        f = monitored_jit(lambda x: x * 2, name="test/churn")
        for n in (3, 4, 5):              # 3 compiles within the window
            f(jnp.ones((n,)))
        with pytest.raises(TrainingHealthError) as ei:
            lst.iteration_done(object(), 0, 0.5)
        assert ei.value.kind == "retrace"

    def test_storm_from_another_fit_thread_is_requeued_not_fired(self):
        """A listener must not halt ITS model for a storm that fired on a
        different fit thread (= a different model's training); the storm is
        requeued so the owning thread's listener still sees it."""
        import threading
        bystander = TrainingHealthListener(action="raise")

        def churn():
            f = monitored_jit(lambda x: x * 2, name="test/other_thread")
            for n in (3, 4, 5):
                f(jnp.ones((n,)))

        t = threading.Thread(target=churn)
        t.start()
        t.join(30)
        # the storm fired on the worker thread; the main-thread listener
        # must neither raise nor destructively consume it
        bystander.iteration_done(object(), 0, 0.5)
        assert not bystander.triggered
        pending = get_jit_registry().drain_storms()
        assert [s["fn"] for s in pending] == ["test/other_thread"]

    def test_watch_retrace_false_ignores_storms(self):
        lst = TrainingHealthListener(action="raise", watch_retrace=False)
        f = monitored_jit(lambda x: x * 2, name="test/churn2")
        for n in (3, 4, 5):
            f(jnp.ones((n,)))
        lst.iteration_done(object(), 0, 0.5)   # no raise
        assert not lst.triggered
        get_jit_registry().drain_storms()      # leave no storm behind


# --------------------------------------------------------- memory + profile
class TestMemoryAndProfile:
    def test_sample_device_memory_graceful_and_counts_buffers(self):
        keep = jnp.ones((16,))
        out = sample_device_memory()       # CPU: no allocator stats
        assert out["live_buffers"] is not None and out["live_buffers"] >= 1
        assert get_registry().gauge("device_live_buffers").value >= 1
        del keep

    def test_profile_endpoint_shows_three_named_fns(self):
        from deeplearning4j_tpu.ui import UIServer, InMemoryStatsStorage
        net = _net(seed=3)
        rng = np.random.default_rng(2)
        ds = _ds(16, rng)
        net.fit(ds)                                   # mln/step
        net.output(ds.features)                       # mln/output
        net.score(ds)                                 # mln/score
        from deeplearning4j_tpu.monitor.jitwatch import wait_cost_captures
        assert wait_cost_captures()    # flops land asynchronously
        ui = UIServer(port=0)
        ui.attach(InMemoryStatsStorage())
        port = ui.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/profile", timeout=10) as r:
                rep = json.loads(r.read())
            named = {n: row for n, row in rep["jit"].items()
                     if n in ("mln/step", "mln/output", "mln/score")}
            assert len(named) == 3
            for row in named.values():
                assert row["compiles"] >= 1
                assert row["compile_seconds"] > 0
                assert row["flops"] > 0
            assert rep["steps"]["iterations"] >= 1
            assert rep["memory"]["live_buffers"] is not None
            # text rendering serves too
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/profile?format=text",
                    timeout=10) as r:
                text = r.read().decode()
            assert "mln/step" in text and "# device memory" in text
            # /metrics scrape carries the jit + memory series
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                metrics = r.read().decode()
            assert 'jit_compiles_total{fn="mln/step"}' in metrics
            assert "device_live_buffers" in metrics
        finally:
            ui.stop()

    def test_profile_report_and_text_render_locally(self):
        f = monitored_jit(lambda x: x - 1, name="test/report")
        f(jnp.ones((2,)))
        rep = profile_report()
        assert "test/report" in rep["jit"]
        text = render_profile_text(rep)
        assert "test/report" in text


# --------------------------------------------- ProfilerListener error seam
class _Exploder:
    """Raises out of the fit loop mid-window (listener-bus member)."""
    def __init__(self, at_iteration):
        self.at = at_iteration

    def iteration_done(self, model, iteration, score):
        if iteration >= self.at:
            raise RuntimeError("boom")

    def on_epoch_start(self, model, epoch):
        pass

    def on_epoch_end(self, model, epoch):
        pass


def test_profiler_listener_closes_when_fit_raises(tmp_path):
    """Regression (PR 5 satellite): a fit that raises mid-trace-window must
    close the process-global jax.profiler trace — leaking it breaks the
    NEXT start_trace."""
    from deeplearning4j_tpu.utils.profiling import ProfilerListener
    net = _net(seed=4)
    prof = ProfilerListener(str(tmp_path / "t1"), start_iteration=1,
                            num_iterations=100)   # window never fills
    net.set_listeners(prof, _Exploder(at_iteration=2))
    rng = np.random.default_rng(3)
    ds = _ds(16, rng)
    with pytest.raises(RuntimeError, match="boom"):
        for _ in range(6):
            net.fit(ds)
    assert not prof._active, "jax.profiler trace leaked past the raise"
    # the proof the leak is fixed: a fresh trace window starts cleanly
    import jax
    jax.profiler.start_trace(str(tmp_path / "t2"))
    jax.profiler.stop_trace()

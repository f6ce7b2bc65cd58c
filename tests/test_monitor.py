"""Unified monitor subsystem (deeplearning4j_tpu/monitor/ —
docs/OBSERVABILITY.md): registry semantics + concurrency, tracer export,
health watchdog, endpoint round-trips on a live UIServer, the
ParamServerMetrics facade regression, and the monitor CLI snapshot."""
import http.client
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                Sgd, DataSet)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.monitor import (MetricsRegistry, Tracer,
                                        TrainingHealthListener,
                                        TrainingHealthError, get_registry,
                                        get_tracer, get_health)
from deeplearning4j_tpu.ui import UIServer, InMemoryStatsStorage


def _net(seed=1, lr=0.1):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=lr)).activation("tanh")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _ds(seed=0, n=16):
    rng = np.random.default_rng(seed)
    return DataSet(rng.normal(size=(n, 4)).astype(np.float32),
                   np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])


def _get(port, path):
    return urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                  timeout=10)


# ----------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_gauge_histogram_and_render(self):
        reg = MetricsRegistry()
        reg.counter("reqs_total", "requests", route="/a").inc(3)
        reg.gauge("temp", "temperature").set(21.5)
        reg.histogram("lat_ms", "latency", op="push").observe(1.0)
        reg.histogram("lat_ms", op="push").observe(100.0)
        text = reg.render_prometheus()
        assert '# TYPE reqs_total counter' in text
        assert 'reqs_total{route="/a"} 3' in text
        assert "temp 21.5" in text
        assert '# TYPE lat_ms histogram' in text
        assert 'lat_ms_count{op="push"} 2' in text
        assert 'le="+Inf"' in text
        # cumulative buckets are monotone and end at n
        counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                  if line.startswith("lat_ms_bucket")]
        assert counts == sorted(counts) and counts[-1] == 2

    def test_same_child_returned_and_type_conflict_raises(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", peer="0")
        b = reg.counter("x_total", peer="0")
        assert a is b
        assert reg.counter("x_total", peer="1") is not a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")

    def test_threaded_increments_sum_exactly(self):
        reg = MetricsRegistry()
        c = reg.counter("hits_total")
        h = reg.histogram("h_ms")
        n_threads, per_thread = 8, 1000

        def work():
            for _ in range(per_thread):
                c.inc()
                h.observe(1.0)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * per_thread
        assert h.summary()["n"] == n_threads * per_thread

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("a_total", role="x").inc(2)
        reg.histogram("b_ms").observe(5.0)
        snap = reg.snapshot()
        assert snap["a_total"][0] == {"labels": {"role": "x"},
                                      "type": "counter", "value": 2.0}
        assert snap["b_ms"][0]["summary"]["n"] == 1.0

    def test_dump_json_roundtrip_rerenders_identically(self):
        """dump() is the OP_TELEMETRY wire form: sending it through JSON
        and re-rendering with render_prometheus_dump must reproduce the
        local exposition byte for byte; extra labels (the fleet's
        ``worker``) merge into every child."""
        from deeplearning4j_tpu.monitor import render_prometheus_dump
        reg = MetricsRegistry()
        reg.counter("reqs_total", "requests", route="/a").inc(3)
        reg.gauge("temp", "temperature").set(21.5)
        reg.histogram("lat_ms", "latency", op="push").observe(1.0)
        text = reg.render_prometheus()
        wire = json.loads(json.dumps(reg.dump()))
        assert render_prometheus_dump(wire) == text
        relabeled = render_prometheus_dump(wire, {"worker": "w9"})
        assert 'reqs_total{route="/a",worker="w9"} 3' in relabeled
        assert 'temp{worker="w9"} 21.5' in relabeled
        assert 'lat_ms_count{op="push",worker="w9"} 1' in relabeled


# ------------------------------------------------------------------- tracer
class TestTracer:
    def test_export_is_valid_chrome_trace_with_nesting(self):
        tr = Tracer()
        with tr.span("outer", cat="test", k=1):
            with tr.span("inner", cat="test"):
                time.sleep(0.002)
        # valid JSON round trip with the trace-event required fields
        doc = json.loads(json.dumps(tr.export()))
        evs = doc["traceEvents"]
        assert len(evs) == 2
        for e in evs:
            assert e["ph"] == "X"
            assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
        inner = next(e for e in evs if e["name"] == "inner")
        outer = next(e for e in evs if e["name"] == "outer")
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        assert outer["args"]["k"] == 1
        # trace-context stamping: both spans share one trace, the inner
        # span parents to the outer one, the root has no parent
        assert inner["args"]["trace_id"] == outer["args"]["trace_id"]
        assert inner["args"]["parent_span_id"] == outer["args"]["span_id"]
        assert "parent_span_id" not in outer["args"]

    def test_ring_buffer_bounded(self):
        tr = Tracer(capacity=10)
        for i in range(25):
            with tr.span(f"s{i}"):
                pass
        evs = tr.export()["traceEvents"]
        assert len(evs) == 10
        assert evs[-1]["name"] == "s24"  # newest survive

    def test_ring_overflow_counts_drops(self):
        """Satellite: ring-buffer eviction is no longer silent — drops
        land on the instance AND in the registry's
        tracer_spans_dropped_total, which /metrics exposes."""
        counter = get_registry().counter(
            "tracer_spans_dropped_total",
            "spans evicted from the trace ring buffer")
        before = counter.value
        tr = Tracer(capacity=5)
        for i in range(12):
            with tr.span(f"s{i}"):
                pass
        assert tr.dropped == 7
        assert counter.value - before == 7
        assert "tracer_spans_dropped_total" in \
            get_registry().render_prometheus()

    def test_remote_parent_joins_trace(self):
        """span(parent=ctx) with a context that 'arrived over the wire'
        records a child of the REMOTE span — the server half of the
        propagation story, without a socket."""
        from deeplearning4j_tpu.monitor import SpanContext
        client_tr, server_tr = Tracer(), Tracer()
        with client_tr.span("rpc") as ctx:
            wire = SpanContext(ctx.trace_id, ctx.span_id)   # 16-byte header
            with server_tr.span("handle", parent=wire):
                pass
        handle = server_tr.events()[0]
        rpc = client_tr.events()[0]
        assert handle["args"]["trace_id"] == rpc["args"]["trace_id"]
        assert handle["args"]["parent_span_id"] == rpc["args"]["span_id"]

    @pytest.mark.parametrize("cat,kept", [("setup", True), ("compile", True),
                                          ("train", False), ("host", False)])
    def test_setup_and_compile_spans_are_kept_with_the_monitor_off(
            self, cat, kept, monkeypatch):
        """The third sink: the ring obeys the switch, the kept list only
        the span's category."""
        from deeplearning4j_tpu.monitor import tracer as tracer_mod
        monkeypatch.setattr(tracer_mod, "_ENABLED", False)
        tr = Tracer()
        with tr.span("work", cat=cat, n=3):
            pass
        tr.record_complete("after/the/fact", time.perf_counter() - 0.5, 0.5,
                           cat=cat)
        assert tr.events() == []
        names = [r["name"] for r in tr.kept()]
        assert names == (["work", "after/the/fact"] if kept else [])

    def test_kept_record_is_on_perf_counter_under_the_open_span(self):
        tr = Tracer()
        t0 = time.perf_counter()
        outer = tr.span("init", cat="setup", network="mln")
        with outer as ctx:
            with tr.span("init/params", cat="setup"):
                time.sleep(0.002)
            tr.record_complete("jax/trace", time.perf_counter() - 0.001,
                               0.001, cat="compile", fun_name="f")
            outer.note(leaves=4)
        t1 = time.perf_counter()
        child, phase, parent = tr.kept()
        assert parent["name"] == "init" and parent["parent_span_id"] == 0
        assert parent["args"] == {"network": "mln", "leaves": 4}
        assert t0 <= parent["start"] <= child["start"] < child["end"] \
            <= parent["end"] <= t1
        assert child["end"] - child["start"] >= 0.002
        for r in (child, phase):
            assert r["parent_span_id"] == parent["span_id"] == ctx.span_id
            assert r["trace_id"] == parent["trace_id"] == ctx.trace_id
            assert r["tid"] == threading.get_ident()
        assert phase["args"] == {"fun_name": "f"} and phase["cat"] == "compile"

    def test_kept_list_is_bounded_keeps_the_first_and_counts_the_rest(self):
        tr = Tracer(kept_capacity=4)
        for i in range(7):
            with tr.span(f"s{i}", cat="setup"):
                pass
        assert [r["name"] for r in tr.kept()] == ["s0", "s1", "s2", "s3"]
        assert tr.kept_dropped == 3
        assert len(tr) == 7               # the ring took them all

    def test_export_carries_a_kept_span_once_and_clear_clears_both(self):
        tr = Tracer(capacity=2)
        with tr.span("init", cat="setup"):
            pass
        both = tr.export()["traceEvents"]     # in the ring and kept: once
        assert [e["name"] for e in both] == ["init"]
        for i in range(3):                    # the ring wraps, start-up stays
            with tr.span(f"step{i}", cat="train"):
                pass
        events = tr.export()["traceEvents"]
        assert [e["name"] for e in events] == ["init", "step1", "step2"]
        first = events[0]
        assert first == both[0]               # the ring's ts, ids in hex
        assert first["cat"] == "setup" and first["ph"] == "X"
        assert int(first["args"]["span_id"], 16) == tr.kept()[0]["span_id"]
        tr.clear()
        assert tr.kept() == [] and tr.export()["traceEvents"] == []

    def test_a_kept_span_costs_microseconds(self, monkeypatch):
        """What the always-written sink costs beside a plain span, monitor
        off (the benchmark's state): a lock and a dict per kept span,
        nothing per plain one. Printed for PERF.md; held to a loose bound."""
        from deeplearning4j_tpu.monitor import tracer as tracer_mod
        monkeypatch.setattr(tracer_mod, "_ENABLED", False)
        n = 2000

        def per_span_us(cat):
            tr = Tracer(kept_capacity=n)
            t = time.perf_counter()
            for _ in range(n):
                with tr.span("s", cat=cat):
                    pass
            return (time.perf_counter() - t) / n * 1e6, tr

        per_span_us("train")                  # warm the annotation's import
        plain = min(per_span_us("train")[0] for _ in range(3))
        kept, tr = min((per_span_us("setup") for _ in range(3)),
                       key=lambda found: found[0])
        assert len(tr.kept()) == n and tr.kept_dropped == 0
        print(f"plain span {plain:.2f} us, kept span {kept:.2f} us")
        assert kept - plain < 100.0

    def test_fit_produces_nested_step_spans(self):
        tracer = get_tracer()
        tracer.clear()
        net = _net()
        ds = _ds()
        for _ in range(3):
            net.fit(ds)
        evs = tracer.export()["traceEvents"]
        steps = [e for e in evs if e["name"] == "step"]
        epochs = [e for e in evs if e["name"] == "epoch"]
        assert len(steps) >= 3 and epochs
        # every step nests inside some epoch span
        for st in steps:
            assert any(ep["ts"] <= st["ts"] and
                       st["ts"] + st["dur"] <= ep["ts"] + ep["dur"] + 1
                       for ep in epochs)


# ---------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_bounded_ordered_and_dropped_counted(self):
        from deeplearning4j_tpu.monitor import FlightRecorder
        fr = FlightRecorder(capacity=4)
        for i in range(7):
            fr.record("e", i=i)
        evs = fr.events()
        assert len(evs) == 4 and fr.dropped == 3
        assert [e["i"] for e in evs] == [3, 4, 5, 6]         # newest win
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)                          # provable order

    def test_dump_jsonl_and_nonserializable_degrade(self, tmp_path):
        from deeplearning4j_tpu.monitor import FlightRecorder
        fr = FlightRecorder()
        fr.record("weird", obj=object())     # degrades to repr, not raise
        fr.record("plain", x=1)
        path = fr.dump(path=str(tmp_path / "fr.jsonl"))
        rows = [json.loads(line)
                for line in open(path).read().splitlines()]
        assert [r["event"] for r in rows] == ["weird", "plain"]
        assert "object" in rows[0]["obj"]
        assert fr.last_dump_path == path

    def test_halt_dumps_flight_recorder(self, tmp_path, monkeypatch):
        """The black-box contract: a TrainingHealthListener halt persists
        the event log to disk (DL4J_TPU_FLIGHT_DIR) without being asked."""
        from deeplearning4j_tpu.monitor import get_flight_recorder
        monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(tmp_path))
        rec = get_flight_recorder()
        rec.clear()
        rec.record("before_halt", marker=1)
        get_health().record_halt("test halt")
        try:
            dumps = list(tmp_path.glob("flightrec-*.jsonl"))
            assert dumps, "halt must leave a JSONL dump behind"
            rows = [json.loads(line) for line
                    in dumps[0].read_text().splitlines()]
            kinds = [r["event"] for r in rows]
            assert "before_halt" in kinds and kinds[-1] == "halt"
            assert rows[-1]["reason"] == "test halt"
        finally:
            get_health().reset()
            rec.clear()


# ------------------------------------------------------------------- health
class TestHealthListener:
    def test_nan_trigger_warn_records(self):
        lst = TrainingHealthListener(action="warn")
        net = _net()
        lst.iteration_done(net, 0, 0.5)
        lst.iteration_done(net, 1, float("nan"))
        assert [t[0] for t in lst.triggered] == ["nan"]

    def test_divergence_trigger_and_raise_action(self):
        lst = TrainingHealthListener(action="raise", divergence_window=3,
                                     divergence_factor=2.0)
        net = _net()
        for i, s in enumerate((1.0, 1.1, 1.05)):
            lst.iteration_done(net, i, s)
        with pytest.raises(TrainingHealthError, match="exceeds"):
            lst.iteration_done(net, 3, 5.0)

    def test_stall_trigger(self):
        lst = TrainingHealthListener(action="warn", stall_timeout=0.01)
        net = _net()
        lst.iteration_done(net, 0, 1.0)
        time.sleep(0.05)
        lst.iteration_done(net, 1, 1.0)
        assert [t[0] for t in lst.triggered] == ["stall"]

    def test_param_nan_scan(self):
        lst = TrainingHealthListener(action="warn", check_params_every=1)
        net = _net()
        net.params["0"]["W"] = np.asarray(net.params["0"]["W"]).copy()
        net.params["0"]["W"][0, 0] = np.inf
        lst.iteration_done(net, 0, 0.5)
        assert [t[0] for t in lst.triggered] == ["nan"]

    def test_halt_action_stops_fit(self):
        class HaltNow(TrainingHealthListener):
            def iteration_done(self, model, iteration, score):
                self._fire(model, "nan", iteration, "injected halt")

        net = _net()
        net.set_listeners(HaltNow(action="halt"))
        net.fit(_ds(), epochs=5)      # halts after the first minibatch
        assert net.iteration_count == 1
        assert get_health().snapshot()["halted"]
        # a fresh fit() supersedes the halt: without the listener the run
        # completes and /healthz goes healthy again
        net.set_listeners()
        net.fit(_ds(), epochs=2)
        assert net.iteration_count == 3
        assert not net.halt_requested
        assert get_health().snapshot()["halted"] is None
        get_health().reset()

    def test_bad_action_rejected(self):
        with pytest.raises(ValueError, match="action"):
            TrainingHealthListener(action="explode")


# ---------------------------------------------------------------- endpoints
class TestEndpoints:
    def test_metrics_healthz_trace_roundtrip(self):
        get_health().reset()
        net = _net()
        ds = _ds()
        for _ in range(3):
            net.fit(ds)

        # paramserver traffic so /metrics carries push/pull histograms from
        # the same shared registry
        from deeplearning4j_tpu.paramserver import (ParameterServer,
                                                    ParameterServerClient)
        with ParameterServer(port=0) as srv:
            with ParameterServerClient(srv.address) as cli:
                cli.init_params(np.zeros(4, np.float32))
                cli.pull()

        srv_ui = UIServer(port=0)
        srv_ui.attach(InMemoryStatsStorage())
        port = srv_ui.start()
        try:
            with _get(port, "/metrics") as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
            assert "training_iterations_total" in text
            assert "training_score" in text
            assert 'paramserver_pull_ms_count{role="client"}' in text
            assert 'paramserver_push_ms_count{role="server"}' in text
            assert 'paramserver_pull_ms_bucket{role="client",le=' in text
            # the fit loop wraps its iterator in PrefetchDataSetIterator,
            # whose wait is this series (dataset_next_ms is
            # AsyncDataSetIterator's own)
            assert "input_wait_seconds_count" in text

            with _get(port, "/healthz") as r:
                h = json.loads(r.read())
            assert h["status"] == "ok" and h["healthy"]
            assert h["last_iteration_age_s"] is not None

            with _get(port, "/trace") as r:
                doc = json.loads(r.read())
            names = {e["name"] for e in doc["traceEvents"]}
            assert "step" in names and "ps/pull" in names
            assert {"epoch", "fit/next_batch", "fit/prepare", "fit/resolve",
                    "input/transform", "input/put_ahead"} <= names
        finally:
            srv_ui.stop()

    def test_healthz_flips_unhealthy_on_nan_score(self):
        get_health().reset()
        srv_ui = UIServer(port=0)
        srv_ui.attach(InMemoryStatsStorage())
        port = srv_ui.start()
        try:
            get_health().record_iteration(5, 0.4)
            with _get(port, "/healthz") as r:
                assert json.loads(r.read())["healthy"]
            # inject a NaN score the way the fit loop reports one
            get_health().record_iteration(6, float("nan"))
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(port, "/healthz")
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body["nan"] and body["status"] == "unhealthy"
        finally:
            srv_ui.stop()
            get_health().reset()

    def test_post_content_length_cap_413(self):
        srv_ui = UIServer(port=0)
        srv_ui.attach(InMemoryStatsStorage())
        port = srv_ui.start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            conn.putrequest("POST", "/remote")
            conn.putheader("Content-Length", str(64 << 20))  # 64 MB claim
            conn.putheader("Content-Type", "application/json")
            conn.endheaders()
            # server must answer 413 WITHOUT waiting for the body
            resp = conn.getresponse()
            assert resp.status == 413
            assert b"limit" in resp.read()
            conn.close()
            # negative Content-Length: reject, never read(-1) (which would
            # block until the client closes the socket)
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            conn.putrequest("POST", "/remote")
            conn.putheader("Content-Length", "-1")
            conn.endheaders()
            assert conn.getresponse().status == 400
            conn.close()
        finally:
            srv_ui.stop()

    def test_host_parameter(self):
        srv_ui = UIServer(port=0, host="0.0.0.0")
        srv_ui.attach(InMemoryStatsStorage())
        port = srv_ui.start()
        try:
            with _get(port, "/healthz"):
                pass  # reachable via loopback while bound wide
        finally:
            srv_ui.stop()


# ------------------------------------------------------- facade regression
def test_paramserver_metrics_snapshot_shape_unchanged():
    """The registry migration must not change the snapshot() contract the
    listener bus and OP_STATS serve."""
    from deeplearning4j_tpu.paramserver import ParamServerMetrics
    from deeplearning4j_tpu.paramserver.metrics import COUNTERS
    m = ParamServerMetrics()
    m.record_push(3.0, 100)
    m.record_pull(1.0, 400)
    m.add("retries")
    snap = m.snapshot()
    assert set(snap) == {"counters", "push_latency", "pull_latency"}
    assert set(snap["counters"]) == set(COUNTERS)
    assert snap["counters"]["pushes"] == 1
    assert snap["counters"]["pull_bytes"] == 400
    assert snap["counters"]["retries"] == 1
    assert {"mean_ms", "p50_ms", "p95_ms", "p99_ms", "max_ms",
            "n"} == set(snap["push_latency"])
    # per-instance isolation: a second facade starts from zero even though
    # both mirror into the same shared registry role
    m2 = ParamServerMetrics()
    assert m2.snapshot()["counters"]["pushes"] == 0


def test_transport_metrics_per_peer():
    """2-rank loopback mesh: gather/broadcast land per-peer byte counters
    and latency histograms in the shared registry."""
    from test_transport import _mesh
    chans = _mesh(2)
    try:
        a, b = chans
        t = threading.Thread(target=lambda: b.exchange(b"y" * 64),
                             daemon=True)
        t.start()
        got = a.exchange(b"x" * 64)
        t.join(10)
        assert got == [b"y" * 64]
        snap = get_registry().snapshot()
        rows = snap["transport_bytes_total"]
        dirs = {(r["labels"]["direction"], r["labels"]["peer"])
                for r in rows}
        assert ("out", "0") in dirs or ("out", "1") in dirs
        assert ("in", "0") in dirs or ("in", "1") in dirs
        assert any(r["summary"]["n"] >= 1
                   for r in snap["transport_recv_ms"])
    finally:
        for c in chans:
            c.close()


# ---------------------------------------------------------------------- CLI
def test_monitor_cli_local_snapshot(capsys):
    from deeplearning4j_tpu.main import main
    get_registry().counter("cli_probe_total").inc()
    assert main(["monitor"]) == 0
    out = capsys.readouterr().out
    assert "# TYPE cli_probe_total counter" in out
    assert '# health {"status"' in out


def test_monitor_cli_remote_and_json(tmp_path, capsys):
    from deeplearning4j_tpu.main import main
    get_health().reset()
    get_health().record_iteration(1, 0.9)
    srv_ui = UIServer(port=0)
    srv_ui.attach(InMemoryStatsStorage())
    port = srv_ui.start()
    try:
        trace_out = tmp_path / "trace.json"
        assert main(["monitor", "--url", f"127.0.0.1:{port}",
                     "--trace-out", str(trace_out)]) == 0
        out = capsys.readouterr().out
        assert "# TYPE" in out
        assert json.loads(trace_out.read_text())["traceEvents"] is not None

        assert main(["monitor", "--format", "json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["health"]["last_score"] == 0.9
        assert "metrics" in doc
    finally:
        srv_ui.stop()

"""Production inference serving tier (serving/ — docs/SERVING.md).

Acceptance (ISSUE 9): concurrent clients with mixed input shapes get
bit-identical results to the unbatched forward while the model's jitted
output compiles exactly ``len(buckets)`` times with zero
``retrace_storm`` flight events; 429 under saturation; graceful drain
drops zero accepted requests; deadlines shed queued work as 504; the
``serving`` block lands on ``/profile``; serving locks run clean under
lockwatch and every observed edge is statically derivable.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                Sgd)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.monitor import (get_flight_recorder, get_health,
                                        get_jit_registry, get_registry,
                                        profile_report,
                                        render_profile_text)
from deeplearning4j_tpu.serving import (ContinuousBatcher,
                                        DeadlineExceededError,
                                        InferenceServer, ModelRegistry,
                                        ModelNotFoundError,
                                        OverloadedError)


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


def _net(seed=1, n_in=6, n_out=4):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=0.1)).activation("tanh").list()
            .layer(DenseLayer(n_in=n_in, n_out=16))
            .layer(OutputLayer(n_in=16, n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


class StubModel:
    """Duck-typed served model: optional per-flush delay, call log."""

    def __init__(self, delay_s=0.0, n_out=2):
        self.delay_s = delay_s
        self.n_out = n_out
        self.calls = []
        self._lock = threading.Lock()

    def output(self, x, mask=None):
        with self._lock:
            self.calls.append((np.asarray(x).shape,
                               None if mask is None
                               else np.asarray(mask).copy()))
        if self.delay_s:
            time.sleep(self.delay_s)
        x = np.asarray(x)
        return np.full((x.shape[0], self.n_out),
                       float(x.reshape(x.shape[0], -1)[:, 0].sum()),
                       np.float32)


def _post(url, doc, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode("utf-8"))
    except urllib.error.HTTPError as e:
        body = json.loads(e.read().decode("utf-8"))
        headers = dict(e.headers)
        e.close()
        body["_headers"] = headers
        return e.code, body


def _storm_events():
    return [e for e in get_flight_recorder().events()
            if e.get("event") == "retrace_storm"]


# --------------------------------------------------------- THE acceptance
def test_concurrent_mixed_shapes_bit_equal_and_closed_signature_set():
    """4 concurrent clients, request sizes churning 1..4, buckets (4, 8):
    every per-request result is BIT-identical to the unbatched forward of
    a twin network, the jitted output compiles exactly len(buckets)
    times, and zero retrace_storm flight events fire."""
    net = _net(seed=11)
    ref = _net(seed=11)       # same seed -> same params; computes the
    # references OUTSIDE the served net so its compile count stays pure
    for p in ("0", "1"):
        np.testing.assert_array_equal(
            np.asarray(net.params[p]["W"]), np.asarray(ref.params[p]["W"]))
    registry = ModelRegistry()
    registry.register("accept", net, batch_buckets=(4, 8), linger_ms=2.0,
                      input_shape=(6,), warmup=True)
    # warmup pre-compiled BOTH buckets; churn must now add zero compiles
    results = {}
    lock = threading.Lock()

    def client(tid):
        rng = np.random.default_rng(tid)
        for i in range(6):
            x = rng.normal(size=(int(rng.integers(1, 5)), 6)) \
                .astype(np.float32)
            fut = registry.submit("accept", x)
            with lock:
                results[(tid, i)] = (x, fut)

    threads = [threading.Thread(target=client, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    outs = {k: fut.result(timeout=30) for k, (_, fut) in results.items()}
    # the serving pins FIRST: exactly len(buckets) compiles of the served
    # net's output wrapper, zero retrace_storm flight events under churn
    wrapper = net._jit_output[(False, False)]
    assert wrapper.compiles == 2, (
        f"expected exactly len(buckets)=2 compiles, got {wrapper.compiles}")
    assert _storm_events() == []
    registry.close_all()
    # THEN the bit-equality references: the twin net's unbatched forwards
    # run at the raw churning sizes (which would trip ITS wrapper's storm
    # detector — that's the exact failure mode serving's buckets close,
    # and why the references come after the zero-storm assertion)
    for k, (x, _) in results.items():
        # equal up to the gemm's own rounding: XLA:CPU's f32 matmul is
        # 1 ulp batch-size-dependent (bucket of 4/8 vs the raw size)
        np.testing.assert_allclose(outs[k], np.asarray(ref.output(x)),
                                   rtol=5e-7, atol=0)


# ------------------------------------------------------------- HTTP front
def test_http_predict_listing_and_error_codes():
    net = _net(seed=2)
    srv = InferenceServer()
    srv.register("mlp", net, batch_buckets=(4, 8), linger_ms=1.0)
    port = srv.start(port=0)
    base = f"http://127.0.0.1:{port}"
    try:
        x = np.random.default_rng(0).normal(size=(3, 6)).astype(np.float32)
        code, doc = _post(f"{base}/v1/models/mlp/predict",
                          {"inputs": x.tolist()})
        assert code == 200 and doc["model"] == "mlp"
        np.testing.assert_allclose(np.asarray(doc["outputs"], np.float32),
                                   np.asarray(net.output(x)),
                                   rtol=1e-6, atol=1e-7)
        assert doc["latency_ms"] > 0

        with urllib.request.urlopen(f"{base}/v1/models", timeout=10) as r:
            listing = json.loads(r.read())
        assert [m["name"] for m in listing["models"]] == ["mlp"]
        assert listing["models"][0]["batch_buckets"] == [4, 8]
        with urllib.request.urlopen(f"{base}/v1/models/mlp",
                                    timeout=10) as r:
            assert json.loads(r.read())["name"] == "mlp"

        code, doc = _post(f"{base}/v1/models/nope/predict",
                          {"inputs": x.tolist()})
        assert code == 404 and "nope" in doc["error"]
        code, doc = _post(f"{base}/v1/models/mlp/predict", {"bogus": 1})
        assert code == 400
        code, doc = _post(f"{base}/v1/models/mlp/predict",
                          {"inputs": np.zeros((9, 6)).tolist()})
        assert code == 400 and "bucket" in doc["error"]   # oversize
        code, doc = _post(f"{base}/v1/models/mlp/other", {"inputs": []})
        assert code == 404
    finally:
        srv.stop()


@pytest.mark.parametrize("qps", [30.0, 90.0])
def test_open_loop_load_answers_every_request(qps):
    """An open-loop load generator: requests leave on a fixed schedule
    whether or not earlier ones came back, for one second at ``qps``. Every
    one is answered with the model's own output for its own input, and the
    batcher counts exactly the requests sent."""
    from concurrent.futures import ThreadPoolExecutor

    net = _net(seed=5)
    name = f"open_loop_{int(qps)}"
    srv = InferenceServer()
    srv.register(name, net, batch_buckets=(1, 2, 4, 8, 16, 32),
                 linger_ms=3.0, max_queue_examples=256,
                 default_deadline_ms=30000.0, input_shape=(6,),
                 warmup=True)
    port = srv.start(port=0)
    url = f"http://127.0.0.1:{port}/v1/models/{name}/predict"
    answered = get_registry().counter("serving_requests_total", model=name,
                                      outcome="ok")
    before = answered.value
    n = int(qps)
    xs = np.random.default_rng(3).normal(size=(n, 1, 6)).astype(np.float32)
    try:
        with ThreadPoolExecutor(max_workers=32) as pool:
            t0 = time.perf_counter()
            futures = []
            for i in range(n):
                delay = t0 + i / qps - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                futures.append(pool.submit(_post, url,
                                           {"inputs": xs[i].tolist()}))
            replies = [f.result() for f in futures]
    finally:
        srv.stop()          # joins the scheduler: its counts are all in
    for x, (code, doc) in zip(xs, replies):
        assert code == 200, doc
        np.testing.assert_allclose(np.asarray(doc["outputs"], np.float32),
                                   np.asarray(net.output(x)),
                                   rtol=1e-6, atol=1e-7)
    assert answered.value - before == n


def test_http_429_under_saturation_with_retry_after():
    """Tiny queue + slow model: concurrent clients overflow admission and
    get typed 429s while accepted requests still complete."""
    srv = InferenceServer()
    srv.register("slow", StubModel(delay_s=0.15), batch_buckets=(1,),
                 max_queue_examples=2, linger_ms=0.0,
                 default_deadline_ms=None)
    port = srv.start(port=0)
    url = f"http://127.0.0.1:{port}/v1/models/slow/predict"
    codes = []
    lock = threading.Lock()

    def client():
        code, doc = _post(url, {"inputs": [[1.0, 2.0]]})
        with lock:
            codes.append((code, doc))

    threads = [threading.Thread(target=client) for _ in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        by_code = {}
        for c, _ in codes:
            by_code[c] = by_code.get(c, 0) + 1
        assert by_code.get(200, 0) >= 1
        assert by_code.get(429, 0) >= 1, by_code
        assert set(by_code) <= {200, 429}
        rejected = next(d for c, d in codes if c == 429)
        assert "overloaded" in rejected["error"]
        assert rejected["_headers"].get("Retry-After") == "1"
        reg = get_registry()
        assert reg.counter("serving_requests_total", model="slow",
                           outcome="rejected").value >= 1
    finally:
        srv.stop()


def test_graceful_drain_drops_zero_accepted_requests():
    """stop(drain=True) mid-backlog: every accepted request still gets a
    200 — nothing is dropped, nothing errors."""
    model = StubModel(delay_s=0.04)
    srv = InferenceServer()
    # buckets (1,): one example per flush -> a real backlog to drain
    srv.register("drain", model, batch_buckets=(1,),
                 max_queue_examples=64, linger_ms=0.0,
                 default_deadline_ms=None)
    port = srv.start(port=0)
    url = f"http://127.0.0.1:{port}/v1/models/drain/predict"
    codes = []
    lock = threading.Lock()

    def client(i):
        code, doc = _post(url, {"inputs": [[float(i), 0.0]]})
        with lock:
            codes.append((i, code, doc))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    # wait until all 8 are ACCEPTED (queued or already flushing) — only
    # then is stop() a genuine mid-backlog drain, and no client can race
    # the closing accept loop
    batcher = srv.registry.get("drain").batcher
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if len(model.calls) + batcher.queue_depth() >= 8:
            break
        time.sleep(0.005)
    srv.stop(drain=True)          # drains the backlog before closing
    for t in threads:
        t.join()
    assert sorted(c for _, c, _ in codes) == [200] * 8, codes
    for i, _, doc in codes:
        # demux integrity: each caller got ITS OWN request's answer
        assert doc["outputs"][0][0] == pytest.approx(float(i))


# ------------------------------------------------- deadlines & admission
def test_deadline_expired_in_queue_raises_504_and_typed_error():
    model = StubModel(delay_s=0.25)
    b = ContinuousBatcher(model.output, name="dl", batch_buckets=(1,),
                          linger_ms=0.0, max_queue_examples=8)
    try:
        f1 = b.submit(np.ones((1, 2), np.float32))          # occupies the
        time.sleep(0.05)                                    # scheduler
        f2 = b.submit(np.ones((1, 2), np.float32), deadline_ms=5.0)
        with pytest.raises(DeadlineExceededError):
            f2.result(timeout=10)
        assert f1.result(timeout=10).shape == (1, 2)
    finally:
        b.close()

    srv = InferenceServer()
    srv.register("dlhttp", StubModel(delay_s=0.25), batch_buckets=(1,),
                 linger_ms=0.0, default_deadline_ms=None)
    port = srv.start(port=0)
    url = f"http://127.0.0.1:{port}/v1/models/dlhttp/predict"
    try:
        out = []
        t = threading.Thread(target=lambda: out.append(
            _post(url, {"inputs": [[1.0, 2.0]]})))
        t.start()
        time.sleep(0.06)          # first request now holds the scheduler
        code, doc = _post(url, {"inputs": [[1.0, 2.0]],
                                "deadline_ms": 5})
        assert code == 504 and "deadline" in doc["error"]
        t.join()
        assert out[0][0] == 200
    finally:
        srv.stop()


def test_batcher_admission_typed_errors_and_reuse_rules():
    b = ContinuousBatcher(StubModel().output, name="adm",
                          batch_buckets=(2, 4), linger_ms=1.0)
    try:
        with pytest.raises(ValueError):       # oversize vs largest bucket
            b.submit(np.zeros((5, 2), np.float32))
        with pytest.raises(ValueError):       # empty request
            b.submit(np.zeros((0, 2), np.float32))
    finally:
        b.close()
    with pytest.raises(OverloadedError):      # closed -> typed rejection
        b.submit(np.zeros((1, 2), np.float32))

    registry = ModelRegistry()
    registry.register("dup", StubModel())
    with pytest.raises(ValueError):
        registry.register("dup", StubModel())
    with pytest.raises(ModelNotFoundError):
        registry.get("missing")
    registry.unregister("dup")
    with pytest.raises(ModelNotFoundError):
        registry.unregister("dup")
    registry.close_all()


def test_cancelled_future_does_not_kill_the_scheduler():
    """Review finding: a caller-cancelled future refuses completion with
    InvalidStateError — that must never escape into the scheduler thread
    (a dead scheduler turns every later submit into a hang)."""
    model = StubModel(delay_s=0.1)
    b = ContinuousBatcher(model.output, name="cancel", batch_buckets=(1,),
                          linger_ms=0.0)
    try:
        f1 = b.submit(np.ones((1, 2), np.float32))          # occupies the
        time.sleep(0.02)                                    # scheduler
        f2 = b.submit(np.ones((1, 2), np.float32), deadline_ms=5.0)
        f3 = b.submit(np.ones((1, 2), np.float32))
        assert f2.cancel()            # pending -> cancellable; its expiry
        assert f3.cancel()            # and its flush both hit cancelled
        assert f1.result(timeout=10).shape == (1, 2)
        # the scheduler survived both cancelled completions: fresh
        # requests still flow
        f4 = b.submit(np.ones((1, 2), np.float32))
        assert f4.result(timeout=10).shape == (1, 2)
    finally:
        b.close()


def test_idle_flush_does_not_rob_the_next_request_of_its_linger():
    """Review finding: flush() on an idle batcher must not leave the
    force flag armed — the next lone request would flush instantly in a
    batch of 1 instead of lingering to coalesce."""
    model = StubModel()
    b = ContinuousBatcher(model.output, name="idleflush",
                          batch_buckets=(4,), linger_ms=40.0)
    try:
        assert b.flush(wait=True)     # idle: no-op, force must NOT stick
        f1 = b.submit(np.ones((1, 2), np.float32))
        f2 = b.submit(np.ones((1, 2), np.float32))
        f1.result(timeout=10), f2.result(timeout=10)
        # both submits landed inside one linger window -> ONE flush
        assert len(model.calls) == 1, model.calls
    finally:
        b.close()


# -------------------------------------------------------- time bucketing
def test_time_buckets_pad_mask_and_slice_back():
    model = StubModel()
    b = ContinuousBatcher(model.output, name="seq", batch_buckets=(2,),
                          time_buckets=(8,), linger_ms=0.0)
    try:
        x = np.random.default_rng(0).normal(size=(1, 5, 3)) \
            .astype(np.float32)
        out = b.submit(x).result(timeout=10)
        # stub output is [b, n_out] (no time axis): batch rows only
        assert out.shape == (1, 2)
        shape, mask = model.calls[0]
        assert shape == (2, 8, 3)             # batch AND time padded
        assert mask.shape == (2, 8)
        np.testing.assert_array_equal(mask[0], [1, 1, 1, 1, 1, 0, 0, 0])
        np.testing.assert_array_equal(mask[1], np.zeros(8))  # pad row

        # exact-fit sequence still carries an (all-ones) mask: mask
        # presence is part of the jit signature (bucketing.py rule)
        model.calls.clear()
        b.submit(np.zeros((1, 8, 3), np.float32)).result(timeout=10)
        _, mask = model.calls[0]
        np.testing.assert_array_equal(mask[0], np.ones(8))
    finally:
        b.close()

    class PerStep:
        def output(self, x, mask=None):
            return np.asarray(x)[..., 0]      # [b, T] per-timestep output

    b = ContinuousBatcher(PerStep().output, name="seq2",
                          batch_buckets=(1,), time_buckets=(8,),
                          linger_ms=0.0)
    try:
        x = np.random.default_rng(1).normal(size=(1, 5, 3, 2)) \
            .astype(np.float32)
        out = b.submit(x).result(timeout=10)
        assert out.shape == (1, 5, 3)         # time padding stripped back
        np.testing.assert_array_equal(out, x[..., 0])
    finally:
        b.close()


def test_queue_examples_gauge_tracks_admission_unit():
    """Review finding: serving_queue_depth counts REQUESTS while the
    admission cap is in EXAMPLES — the serving_queue_examples gauge
    carries the cap's unit so saturation alerts compare like with like."""
    model = StubModel(delay_s=0.1)
    b = ContinuousBatcher(model.output, name="qex", batch_buckets=(2,),
                          linger_ms=0.0, max_queue_examples=64,
                          metrics_label="qex")
    try:
        f1 = b.submit(np.ones((2, 2), np.float32))   # occupies the
        time.sleep(0.03)                             # scheduler
        f2 = b.submit(np.ones((2, 2), np.float32))
        f3 = b.submit(np.ones((2, 2), np.float32))
        g = get_registry().gauge("serving_queue_examples", model="qex")
        assert g.value == 4.0          # 2 queued requests x 2 examples
        for f in (f1, f2, f3):
            f.result(timeout=10)
    finally:
        b.close()
    assert g.value == 0.0              # drained queue reads empty


def test_serving_qps_decays_to_zero_after_traffic_stops():
    """ISSUE 10 satellite: the trailing-window serving_qps gauge was only
    written by completion bookkeeping, so after traffic stopped it
    reported the last value forever. The idle scheduler now wakes as
    completions age out of the window and walks the gauge to zero."""
    registry = ModelRegistry()
    registry.register("qps", StubModel(), batch_buckets=(1, 2),
                      linger_ms=0.5, qps_window_s=0.4)
    try:
        for _ in range(4):
            registry.predict("qps", np.ones((1, 2), np.float32))
        qps = get_registry().gauge("serving_qps", model="qps")
        assert qps.value > 0.0
        deadline = time.monotonic() + 5
        while qps.value > 0.0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert qps.value == 0.0, qps.value
    finally:
        registry.close_all()

    # review finding: closing a model right after traffic must not freeze
    # the gauge at its last nonzero value (the scheduler zeroes it on the
    # way out, before the idle decay ticks ever run)
    registry = ModelRegistry()
    registry.register("qps2", StubModel(), batch_buckets=(1,),
                      linger_ms=0.0, qps_window_s=60.0)
    for _ in range(3):
        registry.predict("qps2", np.ones((1, 2), np.float32))
    assert get_registry().gauge("serving_qps", model="qps2").value > 0.0
    registry.close_all()
    assert get_registry().gauge("serving_qps", model="qps2").value == 0.0


# ----------------------------------------------------- /profile rollup
def test_profile_serving_block_shape_and_text_render():
    registry = ModelRegistry()
    registry.register("profiled", StubModel(), batch_buckets=(1, 2, 4),
                      linger_ms=1.0)
    futs = [registry.submit("profiled", np.ones((1, 2), np.float32))
            for _ in range(6)]
    for f in futs:
        f.result(timeout=10)
    registry.close_all()

    rep = profile_report()
    assert "profiled" in rep["serving"]
    row = rep["serving"]["profiled"]
    assert row["requests"]["ok"] == 6
    for k in ("mean_ms", "p50_ms", "p95_ms", "p99_ms", "max_ms", "n"):
        assert k in row["latency_ms"]
    assert row["latency_ms"]["n"] == 6
    assert row["batch_examples"]["n"] >= 1      # flush count
    assert row["batch_examples"]["mean"] >= 1.0
    assert "queue_depth" in row and "qps" in row
    text = render_profile_text(rep)
    assert "# serving (per hosted model)" in text
    assert "profiled" in text


# --------------------------------------- lockwatch / static cross-check
def test_serving_locks_clean_under_lockwatch_and_statically_derivable():
    """The serving flow under the runtime sanitizer: zero lock-order
    inversions, the batcher's condition actually exercised, and every
    observed edge involving a serving lock derivable by the static
    analyzer (analysis/lockgraph.py) — the PR-8 cross-check extended to
    the new subsystem."""
    from deeplearning4j_tpu.monitor import lockwatch
    prev = lockwatch.enabled()
    lockwatch.set_enabled(True)
    watch = lockwatch.get_lockwatch()
    watch.clear()
    try:
        registry = ModelRegistry(max_in_flight=2)
        registry.register("locked", StubModel(), batch_buckets=(1, 2),
                          linger_ms=1.0)
        futs = [registry.submit("locked", np.ones((1, 2), np.float32))
                for _ in range(5)]
        for f in futs:
            f.result(timeout=10)
        registry.close_all()

        assert watch.inversions() == [], watch.inversions()
        stats = watch.contention_table()
        assert "ContinuousBatcher._cond" in stats      # actually exercised
        assert "ModelRegistry._lock" in stats
        serving_locks = {"ContinuousBatcher._cond", "ModelRegistry._lock"}
        observed = {e for e in watch.observed_edges()
                    if e[0] in serving_locks or e[1] in serving_locks}
        if observed:
            from deeplearning4j_tpu.analysis.lockgraph import \
                analyze_package
            unexplained = observed - analyze_package().edge_set()
            assert not unexplained, sorted(unexplained)
    finally:
        lockwatch.set_enabled(prev)
        watch.clear()


# ------------------------------------------- data plane: bf16 precision
def test_bf16_tolerance_and_closed_compile_set_per_precision():
    """ISSUE 11: a model served with precision="bf16" answers within the
    documented atol (5e-2, docs/SERVING.md) of an f32 twin, returns f32
    responses, and — precision being part of the jit signature — compiles
    exactly len(buckets) variants for the ONE precision actually served,
    with zero retrace storms. An f32-served sibling independently owns
    its own len(buckets) compile set."""
    bf_net, f32_net, ref = _net(seed=21), _net(seed=21), _net(seed=21)
    registry = ModelRegistry()
    registry.register("bf16m", bf_net, batch_buckets=(2, 4), linger_ms=1.0,
                      input_shape=(6,), warmup=True, precision="bf16")
    registry.register("f32m", f32_net, batch_buckets=(2, 4), linger_ms=1.0,
                      input_shape=(6,), warmup=True)
    rng = np.random.default_rng(3)
    served = []
    for i in range(6):
        x = rng.normal(size=(int(rng.integers(1, 5)), 6)).astype(np.float32)
        served.append((x, registry.predict("bf16m", x),
                       registry.predict("f32m", x)))
    # the serving pins FIRST (the twin's unbatched churning forwards
    # below would trip ITS storm detector — exactly the failure serving
    # buckets close): closed compile set PER PRECISION — warmup
    # pre-compiled both buckets in each model's serving dtype, churn
    # added nothing
    assert bf_net._jit_output[(False, False)].compiles == 2
    assert f32_net._jit_output[(False, False)].compiles == 2
    assert _storm_events() == []
    # the bf16 flip reached the layer compute policy (framework nets)
    assert str(bf_net.impls[0].compute_dtype) == "bfloat16"
    assert str(f32_net.impls[0].compute_dtype) == "float32"
    registry.close_all()
    # review finding: the flip must be a property of the REGISTRATION,
    # not a one-way ratchet — re-registering the same net as f32 restores
    # f32 compute (and bit-equality with the twin)
    registry2 = ModelRegistry()
    registry2.register("back", bf_net, batch_buckets=(2, 4), linger_ms=1.0,
                       input_shape=(6,), warmup=True)
    assert str(bf_net.impls[0].compute_dtype) == "float32"
    for x, y_bf, y_f32 in served:
        y_ref = np.asarray(ref.output(x))
        assert y_bf.dtype == np.float32          # f32 out, always
        np.testing.assert_allclose(y_bf, y_ref, atol=5e-2)
        # f32 sibling unchanged: still bit-identical to the twin
        # (up to the 1-ulp batch-size dependence of XLA:CPU's f32 gemm)
        np.testing.assert_allclose(y_f32, y_ref, rtol=5e-7, atol=0)
        np.testing.assert_allclose(registry2.predict("back", x), y_ref,
                                   rtol=5e-7, atol=0)
    registry2.close_all()

    with pytest.raises(ValueError):
        ModelRegistry().register("bad", StubModel(), precision="f16")


# --------------------------------------------- data plane: response cache
def test_response_cache_bit_equality_and_hit_skips_queue():
    """ISSUE 11: a cache hit returns rows BIT-identical to the freshly
    computed response, without queueing (no serving/queue_wait span, no
    flush, no forward call) — and the hit/miss counters move."""
    from deeplearning4j_tpu.monitor.tracer import get_tracer
    model = StubModel()
    registry = ModelRegistry()
    registry.register("cached", model, batch_buckets=(1, 2), linger_ms=0.0,
                      cache_size=8)
    reg = get_registry()
    hits = reg.counter("serving_cache_hits_total", model="cached")
    misses = reg.counter("serving_cache_misses_total", model="cached")
    h0, m0 = hits.value, misses.value
    x = np.random.default_rng(5).normal(size=(2, 3)).astype(np.float32)
    fresh = registry.predict("cached", x)
    assert misses.value == m0 + 1 and hits.value == h0
    n_forwards = len(model.calls)

    tracer = get_tracer()
    tracer.clear()
    fut = registry.submit("cached", x)
    assert fut.done()                  # resolved AT submit — queue skipped
    cached = fut.result(0)
    assert cached.tobytes() == fresh.tobytes()          # strict bit-equality
    assert cached.shape == fresh.shape and cached.dtype == fresh.dtype
    assert hits.value == h0 + 1
    assert len(model.calls) == n_forwards               # no forward ran
    names = {e["name"] for e in tracer.events()}
    assert "serving/queue_wait" not in names            # queue-wait absent
    assert "serving/flush" not in names
    # review finding: hits are completions — the trailing-QPS gauge must
    # count them, or a cache-heavy model reads as idle
    assert reg.gauge("serving_qps", model="cached").value > 0.0
    assert reg.counter("serving_requests_total", model="cached",
                       outcome="ok").value >= 2

    # cached masters are mutation-proof: a caller scribbling on its copy
    # must not corrupt later hits
    cached[:] = -1.0
    again = registry.submit("cached", x).result(5)
    assert again.tobytes() == fresh.tobytes()
    # a different input is a genuine miss
    registry.predict("cached", x + 1.0)
    assert misses.value == m0 + 2
    stats = registry.get("cached").stats()
    assert stats["cache_size"] == 8 and stats["precision"] == "f32"
    assert stats["cache"]["entries"] == 2
    registry.close_all()


def test_cache_miss_owns_bytes_against_linger_window_mutation():
    """Review finding: the cache key is hashed at submit, the value
    computed at flush — without owning the bytes on a miss, a caller
    mutating its array in the linger window would plant a poisoned entry
    under the ORIGINAL bytes' hash for every other caller. The miss path
    copies, so the mutation can't even reach the flush."""
    model = StubModel()
    b = ContinuousBatcher(model.output, name="poison", batch_buckets=(1,),
                          linger_ms=200.0, cache_size=8)
    try:
        x = np.ones((1, 3), np.float32)
        fut = b.submit(x)                     # miss: enqueued + hashed
        x *= 5.0                              # contract violation, mid-linger
        b.flush(wait=True)
        poisoned = fut.result(5)
        # the flush computed from the OWNED copy of the original bytes
        expected = model.output(np.ones((1, 3), np.float32))
        np.testing.assert_array_equal(poisoned, expected)
        # a pristine caller of the original bytes hits the honest entry
        hit = b.submit(np.ones((1, 3), np.float32))
        assert hit.done()
        np.testing.assert_array_equal(hit.result(0), expected)
    finally:
        b.close()


def test_qps_decays_after_cache_hit_only_traffic():
    """Review finding: cache hits complete on submitter threads while the
    scheduler may be parked with wait(None) — the hit path must wake it,
    or the qps gauge stays frozen at its last value forever after
    hit-only traffic stops (the ISSUE-10 staleness bug, reborn)."""
    registry = ModelRegistry()
    registry.register("hitqps", StubModel(), batch_buckets=(1, 2),
                      linger_ms=0.5, qps_window_s=0.4, cache_size=16)
    try:
        x = np.ones((1, 2), np.float32)
        registry.predict("hitqps", x)          # miss: computes + caches
        time.sleep(0.6)                        # flush completion ages out;
        qps = get_registry().gauge("serving_qps", model="hitqps")
        for _ in range(3):                     # scheduler parks (no queue)
            assert registry.predict("hitqps", x) is not None   # pure hits
        assert qps.value > 0.0                 # hits counted in the window
        deadline = time.monotonic() + 5
        while qps.value > 0.0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert qps.value == 0.0, qps.value     # idle decay still ran
    finally:
        registry.close_all()
    assert get_registry().gauge("serving_qps", model="hitqps").value == 0.0


def test_cache_lru_evicts_by_examples():
    b = ContinuousBatcher(StubModel(delay_s=0.05).output, name="lru",
                          batch_buckets=(2,), linger_ms=0.0, cache_size=4)
    try:
        xs = [np.full((2, 3), float(i), np.float32) for i in range(3)]
        for x in xs:
            b.submit(x).result(5)
        # capacity 4 examples, entries are 2 examples each -> the oldest
        # entry aged out; the two newest are resident
        assert b.cache_stats() == {"entries": 2, "examples": 4}
        assert not b.submit(xs[0]).done()       # evicted -> real request
    finally:
        b.close()


# --------------------------------- data plane: device residency/donation
def test_device_resident_flush_donation_safety_and_zero_padding():
    """ISSUE 11: the flush pads on device into a donation-recycled
    bucket buffer. Safety contract pinned here: the donated buffer is
    only ever OVERWRITTEN (padding rows are zeros on every flush, even
    though the recycled buffer held the previous flush's data) and the
    batcher never touches the donated handle again (a fresh handle is
    stored per flush, so backends that truly donate can invalidate the
    old one freely)."""
    model = StubModel()
    b = ContinuousBatcher(model.output, name="dev", batch_buckets=(4,),
                          linger_ms=0.0, device_path=True)
    try:
        b.submit(np.full((2, 3), 7.0, np.float32)).result(5)
        (buf_key, buf1), = list(b._dev_bufs.items())
        b.submit(np.full((3, 3), 9.0, np.float32)).result(5)
        buf2 = b._dev_bufs[buf_key]
        assert buf2 is not buf1          # handle replaced, old one dead
        # the forward saw a DEVICE array both times, padded to the bucket
        assert model.calls[0][0] == (4, 3) and model.calls[1][0] == (4, 3)
        # padding rows are zero DESPITE the recycled buffer having held
        # the previous flush's 7.0 rows — overwrite-only, never read
        np.testing.assert_array_equal(np.asarray(buf2)[:3], 9.0)
        np.testing.assert_array_equal(np.asarray(buf2)[3:], 0.0)
    finally:
        b.close()
    assert b._dev_bufs == {}             # device residency released


def test_warmup_precompiles_pad_programs():
    """Review finding: the device-pad jit specializes per (real rows,
    bucket) pair — warmup must pre-drive those programs so no live flush
    pays a pad compile inside a request. Pinned structurally: after
    register(warmup=True) the batcher holds a recycled pad buffer per
    bucket for the serving trailing shape/dtype."""
    registry = ModelRegistry()
    registry.register("padwarm", _net(seed=4), batch_buckets=(2, 4),
                      linger_ms=1.0, input_shape=(6,), warmup=True)
    b = registry.get("padwarm").batcher
    try:
        key = ((6,), "float32", False)
        assert set(b._dev_bufs) == {(key, 2), (key, 4)}
    finally:
        registry.close_all()


def test_cache_hit_path_respects_closed_admission():
    """Review finding: a closed (draining) batcher must not keep
    answering cached inputs while rejecting uncached ones — admission
    after close() is uniform (OverloadedError for both)."""
    b = ContinuousBatcher(StubModel().output, name="closedhit",
                          batch_buckets=(1,), linger_ms=0.0, cache_size=8)
    try:
        x = np.ones((1, 3), np.float32)
        b.submit(x).result(5)              # cached
        with b._cond:
            b._closed = True      # the drain window: closed, cache still
        assert b.cache_stats()["entries"] == 1     # populated (close()
        with pytest.raises(OverloadedError):       # hasn't cleared yet)
            b.submit(x)                    # hit in cache, still rejected
        with pytest.raises(OverloadedError):
            b.submit(x + 1.0)              # uncached: same outcome
    finally:
        with b._cond:
            b._closed = False
        b.close()


class _TypeSpy:
    """Records the concrete array type the forward receives."""

    def __init__(self):
        self.types = []

    def output(self, x, mask=None):
        self.types.append(type(x))
        return np.zeros((np.asarray(x).shape[0], 2), np.float32)


def test_forward_receives_device_resident_batch():
    import jax
    spy = _TypeSpy()
    b = ContinuousBatcher(spy.output, name="devtype", batch_buckets=(2,),
                          linger_ms=0.0, device_path=True)
    try:
        b.submit(np.ones((1, 3), np.float32)).result(5)
        assert issubclass(spy.types[0], jax.Array), spy.types
    finally:
        b.close()

    # review finding: a DIRECTLY-constructed batcher defaults to the
    # host path — a pre-existing numpy forward must keep receiving the
    # mutable ndarrays it always got (the registry opts framework nets
    # into the device path; ServedModel test below)
    spy2 = _TypeSpy()
    b = ContinuousBatcher(spy2.output, name="hosttype", batch_buckets=(2,),
                          linger_ms=0.0)
    try:
        b.submit(np.ones((1, 3), np.float32)).result(5)
        assert spy2.types[0] is np.ndarray
    finally:
        b.close()

    # a registry-registered framework net rides the device path (its
    # forward is jax-backed by construction)
    registry = ModelRegistry()
    registry.register("devnet", _net(seed=5), batch_buckets=(2,),
                      linger_ms=0.5, input_shape=(6,), warmup=True)
    try:
        assert registry.get("devnet").batcher._use_device() is True
        registry.predict("devnet", np.ones((1, 6), np.float32))
    finally:
        registry.close_all()


# ------------------------------------- data plane: submit no-copy contract
def test_submit_does_not_copy_conforming_ndarray():
    """ISSUE 11 satellite: a preexisting ndarray whose dtype already
    conforms is enqueued AS-IS — the old per-submit asarray+cast copy is
    gone. Non-conforming dtypes still convert (the one allowed copy)."""
    b = ContinuousBatcher(StubModel().output, name="nocopy",
                          batch_buckets=(4,), linger_ms=10_000.0)
    try:
        x = np.ones((1, 3), np.float32)
        fut = b.submit(x)
        with b._cond:
            assert b._queue[0].x is x          # the SAME object, no copy
        x64 = np.ones((1, 3), np.float64)
        b.submit(x64)
        with b._cond:
            assert b._queue[1].x is not x64
            assert b._queue[1].x.dtype == np.float32
        b.flush(wait=True)
        assert fut.result(5).shape == (1, 2)
    finally:
        b.close()

    # bf16 precision: the conforming dtype IS bfloat16
    import ml_dtypes
    b = ContinuousBatcher(StubModel().output, name="nocopy16",
                          batch_buckets=(4,), linger_ms=10_000.0,
                          precision="bf16")
    try:
        xb = np.ones((1, 3), ml_dtypes.bfloat16)
        b.submit(xb)
        with b._cond:
            assert b._queue[0].x is xb
        xf = np.ones((1, 3), np.float32)
        b.submit(xf)
        with b._cond:
            assert b._queue[1].x.dtype == np.dtype(ml_dtypes.bfloat16)
    finally:
        b.close()


# ------------------------------------------------------- public surface
def test_package_root_exports_with_docstrings():
    import deeplearning4j_tpu as pkg
    for name in ("InferenceServer", "ModelRegistry", "ContinuousBatcher",
                 "OverloadedError", "DeadlineExceededError"):
        obj = getattr(pkg, name)
        assert obj.__doc__ and obj.__doc__.strip(), name
    from deeplearning4j_tpu import serving
    assert "continuous" in serving.ContinuousBatcher.__doc__.lower() \
        or "coalesc" in serving.ContinuousBatcher.__doc__.lower()

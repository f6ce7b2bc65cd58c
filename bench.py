"""Benchmark harness.

``python bench.py`` measures the BASELINE.json north-star config (ResNet50
ComputationGraph training, images/sec on one chip) and prints one headline
JSON line on stdout; ``--all`` runs every config in ``ALL_BENCHES`` first.
The parent process never imports jax: a chip belongs to one process at a
time, so each config runs in its own ``--one <name>`` child, one at a
time, and the parent only relays. Every record names the device it was
measured on (``platform``, ``device_kind``, device count); a chip config
that finds no TPU exits non-zero, and a failed child makes the parent exit
non-zero. Nothing is printed that this run did not measure.

Throughput accounting matches the reference's ``PerformanceListener``
(samples/sec; ``optimize/listeners/PerformanceListener.java:22-23``). Synthetic
inputs follow the reference's ``BenchmarkDataSetIterator`` pattern. The whole
train step (forward, AD backward, updater, param update) is a single jitted
XLA computation; params in f32, matmul/conv compute in bfloat16 on the MXU.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))


def _sync(x):
    """Completion barrier for a timed window: dispatch is asynchronous, so
    the window closes on a device→host fetch of (a leaf of) ``x`` — the
    loss scalar, whose value transitively requires every queued step's
    compute. (``jax.block_until_ready`` closes it equally; chip_smoke.py
    leg A prints both.)"""
    import jax
    leaf = jax.tree_util.tree_leaves(x)[-1]
    return np.asarray(leaf)


#: published per-chip peaks, keyed by ``jax.devices()[0].device_kind``
#: (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM)
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks():
    """Peaks of the device jax runs on. A roofline against the wrong
    chip's peaks is wrong, so a ``device_kind`` that is not in the table
    is an error, not a default."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise SystemExit(f"no published peaks for device_kind {kind!r} "
                         f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]


def _time_steps(step_fn, n_warmup=3, n_timed=10):
    """Run ``step_fn(i)`` (must return a device value whose VALUE depends on
    the step's compute — the loss) and return the timed-phase duration."""
    out = None
    for i in range(n_warmup):
        out = step_fn(i)
    _sync(out)
    t0 = time.perf_counter()
    for i in range(n_warmup, n_warmup + n_timed):
        out = step_fn(i)
    _sync(out)
    return time.perf_counter() - t0


def _warm_time(fn, *args, iters=5):
    """Compile+warm ``fn(*args)`` once, then return mean seconds per call
    over ``iters`` calls — the shared timing harness for the perf_* scripts
    (same value-fetch gating rationale as :func:`_time_steps`)."""
    _sync(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def _cnn_throughput(model_cls, batch, img, classes=1000, iters=10,
                    compute_dtype="bfloat16", **model_kw):
    """images/sec for a zoo CNN (ComputationGraph or MultiLayerNetwork) on
    synthetic data."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.graph import ComputationGraphConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    model = model_cls(num_classes=classes, **model_kw)
    conf = model.conf()
    conf.global_conf.compute_dtype = compute_dtype
    is_graph = isinstance(conf, ComputationGraphConfiguration)
    net = (ComputationGraph(conf) if is_graph
           else MultiLayerNetwork(conf)).init()
    rng = np.random.default_rng(0)
    c, h, w = img
    f = jnp.asarray(rng.normal(size=(batch, c, h, w)), jnp.float32)
    l = jnp.asarray(np.eye(classes, dtype=np.float32)[
        rng.integers(0, classes, batch)])
    step = net._ensure_step()
    state = {"p": net.params, "s": net.states, "u": net.updater_state}
    key = jax.random.PRNGKey(0)

    feats = (f,) if is_graph else f
    labels = (l,) if is_graph else l

    def one(i):
        it = jnp.asarray(i, jnp.int32)
        state["p"], state["s"], state["u"], loss = step(
            state["p"], state["s"], state["u"], it, key, feats, labels,
            None, None)
        return loss

    dt = _time_steps(one, n_timed=iters)
    return batch * iters / dt


def bench_resnet50(batch=256):
    # batch 256: v5e is HBM-bandwidth-bound on ResNet50; smaller batches
    # under-amortize fixed per-step work (PERF.md has the batch sweep).
    # 25 timed iters: single runs of 10 showed a ~5% run-to-run band
    from deeplearning4j_tpu.models import ResNet50
    return _cnn_throughput(ResNet50, batch, (3, 224, 224), iters=25)


def bench_vgg16(batch=256):
    # batch 256: 1403 img/s = 126 TFLOPS = 64% MFU by XLA's flop count
    # (22.98 TF / 69.9 GB per step) — compute-bound; 128 gives 1311
    from deeplearning4j_tpu.models import VGG16
    return _cnn_throughput(VGG16, batch, (3, 224, 224))


def bench_lenet(batch=1024, n_iter=10, fits=10):
    """LeNet MNIST (MultiLayerNetwork) images/sec through the public fit
    path, using the framework's own small-model configs: ``iterations(10)``
    (reference 0.9.x multi-iteration minibatch, compiled here as ONE scanned
    XLA program) + ``CacheMode.DEVICE`` (HBM-resident batch). Without them
    LeNet is dispatch-latency-bound."""
    from deeplearning4j_tpu.models import LeNet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.datasets.dataset import DataSet

    conf = LeNet(num_classes=10).conf()
    conf.global_conf.compute_dtype = "bfloat16"
    conf.global_conf.cache_mode = "device"
    conf.global_conf.iterations = n_iter
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    ds = DataSet(rng.normal(size=(batch, 1, 28, 28)).astype(np.float32),
                 np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
    net.fit(ds)
    _sync(net.score_)
    t0 = time.perf_counter()
    for _ in range(fits):
        net.fit(ds)
    _sync(net.score_)
    return batch * fits * n_iter / (time.perf_counter() - t0)


def graves_lstm_net(vocab=80, width=512, tbptt=50):
    """2×GravesLSTM(width) char-RNN with TBPTT (the reference
    CudnnLSTMHelper's showcase config)."""
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, BackpropType
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu import Adam

    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Adam(learning_rate=1e-3)).activation("tanh")
            .compute_dtype("bfloat16")
            .cache_mode("device")  # epoch reuse: one H2D, HBM-resident after
            .list()
            .layer(GravesLSTM(n_in=vocab, n_out=width))
            .layer(GravesLSTM(n_in=width, n_out=width))
            .layer(RnnOutputLayer(n_in=width, n_out=vocab,
                                  activation="softmax", loss="mcxent"))
            .build())
    conf.backprop_type = BackpropType.TruncatedBPTT
    conf.tbptt_fwd_length = tbptt
    conf.tbptt_back_length = tbptt
    return MultiLayerNetwork(conf).init()


def char_stream(batch, seq_len, vocab=80):
    """Seeded one-hot character stream with next-character labels."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq_len))
    f = np.eye(vocab, dtype=np.float32)[ids]          # [b, T, vocab]
    l = np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    return DataSet(f, l)


def bench_graves_lstm(batch=64, seq_len=200, tbptt=50, vocab=80, width=512):
    """GravesLSTM char-RNN with TBPTT: characters/sec processed."""
    net = graves_lstm_net(vocab, width, tbptt)
    ds = char_stream(batch, seq_len, vocab)
    net.fit(ds)  # warmup/compile all TBPTT segment shapes
    _sync(net.score_)
    n = 3
    t0 = time.perf_counter()
    for _ in range(n):
        net.fit(ds)
    _sync(net.score_)  # value fetch: transitively waits on every segment step
    dt = time.perf_counter() - t0
    return batch * seq_len * n / dt


#: latched by bench_input_pipeline; embedded in its --one record so the
#: BENCH trajectory carries the prefetch-off/on ETL comparison, not just
#: the headline number
INPUT_PIPELINE_STATS = {}


def bench_input_pipeline(batch=256, n_batches=32, delay_ms=25.0, workers=8):
    """Input-bound benchmark (datasets/prefetch.py): the base iterator
    sleeps ``delay_ms`` per batch — a slow decode/augment stand-in — so a
    synchronous fit pays the full ETL latency on the training thread every
    step. Runs the same fit with the input pipeline OFF
    (``DL4J_TPU_PREFETCH_WORKERS=0``) and ON (multi-worker prefetch +
    device-put-ahead), reading ``etl_ms`` from the monitor registry, and
    latches the comparison into ``INPUT_PIPELINE_STATS`` for the ``--one``
    record. Headline value: images/sec with the pipeline on."""
    from deeplearning4j_tpu import (NeuralNetConfiguration,
                                    MultiLayerNetwork, Sgd)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetIterator
    from deeplearning4j_tpu.monitor import get_registry

    class SlowIter(DataSetIterator):
        """Slow synthetic source: the per-batch cost (the sleep — a
        decode/augment stand-in) sits in ``__next__`` itself, so only
        CONCURRENT pulls can hide it. The counter is lock-guarded and the
        sleep runs outside the lock: safe for N prefetch workers."""

        def __init__(self, ds, n, delay_s):
            self._ds, self._n, self._delay = ds, n, delay_s
            self._pos = 0
            self._lock = threading.Lock()

        def __next__(self):
            with self._lock:
                if self._pos >= self._n:
                    raise StopIteration
                self._pos += 1
            time.sleep(self._delay)
            return self._ds

        def reset(self):
            with self._lock:
                self._pos = 0

        def batch(self):
            return self._ds.num_examples()

        def concurrent_pull_supported(self):
            return True

    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(learning_rate=0.05)).activation("tanh")
            .list()
            .layer(DenseLayer(n_in=784, n_out=256))
            .layer(OutputLayer(n_in=256, n_out=10, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    ds = DataSet(rng.normal(size=(batch, 784)).astype(np.float32),
                 np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
    etl_hist = get_registry().histogram(
        "training_etl_ms", "host wait for the next minibatch")

    def phase(n_workers):
        prev = os.environ.get("DL4J_TPU_PREFETCH_WORKERS")
        os.environ["DL4J_TPU_PREFETCH_WORKERS"] = str(n_workers)
        try:
            _, total0, n0 = etl_hist.state()
            t0 = time.perf_counter()
            net.fit(SlowIter(ds, n_batches, delay_ms / 1e3))
            _sync(net.score_)
            wall = time.perf_counter() - t0
            _, total1, n1 = etl_hist.state()
            served = max(n1 - n0, 1)
            etl_mean = (total1 - total0) / served
            return etl_mean, batch * served / wall
        finally:
            if prev is None:
                os.environ.pop("DL4J_TPU_PREFETCH_WORKERS", None)
            else:
                os.environ["DL4J_TPU_PREFETCH_WORKERS"] = prev

    net.fit(ds)                   # compile outside both timed phases
    _sync(net.score_)
    etl_sync, ips_sync = phase(0)
    etl_pre, ips_pre = phase(workers)
    INPUT_PIPELINE_STATS.update({
        "delay_ms": delay_ms, "workers": workers, "batches": n_batches,
        "etl_ms_sync": round(etl_sync, 3),
        "etl_ms_prefetch": round(etl_pre, 3),
        "etl_reduction": round(etl_sync / max(etl_pre, 1e-9), 1),
        "overlap_ratio": round(1.0 - etl_pre / max(etl_sync, 1e-9), 4),
        "sync_images_per_sec": round(ips_sync, 1),
        "prefetch_images_per_sec": round(ips_pre, 1),
    })
    return ips_pre


#: latched by bench_serving_latency; embedded in its --one record so the
#: BENCH trajectory starts tracking tail latency (p50/p99 vs offered QPS)
#: alongside img/s
SERVING_STATS = {}


def bench_serving_latency(qps_points=(50.0, 250.0), duration_s=4.0,
                          n_in=64, hidden=128, classes=10,
                          buckets=(1, 2, 4, 8, 16, 32), linger_ms=3.0,
                          max_queue_examples=64, pool_workers=64,
                          variants=True, zipf_pool=24, zipf_s=1.3,
                          cold_start=True):
    """Serving-tier tail latency (serving/ — docs/SERVING.md): an
    OPEN-LOOP load generator drives ``POST /v1/models/<name>/predict``
    on an in-process :class:`InferenceServer` at fixed offered QPS —
    requests fire on schedule whether or not earlier ones returned, so
    queueing delay shows up as tail latency instead of silently throttling
    the generator (closed-loop coordination would hide saturation).
    Sweeps ``qps_points``; per point latches {offered_qps, achieved_qps,
    p50_ms, p99_ms, reject_rate, mean_batch_size} into ``SERVING_STATS``.

    ``variants=True`` (ISSUE 11) additionally re-drives the SAME offered-
    QPS points against the data-plane configurations {f32-nocache (the
    main sweep), bf16, bf16+cache under a ZIPFIAN request mix} and
    latches a ``variants`` sub-block — {p50_ms, p99_ms, achieved_qps,
    cache_hit_rate, mean_batch_size} per point per variant — so the
    BENCH trajectory carries the precision/cache before-after, not just
    the headline. Headline value: main-sweep achieved QPS at the highest
    offered point."""
    from concurrent.futures import ThreadPoolExecutor
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu import (NeuralNetConfiguration,
                                    MultiLayerNetwork, Sgd,
                                    InferenceServer, ModelRegistry)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.monitor import (AlertEngine, MetricsHistory,
                                            default_serving_rules,
                                            get_registry)

    def make_server(model_name, precision="f32", cache_size=None):
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Sgd(learning_rate=0.05)).activation("tanh").list()
                .layer(DenseLayer(n_in=n_in, n_out=hidden))
                .layer(OutputLayer(n_in=hidden, n_out=classes,
                                   activation="softmax", loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        registry = ModelRegistry()
        # warmup=True pre-compiles every bucket signature (in the serving
        # precision) OUTSIDE the timed sweep: serving cold-start is the
        # compile-cache item's problem; this config measures steady-state
        # scheduling + forward latency
        registry.register(model_name, net, batch_buckets=buckets,
                          linger_ms=linger_ms,
                          max_queue_examples=max_queue_examples,
                          default_deadline_ms=5000.0,
                          input_shape=(n_in,), warmup=True,
                          precision=precision, cache_size=cache_size)
        srv = InferenceServer(registry)
        port = srv.start(port=0)
        return srv, f"http://127.0.0.1:{port}/v1/models/{model_name}/predict"

    rng = np.random.default_rng(0)
    # one fixed payload for the nocache sweeps (the pre-ISSUE-11 shape),
    # a pool of distinct payloads for the Zipfian cache variant — the
    # "millions of users" mix where a hot head dominates
    payloads = [json.dumps(
        {"inputs": rng.normal(size=(1, n_in)).astype(np.float32).tolist()}
    ).encode() for _ in range(zipf_pool)]

    def fire(url, data, out, lock):
        t0 = time.perf_counter()
        try:
            req = urllib.request.Request(
                url, data=data,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()
            code = 200
        except urllib.error.HTTPError as e:
            e.close()
            code = e.code
        except OSError:
            code = -1
        with lock:
            out.append((code, (time.perf_counter() - t0) * 1e3))

    def drive(offered, url, model_name, pick_payload, engine=None,
              cache_counters=None):
        batch_hist = get_registry().histogram("serving_batch_examples",
                                              "", model=model_name)
        out, lock = [], threading.Lock()
        n = int(offered * duration_s)
        period = 1.0 / offered
        c0 = ([c.value for c in cache_counters]
              if cache_counters else None)
        with ThreadPoolExecutor(max_workers=pool_workers) as pool:
            _, b_total0, b_n0 = batch_hist.state()
            t_start = time.perf_counter()
            for i in range(n):
                target = t_start + i * period
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                pool.submit(fire, url, pick_payload(i), out, lock)
        wall = time.perf_counter() - t_start
        _, b_total1, b_n1 = batch_hist.state()
        lat_ok = sorted(l for c, l in out if c == 200)
        rejects = sum(1 for c, _ in out if c == 429)
        flushes = max(b_n1 - b_n0, 1)

        def pct(q):
            return lat_ok[min(int(q * (len(lat_ok) - 1)),
                              len(lat_ok) - 1)] if lat_ok else None
        hit_rate = None
        if cache_counters:
            hits = cache_counters[0].value - c0[0]
            misses = cache_counters[1].value - c0[1]
            if hits + misses:
                hit_rate = round(hits / (hits + misses), 4)
        point = {
            "offered_qps": offered,
            "sent": n,
            "achieved_qps": round(len(lat_ok) / wall, 1),
            "p50_ms": round(pct(0.50), 2) if lat_ok else None,
            "p99_ms": round(pct(0.99), 2) if lat_ok else None,
            "reject_rate": round(rejects / max(n, 1), 4),
            "mean_batch_size": round((b_total1 - b_total0) / flushes, 2),
            "cache_hit_rate": hit_rate,
        }
        if engine is not None:
            engine.evaluate(strict=False)
            point["alerts_fired"] = engine.firing()
        return point

    # ---- main sweep: f32, no cache, fixed payload, SLO rules watching.
    # The default serving rule pack over a fast-sampling history ring;
    # each offered-QPS point latches which rules were FIRING when the
    # point ended — and the LOWEST point must end alert-free (a healthy
    # server at trivial load with alerts firing means the bench or the
    # rules are broken)
    srv, url = make_server("bench")
    history = MetricsHistory(capacity=256, interval_s=0.25)
    engine = AlertEngine(history=history)
    engine.add(*default_serving_rules(
        model="bench", windows=(2.0, 4.0), p99_target_ms=250.0,
        queue_cap=max_queue_examples, for_seconds=0.0))
    # for_seconds=0: the sweep points are seconds long — the production
    # hold-down would mask every breach, and alerts_fired at the high
    # points is part of the latched record
    rule_names = [r.name for r in engine.rules()]
    history.start()
    try:
        points = [drive(q, url, "bench", lambda i: payloads[0],
                        engine=engine) for q in qps_points]
    finally:
        srv.stop()
        history.stop()
        # rules legitimately FIRING at a high-QPS point must not leave
        # alerts_firing{rule=}=1 squatting in the process-global registry
        # for the rest of the run — clear() records the closing edges
        engine.clear()
    assert not points[0]["alerts_fired"], (
        f"SLO rules FIRING at the lowest offered-QPS point "
        f"({qps_points[0]} qps): {points[0]['alerts_fired']} — a healthy "
        f"server at trivial load must be alert-free")
    SERVING_STATS.update({
        "buckets": list(buckets), "linger_ms": linger_ms,
        "max_queue_examples": max_queue_examples,
        "duration_s": duration_s, "points": points,
        "alert_rules": rule_names,
    })

    if variants:
        # ---- data-plane variants at the SAME offered-QPS points.
        # f32-nocache re-uses the main sweep's points verbatim (same
        # harness, same payload) so the comparison costs one sweep, not
        # two; bf16 and bf16+cache each get a fresh net + server so
        # precision flips and cache state never leak across variants.
        recorded = [{"variant": "f32-nocache", "precision": "f32",
                     "cache_size": None, "zipfian": False,
                     "points": points, "cache_hit_rate": None}]
        zrng = np.random.default_rng(1)
        zipf_idx = [int((zrng.zipf(zipf_s) - 1) % zipf_pool)
                    for _ in range(int(max(qps_points) * duration_s) + 1)]
        for variant, cache_size, zipfian in (
                ("bf16", None, False),
                ("bf16-cache", zipf_pool, True)):
            model_name = f"bench_{variant.replace('-', '_')}"
            srv, url = make_server(model_name, precision="bf16",
                                   cache_size=cache_size)
            counters = None
            if cache_size:
                counters = (
                    get_registry().counter("serving_cache_hits_total",
                                           model=model_name),
                    get_registry().counter("serving_cache_misses_total",
                                           model=model_name))
            pick = ((lambda i: payloads[zipf_idx[i]]) if zipfian
                    else (lambda i: payloads[0]))
            # the registry is process-global and the model name fixed:
            # the overall rate must diff against THIS sweep's start like
            # the per-point rate does, or a re-run in the same process
            # reports a blended stale figure
            base = [c.value for c in counters] if counters else None
            try:
                vpoints = [drive(q, url, model_name, pick,
                                 cache_counters=counters)
                           for q in qps_points]
            finally:
                srv.stop()
            overall = None
            if counters:
                hits, misses = (c.value - b0
                                for c, b0 in zip(counters, base))
                if hits + misses:
                    overall = round(hits / (hits + misses), 4)
            recorded.append({"variant": variant, "precision": "bf16",
                             "cache_size": cache_size, "zipfian": zipfian,
                             "points": vpoints,
                             "cache_hit_rate": overall})
        SERVING_STATS["variants"] = recorded

    if cold_start:
        # ---- compile-once fleet (ISSUE 12): cold-vs-warm cache-dir
        # serving warmup in child processes, latched as the --one
        # record's cold_start block (same net/buckets as the sweep)
        _measure_cold_start(n_in=n_in, hidden=hidden, classes=classes,
                            buckets=buckets)
    return points[-1]["achieved_qps"] or 0.0


#: latched by _measure_cold_start (driven from bench_serving_latency);
#: embedded in the --one record as its ``cold_start`` block so the BENCH
#: trajectory carries the compile-once-fleet before/after (ISSUE 12)
COLD_START_STATS = {}

#: child source for the cold-start measurement: ONE serving warmup —
#: build the same MLP the serving bench uses, register with warmup=True
#: (pre-compiles every bucket signature), report jitwatch's compile
#: seconds + the persistent hit/miss split. The PARENT points
#: DL4J_TPU_COMPILE_CACHE_DIR at a shared dir and runs this twice: the
#: first child populates the disk cache (cold), the second hits it
#: (warm) — the delta is exactly what a serving replica's cold start (or
#: a post-scale_to worker rejoin) saves fleet-wide.
_COLD_START_SRC = """
import json, os, sys
from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                Sgd, ModelRegistry)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
n_in, hidden, classes = (int(a) for a in sys.argv[1:4])
buckets = tuple(int(b) for b in sys.argv[4].split(','))
conf = (NeuralNetConfiguration.builder().seed(7)
        .updater(Sgd(learning_rate=0.05)).activation('tanh').list()
        .layer(DenseLayer(n_in=n_in, n_out=hidden))
        .layer(OutputLayer(n_in=hidden, n_out=classes,
                           activation='softmax', loss='mcxent'))
        .build())
net = MultiLayerNetwork(conf).init()
reg = ModelRegistry()
reg.register('coldstart', net, batch_buckets=buckets,
             input_shape=(n_in,), warmup=True)
from deeplearning4j_tpu.monitor.jitwatch import get_jit_registry
from deeplearning4j_tpu.compilecache import persistent_cache_counts
row = get_jit_registry().table().get('mln/output', {})
reg.close_all(drain=False)
print(json.dumps({'compile_s': row.get('compile_seconds', 0.0),
                  'compiles': row.get('compiles', 0),
                  'persistent_cache_hits':
                      row.get('persistent_cache_hits', 0),
                  'process': persistent_cache_counts()}))
"""


def _measure_cold_start(n_in=64, hidden=128, classes=10,
                        buckets=(1, 2, 4, 8, 16, 32), timeout_s=600):
    """Cold-start mode (ISSUE 12): run the serving warmup in a child
    process twice against one shared ``DL4J_TPU_COMPILE_CACHE_DIR`` —
    cold dir, then warm dir — and latch
    ``{cold_compile_s, warm_compile_s, speedup, ...}`` into
    ``COLD_START_STATS`` for the ``--one`` record's ``cold_start``
    block. A CPU-harness probe: the children are pinned to the CPU (the
    calling process may hold the chip, which belongs to one process at a
    time) and do not inherit ``JAX_COMPILATION_CACHE_DIR`` (it outranks
    the dial, and a cache placed from outside would make the "cold" leg
    warm). A child that fails or prints no record raises."""
    import shutil
    import subprocess
    import tempfile

    d = tempfile.mkdtemp(prefix="bench_compile_cache_")
    argv = [str(n_in), str(hidden), str(classes),
            ",".join(str(b) for b in buckets)]
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", DL4J_TPU_COMPILE_CACHE_DIR=d,
               PYTHONPATH=_ROOT + os.pathsep + env.get("PYTHONPATH", ""))
    runs = []
    try:
        for phase in ("cold", "warm"):
            p = subprocess.run(
                [sys.executable, "-c", _COLD_START_SRC] + argv,
                capture_output=True, text=True, env=env, timeout=timeout_s)
            if p.returncode != 0:
                raise RuntimeError(
                    f"cold-start {phase} child failed rc={p.returncode}: "
                    f"{p.stderr.strip()[-500:]}")
            runs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    cold, warm = runs
    COLD_START_STATS.update({
        "buckets": list(buckets),
        "compiles": warm["compiles"],
        "cold_compile_s": round(cold["compile_s"], 4),
        "warm_compile_s": round(warm["compile_s"], 4),
        "speedup": round(cold["compile_s"]
                         / max(warm["compile_s"], 1e-9), 2),
        "cold_persistent_hits": cold["persistent_cache_hits"],
        "warm_persistent_hits": warm["persistent_cache_hits"],
    })
    return COLD_START_STATS


#: latched by bench_paramserver; embedded in its --one record so the BENCH
#: trajectory carries the 1-server-full-vector vs N-server-delta wire and
#: throughput comparison, not just the headline number
PARAMSERVER_STATS = {}


def bench_paramserver(steps=32, n_in=1024, hidden=1024, classes=10,
                      batch=64, num_servers=3):
    """Parameter-server fleet throughput (paramserver/sharded.py): the same
    async-SGD fit run against (a) ONE server with dense full-vector pulls
    (the PR-1 wire: staleness=0 re-pulls the whole parameter vector every
    step) and (b) a ``num_servers``-node sharded group speaking the proto
    v3 delta wire (per-shard sparse pushes in parallel, journal-replay
    pulls). Latches {steps/sec, push+pull wire bytes per step} for both
    into ``PARAMSERVER_STATS`` for the ``--one`` record; wire bytes come
    from the master's own exact per-instance client counters
    (``push_bytes``/``pull_bytes``), deltaed around the timed fit.
    Headline value: N-server-delta steps/sec."""
    from deeplearning4j_tpu import (NeuralNetConfiguration,
                                    MultiLayerNetwork, DataSet,
                                    ListDataSetIterator, Sgd)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import DistributedMultiLayerNetwork
    from deeplearning4j_tpu.paramserver import (
        ParameterServer, ParameterServerTrainingMaster,
        ShardedParameterServerGroup)

    rng = np.random.default_rng(0)
    batches = [DataSet(rng.normal(size=(batch, n_in)).astype(np.float32),
                       np.eye(classes, dtype=np.float32)[
                           rng.integers(0, classes, batch)])
               for _ in range(steps)]

    def build_net():
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Sgd(learning_rate=0.05)).activation("tanh").list()
                .layer(DenseLayer(n_in=n_in, n_out=hidden))
                .layer(OutputLayer(n_in=hidden, n_out=classes,
                                   activation="softmax", loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    def run(servers, delta):
        net = build_net()
        group = srv = None
        if servers == 1 and not delta:
            srv = ParameterServer(port=0)
            address = srv.address
        else:
            group = ShardedParameterServerGroup(servers)
            address = group.address
        try:
            master = (ParameterServerTrainingMaster.Builder(address)
                      .staleness(0).threshold(1e-3).backoff(0.01)
                      .delta_push(delta).build())
            dnet = DistributedMultiLayerNetwork(net, master)
            dnet.fit(ListDataSetIterator(batches[:2]))   # compile, un-timed
            c0 = dict(master.client.metrics.snapshot()["counters"])
            t0 = time.perf_counter()
            dnet.fit(ListDataSetIterator(batches))
            dt = time.perf_counter() - t0
            c1 = master.client.metrics.snapshot()["counters"]
            wire = (c1["push_bytes"] - c0["push_bytes"]
                    + c1["pull_bytes"] - c0["pull_bytes"])
            master.client.close()
            return steps / dt, wire / steps
        finally:
            if srv is not None:
                srv.stop()
            if group is not None:
                group.stop()

    sps_dense, wire_dense = run(1, delta=False)
    sps_delta, wire_delta = run(num_servers, delta=True)
    n_params = n_in * hidden + hidden + hidden * classes + classes
    PARAMSERVER_STATS.update({
        "num_servers": num_servers, "steps": steps, "params": n_params,
        "dense_steps_per_sec": round(sps_dense, 1),
        "delta_steps_per_sec": round(sps_delta, 1),
        "dense_wire_bytes_per_step": int(wire_dense),
        "delta_wire_bytes_per_step": int(wire_delta),
        "wire_reduction": round(wire_dense / max(wire_delta, 1.0), 1),
        "speedup": round(sps_delta / max(sps_dense, 1e-9), 2),
    })
    return sps_delta


#: latched by bench_paramserver_overlap; embedded in its --one record so
#: the BENCH trajectory carries the sync-vs-overlap comparison AND the
#: per-phase breakdown that proves WHERE the win came from (comms hidden
#: under compute), not just the headline number
PARAMSERVER_OVERLAP_STATS = {}


def bench_paramserver_overlap(steps=16, n_in=256, hidden=256, classes=10,
                              batch=2048, min_delay_s=0.005):
    """Latency-hiding hot loop (paramserver/overlap.py): the same async-SGD
    fit run twice against ONE server — sync (``overlap=False``, today's
    fully-serial loop) and overlapped (``overlap=True``: a comms worker
    encodes+pushes step k while the device computes step k+1) — with an
    INJECTED per-push transport delay (``push_delay_s`` ≥ 5 ms: a real
    cross-host RTT, where localhost would measure ~100 µs and hide
    nothing worth hiding). The delay is calibrated to the measured
    compute+d2h mean of an un-delayed sync run, putting the comms round
    and the device step in the same regime — exactly where the pipeline
    earns its keep: sync pays compute + comms per step, overlap pays
    ~max(compute, comms). Latches {steps/sec both modes, speedup, exact
    per-phase means from ``train_step_phase_ms`` registry deltas, wall
    step means} into ``PARAMSERVER_OVERLAP_STATS`` for the ``--one``
    record. Headline value: overlap steps/sec.

    Shape note: SMALL model × LARGE batch on purpose. On the CPU harness
    the 'device' shares cores with the comms worker, so a big parameter
    vector makes the worker's encode fight the next step's compute and
    eat the win; ~68K params keeps encode sub-ms so the comms round is
    dominated by the injected sleep (which contends with nothing), while
    batch=2048 keeps compute comparable to the delay."""
    from deeplearning4j_tpu import (NeuralNetConfiguration,
                                    MultiLayerNetwork, DataSet,
                                    ListDataSetIterator, Sgd)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import DistributedMultiLayerNetwork
    from deeplearning4j_tpu.monitor import get_registry
    from deeplearning4j_tpu.paramserver import (
        ParameterServer, ParameterServerClient,
        ParameterServerTrainingMaster)

    rng = np.random.default_rng(0)
    batches = [DataSet(rng.normal(size=(batch, n_in)).astype(np.float32),
                       np.eye(classes, dtype=np.float32)[
                           rng.integers(0, classes, batch)])
               for _ in range(steps)]

    def build_net():
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Sgd(learning_rate=0.05)).activation("tanh").list()
                .layer(DenseLayer(n_in=n_in, n_out=hidden))
                .layer(OutputLayer(n_in=hidden, n_out=classes,
                                   activation="softmax", loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    def phase_totals():
        # (ms-sum, n) per phase straight from the registry children —
        # exact per-mode means come from deltas around each timed fit
        # (the registry is process-global and cumulative across runs)
        reg = get_registry()
        out = {}
        for p in ("compute", "d2h", "encode", "push"):
            _, total, n = reg.histogram(
                "train_step_phase_ms",
                "paramserver training hot-loop phase latency",
                phase=p).state()
            out[p] = (total, n)
        _, total, n = reg.histogram(
            "train_step_wall_ms",
            "paramserver training wall time per step").state()
        out["wall"] = (total, n)
        return out

    def run(overlap, delay_s):
        net = build_net()
        srv = ParameterServer(port=0)
        try:
            # the injected-latency client rides the master's ctor seam;
            # count_own_pushes=False keeps staleness=0 from re-pulling the
            # full vector after every own push (single worker, contiguous
            # versions) so the comms round under test is push-only
            client = ParameterServerClient(
                srv.address, staleness=0, max_retries=5, backoff=0.01,
                push_delay_s=delay_s)
            master = ParameterServerTrainingMaster(
                srv.address, staleness=0, threshold=1e-3, backoff=0.01,
                count_own_pushes=False, client=client, overlap=overlap)
            dnet = DistributedMultiLayerNetwork(net, master)
            dnet.fit(ListDataSetIterator(batches[:2]))   # compile, un-timed
            p0 = phase_totals()
            t0 = time.perf_counter()
            dnet.fit(ListDataSetIterator(batches))
            dt = time.perf_counter() - t0
            p1 = phase_totals()
            master.close()
            phase_ms = {k: round((p1[k][0] - p0[k][0])
                                 / max(p1[k][1] - p0[k][1], 1), 3)
                        for k in p1}
            return steps / dt, phase_ms
        finally:
            srv.stop()

    # calibrate: delay ≈ the step's device-side cost, floored at 5 ms
    _, cal = run(overlap=False, delay_s=0.0)
    delay_s = max(float(min_delay_s), (cal["compute"] + cal["d2h"]) / 1e3)

    sps_sync, ph_sync = run(overlap=False, delay_s=delay_s)
    sps_over, ph_over = run(overlap=True, delay_s=delay_s)
    wall_sync = ph_sync.pop("wall")
    wall_over = ph_over.pop("wall")
    PARAMSERVER_OVERLAP_STATS.update({
        "steps": steps, "params": n_in * hidden + hidden
                                  + hidden * classes + classes,
        "push_delay_ms": round(delay_s * 1e3, 3),
        "steps_per_sec_sync": round(sps_sync, 2),
        "steps_per_sec_overlap": round(sps_over, 2),
        "speedup": round(sps_over / max(sps_sync, 1e-9), 2),
        "phase_ms": {"sync": ph_sync, "overlap": ph_over},
        "wall_ms_sync": round(wall_sync, 3),
        "wall_ms_overlap": round(wall_over, 3),
        # wall < Σ phases is the proof the comms ran UNDER the compute
        "hidden_ms_per_step": round(
            sum(ph_over.values()) - wall_over, 3),
    })
    return sps_over


CONTROL_LOOP_STATS = {}


def bench_control_loop(slow_ms=120.0, shards=2, timeout_s=60.0):
    """Closed-loop control chaos drill (control/plane.py, docs/CONTROL.md):
    an inference server with a faultable model + a sharded paramserver
    fleet run under the control plane's daemon (serving-pressure +
    shard-restart policies), then BOTH faults land at once — the model
    turns slow (p99 SLO breach) and a shard server is killed
    (``shard_server_down``) — and the drill measures the wall time until
    the system is back to an alert-free steady state with ZERO human
    intervention: admission stepped then restored, the shard restarted
    from its latched snapshot. Latches {time_to_recover_s, actions_taken,
    alerts_fired} (plus per-incident reaction times) into
    ``CONTROL_LOOP_STATS`` for the ``--one`` record. Headline value:
    seconds to recover (lower is better, unlike the throughput benches —
    trajectory tooling reads the unit)."""
    import json as _json
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.control import (get_control_plane,
                                            serving_pressure_policy,
                                            shard_restart_policy)
    from deeplearning4j_tpu.monitor import (BurnRateRule, get_alert_engine,
                                            get_flight_recorder,
                                            get_history)
    from deeplearning4j_tpu.paramserver import (
        ShardedParameterServerClient, ShardedParameterServerGroup)
    from deeplearning4j_tpu.serving import InferenceServer

    class FaultableModel:
        def __init__(self):
            self.delay_s = 0.0

        def output(self, x, mask=None):
            if self.delay_s:
                time.sleep(self.delay_s)
            x = np.asarray(x)
            return np.full((x.shape[0], 2), 1.0, np.float32)

    model = FaultableModel()
    srv = InferenceServer()
    srv.register("drill", model, batch_buckets=(1, 2, 4), linger_ms=0.5,
                 max_queue_examples=64, qps_window_s=1.0)
    port = srv.start(port=0)
    url = f"http://127.0.0.1:{port}/v1/models/drill/predict"
    engine, hist = get_alert_engine(), get_history()
    rec = get_flight_recorder()
    engine.add(BurnRateRule("drill_p99", kind="latency", target_ms=40.0,
                            windows=(1.5, 3.0),
                            latency_labels={"model": "drill"},
                            for_seconds=0.2))
    n = 64
    group = ShardedParameterServerGroup(shards)
    client = ShardedParameterServerClient(group.addresses, max_retries=0,
                                          backoff=0.01, down_backoff=0.05)
    plane = get_control_plane()
    plane.add(serving_pressure_policy(srv.registry, "drill",
                                      rules=("drill_p99",),
                                      cooldown_s=0.5),
              shard_restart_policy(group, cooldown_s=0.5))
    served = srv.registry.get("drill")
    body = _json.dumps({"inputs": [[1.0, 2.0]]}).encode("utf-8")

    def post():
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                r.read()
        except urllib.error.HTTPError as e:
            e.read()
            e.close()

    def drive(k):
        for _ in range(k):
            post()
        hist.sample()
        engine.evaluate(strict=False)

    def actions(name):
        return [a for a in plane.actions() if a["action"] == name]

    events0 = len(rec.events())
    try:
        client.set_params(np.zeros(n, np.float32))
        plane.start(interval_s=0.05)
        drive(6)                                  # healthy baseline

        # ---- both faults land; the recovery clock starts HERE
        t_fault = time.perf_counter()
        model.delay_s = slow_ms / 1e3
        group.kill(1)                             # latches the snapshot
        client.push_encoded((np.array([0, 1], np.int32),
                             np.array([1, 1], np.int8), 0.5, n))

        stepped = restarted = None
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            drive(3)
            if stepped is None and actions("set_admission"):
                stepped = time.perf_counter() - t_fault
                # the clamp shed the load: the incident's cause clears,
                # and from here recovery is the loop's job alone
                model.delay_s = 0.0
            if restarted is None and actions("restart"):
                restarted = time.perf_counter() - t_fault
            if stepped is not None and restarted is not None \
                    and not engine.firing() \
                    and actions("restore_admission"):
                break
            time.sleep(0.05)
        t_recover = time.perf_counter() - t_fault
        recovered = (not engine.firing()
                     and bool(actions("restore_admission"))
                     and getattr(group.servers[1], "_running", False))
        fresh = rec.events()[events0:]
        CONTROL_LOOP_STATS.update({
            "time_to_recover_s": round(t_recover, 3),
            "recovered": recovered,
            "time_to_admission_step_s":
                round(stepped, 3) if stepped is not None else None,
            "time_to_shard_restart_s":
                round(restarted, 3) if restarted is not None else None,
            "actions_taken": len([e for e in fresh
                                  if e["event"] == "control_action"]),
            "alerts_fired": len([e for e in fresh
                                 if e["event"] == "alert_firing"]),
            "admission_restored":
                served.batcher.max_queue_examples == 64,
        })
        return t_recover
    finally:
        plane.stop()
        plane.clear()
        engine.remove("drill_p99")
        client.close()
        group.stop()
        srv.stop()


FLEET_SCRAPE_STATS = {}


def bench_fleet_scrape(replicas=3, ticks=25, warm_requests=4):
    """Scrape-plane collector bench (monitor/collector.py): K in-process
    inference replicas polled over real HTTP by one TelemetryCollector
    into a PRIVATE FleetState, measuring the per-target ``/telemetry``
    scrape cost and the whole-tick overhead around the scrapes (fleet
    merge + history sample + alert evaluation). Latches
    {scrape_ms_p50, scrape_ms_p99, targets, merged_series,
    tick_overhead_ms, scrape_errors} into ``FLEET_SCRAPE_STATS`` for
    the ``--one`` record. Headline value: scrape p99 ms (lower is
    better — trajectory tooling reads the unit)."""
    import json as _json
    import urllib.request

    from deeplearning4j_tpu.monitor.collector import TelemetryCollector
    from deeplearning4j_tpu.monitor.fleet import FleetState
    from deeplearning4j_tpu.serving import InferenceServer

    class TinyModel:
        def output(self, x, mask=None):
            x = np.asarray(x)
            return np.full((x.shape[0], 2), 1.0, np.float32)

    servers = []
    collector = TelemetryCollector(fleet=FleetState())
    body = _json.dumps({"inputs": [[1.0, 2.0]]}).encode("utf-8")
    try:
        for i in range(int(replicas)):
            srv = InferenceServer()
            srv.register(f"m{i}", TinyModel(), batch_buckets=(1, 2, 4),
                         linger_ms=0.0, max_queue_examples=64)
            port = srv.start(port=0)
            servers.append(srv)
            collector.add_target(f"replica{i}", f"127.0.0.1:{port}")
            # a few real requests so each reply carries latency series
            # (and exemplars) — an idle registry would undercount the
            # merge cost
            for _ in range(int(warm_requests)):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/models/m{i}/predict",
                    data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    r.read()
        collector.tick()                        # cursor-priming pass
        samples, overhead, errors = [], [], 0
        for _ in range(int(ticks)):
            summary = collector.tick()
            errors += len(summary["errors"])
            ms = list(summary["scrape_ms"].values())
            samples.extend(ms)
            overhead.append(summary["duration_ms"] - sum(ms))
        samples.sort()

        def pct(q):
            return samples[min(len(samples) - 1,
                               int(q * (len(samples) - 1)))]

        p99 = round(pct(0.99), 3)
        FLEET_SCRAPE_STATS.update({
            "scrape_ms_p50": round(pct(0.50), 3),
            "scrape_ms_p99": p99,
            "targets": int(replicas),
            "merged_series": len(collector.fleet_dump()),
            "tick_overhead_ms": round(sum(overhead) / len(overhead), 3),
            "scrape_errors": errors,
        })
        return p99
    finally:
        collector.stop()
        for srv in servers:
            srv.stop()


PROBE_OVERHEAD_STATS = {}


_PROBE_REPLICA_SRC = r"""
import json, sys
import numpy as np
from deeplearning4j_tpu.serving import InferenceServer

class TinyModel:
    def output(self, x, mask=None):
        x = np.asarray(x)
        return np.full((x.shape[0], 2), 1.0, np.float32)

srv = InferenceServer()
served = srv.register("probed", TinyModel(), input_shape=(2,),
                      batch_buckets=(1, 2, 4), linger_ms=0.0,
                      max_queue_examples=64, cache_size=16)
golden = served.golden()
port = srv.start(port=0)
print(json.dumps({"port": port, "golden": golden}), flush=True)
sys.stdin.read()
"""

#: prober child for bench_probe_overhead: a Prober in its OWN process
#: (the deployment shape — co-located with neither the replica nor the
#: latency-measuring driver), started/stopped between phases over a
#: stdin line protocol: "start <interval_s>" / "stop" / "quit" (each
#: ack'd with "ok"); "quit" prints the target's final snapshot row
_PROBE_PROBER_SRC = r"""
import json, sys
from deeplearning4j_tpu.monitor.probes import Prober

cfg = json.loads(sys.stdin.readline())
p = Prober()
p.add_target("bench", cfg["url"], cfg["golden"])
for line in sys.stdin:
    cmd = line.split()
    if cmd[0] == "start":
        p.start(interval_s=float(cmd[1]))
    elif cmd[0] == "stop":
        p.stop()
    elif cmd[0] == "quit":
        p.stop()
        print(json.dumps(p.snapshot()["targets"]["bench"]), flush=True)
        break
    print("ok", flush=True)
"""


def bench_probe_overhead(requests=2000, probe_qps=(1.0, 4.0)):
    """Probe-plane interference bench (monitor/probes.py): serving
    p50/p99 over real HTTP against a REPLICA SUBPROCESS with the prober
    OFF, then at each probe QPS point with a live Prober firing
    golden-set probes at the same replica — the deployment shape (the
    probe plane is external by definition; co-locating the prober inside
    the replica would measure GIL contention no real probe causes). The
    probe plane's pitch is "black-box monitoring at negligible serving
    cost" — this latches the receipt: {p50_off_ms, p99_off_ms, points:
    [{probe_qps, p50_ms, p99_ms, p99_overhead_pct, probes,
    last_outcome}], max_p99_overhead_pct, cache_entries_after} into
    ``PROBE_OVERHEAD_STATS`` for the ``--one`` record. Headline value:
    worst p99 overhead percent across the QPS points (lower is better;
    the acceptance pin is < 5%). The replica serves with its response
    cache ON: real traffic lands exactly one entry and every probe
    bypasses it, so ``cache_entries_after == 1`` restates the drill's
    cache-purity invariant under load."""
    import json as _json
    import subprocess
    import urllib.request

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"        # numpy model: never wait on a device
    root = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-c", _PROBE_REPLICA_SRC],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, env=env, cwd=root)
    doc = _json.loads(proc.stdout.readline())
    port, golden = int(doc["port"]), doc["golden"]
    url = f"http://127.0.0.1:{port}/v1/models/probed/predict"
    body = _json.dumps({"inputs": [[1.0, 2.0]]}).encode("utf-8")
    pproc = subprocess.Popen(
        [sys.executable, "-c", _PROBE_PROBER_SRC],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, env=env, cwd=root)
    pproc.stdin.write(_json.dumps(
        {"url": f"127.0.0.1:{port}", "golden": golden}) + "\n")
    pproc.stdin.flush()

    def prober_cmd(cmd):
        pproc.stdin.write(cmd + "\n")
        pproc.stdin.flush()
        return pproc.stdout.readline().strip()

    def drive(n):
        lat = []
        for _ in range(int(n)):
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=10) as r:
                r.read()
            lat.append((time.perf_counter() - t0) * 1e3)
        return lat

    def pct(lat, q):
        lat = sorted(lat)
        return round(lat[min(len(lat) - 1, int(q * (len(lat) - 1)))], 3)

    try:
        # warm the whole serving path until the startup transient is
        # gone: the first ~100 requests of a fresh replica show one-off
        # multi-ms hiccups (thread-pool growth, allocator warmup) that
        # would land entirely in whichever pool is measured first
        drive(max(150, int(requests) // 4))
        # interleaved + shuffled design: loopback p99s are
        # sub-millisecond, so two phases measured at different times
        # mostly measure machine drift, not probes. Each rep drives one
        # OFF segment and one ON segment per QPS point in a (seeded)
        # shuffled order — slow machine periods and position effects
        # land evenly across the pools — and the per-phase pools are
        # compared as wholes, so the p99 index sits on a real 1% tail
        # instead of a tiny segment's max sample
        import random
        rng = random.Random(0)
        reps = 5
        per = max(1, int(requests) // reps)
        off = []
        on = {float(qps): [] for qps in probe_qps}
        for _ in range(reps):
            phases = [None] + [float(q) for q in probe_qps]
            rng.shuffle(phases)
            for qps in phases:
                # every phase opens with an UNMEASURED ~32-request burst:
                # the serving path shows a one-off ~5ms hiccup ~25
                # requests into a fresh burst (observed with the prober
                # completely absent), and a phase comparison is only fair
                # if that transient lands in nobody's measured pool
                if qps is None:
                    drive(32)
                    off += drive(per)
                    continue
                # each start fires an immediate probe, so every rep
                # guarantees at least one probe lands inside its phase
                assert prober_cmd(f"start {1.0 / qps}") == "ok"
                try:
                    drive(32)
                    on[qps] += drive(per)
                finally:
                    assert prober_cmd("stop") == "ok"
        p50_off = pct(off, 0.50)
        p99_off = pct(off, 0.99)
        PROBE_OVERHEAD_STATS.update({
            "p50_off_ms": p50_off, "p99_off_ms": p99_off,
            "requests_per_point": per * reps, "points": []})
        snap = _json.loads(prober_cmd("quit"))
        worst = 0.0
        for qps in probe_qps:
            overhead = ((pct(on[float(qps)], 0.99) - p99_off)
                        / max(p99_off, 1e-9) * 100.0)
            worst = max(worst, overhead)
            PROBE_OVERHEAD_STATS["points"].append({
                "probe_qps": float(qps),
                "p50_ms": pct(on[float(qps)], 0.50),
                "p99_ms": pct(on[float(qps)], 0.99),
                "p99_overhead_pct": round(overhead, 2),
                "probes": snap["probes"],
                "last_outcome": snap["last_outcome"],
            })
        worst = round(max(0.0, worst), 2)
        PROBE_OVERHEAD_STATS["max_p99_overhead_pct"] = worst
        # cache purity under load: drive()'s identical bodies land ONE
        # entry; every probe bypassed the cache or this would be 2
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/models/probed",
                timeout=10) as r:
            PROBE_OVERHEAD_STATS["cache_entries_after"] = \
                _json.loads(r.read())["cache"]["entries"]
        return worst
    finally:
        for p in (pproc, proc):
            p.kill()
            p.wait(timeout=30)


INCIDENT_OVERHEAD_STATS = {}


def bench_incident_overhead(requests=400, slow_ms=80.0, timeout_s=30.0):
    """Incident-plane interference bench (monitor/incidents.py): the
    chaos-drill shape — serving goes slow, the p99 burn rule fires, a
    control policy steps admission, the model heals, the alert resolves
    — run TWICE: once bare, once with a live :class:`IncidentRecorder`
    capturing at the fire edge and persisting the bundle at resolve.
    Serving p99 is measured over identical healthy request pools on
    both sides of the drill; the incident plane's pitch is "the black
    box is free for the serving path" (capture runs on the recorder's
    own tick thread, persistence outside every lock) and this latches
    the receipt: {p99_off_ms, p99_on_ms, overhead_pct, capture_ms_p99,
    bundle_bytes, incidents, fired, resolved} into
    ``INCIDENT_OVERHEAD_STATS`` for the ``--one`` record. Headline
    value: p99 overhead percent with the recorder on (lower is better;
    the acceptance pin is <= 1% on the drill p99). The on-phase must
    end with exactly ONE persisted ``.dl4jinc`` bundle — the drill's
    merged edges are one incident, not a bundle per edge."""
    import json as _json
    import tempfile
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.control import (get_control_plane,
                                            serving_pressure_policy)
    from deeplearning4j_tpu.monitor import (BurnRateRule, IncidentRecorder,
                                            get_alert_engine, get_history,
                                            get_registry)
    from deeplearning4j_tpu.serving import InferenceServer

    class FaultableModel:
        def __init__(self):
            self.delay_s = 0.0

        def output(self, x, mask=None):
            if self.delay_s:
                time.sleep(self.delay_s)
            x = np.asarray(x)
            return np.full((x.shape[0], 2), 1.0, np.float32)

    def pct(lat, q):
        lat = sorted(lat)
        return round(lat[min(len(lat) - 1, int(q * (len(lat) - 1)))], 3)

    def phase(dump_dir):
        """One full drill; ``dump_dir`` not None → recorder ON. Returns
        (healthy latencies, phase stats)."""
        model = FaultableModel()
        srv = InferenceServer()
        srv.register("incdrill", model, batch_buckets=(1, 2, 4),
                     linger_ms=0.5, max_queue_examples=64,
                     qps_window_s=1.0)
        port = srv.start(port=0)
        url = f"http://127.0.0.1:{port}/v1/models/incdrill/predict"
        body = _json.dumps({"inputs": [[1.0, 2.0]]}).encode("utf-8")
        engine, hist = get_alert_engine(), get_history()
        hist.clear()                    # stale slow-phase samples from a
        engine.add(BurnRateRule(       # prior phase must not pre-burn
            "incdrill_p99", kind="latency", target_ms=40.0,
            windows=(1.5, 3.0), latency_labels={"model": "incdrill"},
            for_seconds=0.2))
        plane = get_control_plane()
        plane.add(serving_pressure_policy(srv.registry, "incdrill",
                                          rules=("incdrill_p99",),
                                          factor=0.5, min_cap=8,
                                          cooldown_s=0.5))
        rec = None
        if dump_dir is not None:
            rec = IncidentRecorder(engine=engine, dump_dir=dump_dir)
            rec.start(interval_s=0.05)

        def post(timed=None):
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    r.read()
            except urllib.error.HTTPError as e:
                e.read()
                e.close()
            if timed is not None:
                timed.append((time.perf_counter() - t0) * 1e3)

        lat = []
        stats = {"fired": False, "resolved": False}
        try:
            plane.start(interval_s=0.05)
            for _ in range(64):             # unmeasured warmup
                post()
            for _ in range(int(requests) // 2):   # healthy pool A
                post(timed=lat)
            hist.sample()
            engine.evaluate(strict=False)
            # ---- the fault lands; drive (untimed) until the rule fires
            model.delay_s = slow_ms / 1e3
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                for _ in range(3):
                    post()
                hist.sample()
                engine.evaluate(strict=False)
                if engine.firing():
                    stats["fired"] = True
                    break
            # ---- heal; drive until the alert resolves (and, with the
            # recorder on, the resolve has persisted the bundle)
            model.delay_s = 0.0
            while time.monotonic() < deadline:
                for _ in range(3):
                    post()
                hist.sample()
                engine.evaluate(strict=False)
                if engine.firing():
                    continue
                if rec is not None and not any(
                        inc.path for inc in rec.incidents()):
                    continue
                stats["resolved"] = True
                break
            for _ in range(int(requests) // 2):   # healthy pool B
                post(timed=lat)
            if rec is not None:
                rows = rec.snapshot()["incidents"]
                stats["incidents"] = len(rows)
                stats["bundle_bytes"] = sum(
                    r["bundle_bytes"] or 0 for r in rows)
            return lat, stats
        finally:
            if rec is not None:
                rec.stop()
            plane.stop()
            plane.clear()
            engine.remove("incdrill_p99")
            srv.stop()

    dump_dir = tempfile.mkdtemp(prefix="incbench_")
    lat_off, _ = phase(None)
    lat_on, on_stats = phase(dump_dir)
    p99_off, p99_on = pct(lat_off, 0.99), pct(lat_on, 0.99)
    overhead = round(max(
        0.0, (p99_on - p99_off) / max(p99_off, 1e-9) * 100.0), 2)
    cap = get_registry().histogram("incident_capture_ms").summary()
    INCIDENT_OVERHEAD_STATS.update({
        "p99_off_ms": p99_off, "p99_on_ms": p99_on,
        "p50_off_ms": pct(lat_off, 0.50), "p50_on_ms": pct(lat_on, 0.50),
        "overhead_pct": overhead,
        "requests_per_phase": (int(requests) // 2) * 2,
        "capture_ms_p99": round(cap.get("p99_ms", 0.0), 3),
        "bundle_bytes": on_stats.get("bundle_bytes", 0),
        "incidents": on_stats.get("incidents", 0),
        "fired": on_stats["fired"], "resolved": on_stats["resolved"],
        "dump_dir": dump_dir,
    })
    return overhead


PARALLEL_MEMORY_STATS = {}

#: child source for the too-few-devices fallback: re-run the grid on a
#: virtual 8-device CPU mesh in a fresh interpreter (set_cpu_devices must
#: beat backend init — impossible in the already-initialized parent).
#: Same pattern as _COLD_START_SRC. argv: steps n_in hidden classes batch
#: model_extent bench_path
_PM_CHILD_SRC = """
import importlib.util, json, sys
sys.path.insert(0, __import__('os').path.dirname(sys.argv[7]))
from deeplearning4j_tpu.compat import set_cpu_devices
# size the virtual mesh from the requested model extent, or the child
# would re-fail the parent's device check and recurse another child
set_cpu_devices(max(8, 2 * int(sys.argv[6])))
spec = importlib.util.spec_from_file_location('bench_pm_child', sys.argv[7])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.bench_parallel_memory(*[int(a) for a in sys.argv[1:7]])
print(json.dumps(mod.PARALLEL_MEMORY_STATS))
"""


def bench_parallel_memory(steps=8, n_in=256, hidden=1024, classes=16,
                          batch=64, model_extent=2):
    """Unified-mesh memory/throughput grid (parallel/mesh.py substrate):
    the same Adam fit under {replicated, ws (ZeRO-1 optimizer-state
    sharding), fsdp (ZeRO-3 sharded storage)} × {1-D data mesh, 2-D
    data × model mesh with megatron TP rules}. Latches per cell
    {steps_per_sec, state_bytes_per_device (EXACT: params+updater bytes
    resident on device 0 — the quantity ZeRO divides), bytes_in_use /
    peak_bytes (backend memory stats; None on statless backends like the
    CPU harness — peak is process-cumulative, read it only for the cell
    that interests you in a dedicated run)} into
    ``PARALLEL_MEMORY_STATS`` for the ``--one`` record's
    ``parallel_memory`` block. Headline value: fsdp-on-2-D steps/sec —
    the composed topology the substrate exists for."""
    from deeplearning4j_tpu import (NeuralNetConfiguration,
                                    MultiLayerNetwork, DataSet,
                                    ListDataSetIterator, Adam)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.monitor.jitwatch import sample_device_memory
    import jax

    if len(jax.devices()) < 2 * model_extent:
        # single-chip harness (TPU v5 lite0 / plain CPU): the grid needs a
        # real multi-device mesh, so run it on a virtual 8-device CPU mesh
        # in a child interpreter (set_cpu_devices must beat backend init)
        # and latch the child's stats, marked as such
        import subprocess
        argv = [str(int(v)) for v in (steps, n_in, hidden, classes, batch,
                                      model_extent)]
        p = subprocess.run(
            [sys.executable, "-c", _PM_CHILD_SRC] + argv
            + [os.path.abspath(__file__)],
            capture_output=True, text=True, timeout=1200,
            env={k: v for k, v in os.environ.items()
                 if k != "JAX_PLATFORMS"} | {"JAX_PLATFORMS": "cpu"})
        if p.returncode != 0:
            raise RuntimeError(
                f"parallel_memory CPU-mesh child failed rc={p.returncode}: "
                f"{p.stderr.strip()[-500:]}")
        stats = json.loads(p.stdout.strip().splitlines()[-1])
        stats["virtual_cpu_mesh"] = True
        PARALLEL_MEMORY_STATS.update(stats)
        return stats["grid"]["fsdp_2d"]["steps_per_sec"]

    rng = np.random.default_rng(0)
    batches = [DataSet(rng.normal(size=(batch, n_in)).astype(np.float32),
                       np.eye(classes, dtype=np.float32)[
                           rng.integers(0, classes, batch)])
               for _ in range(steps)]

    def build_net():
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(Adam(learning_rate=1e-3)).activation("tanh").list()
                .layer(DenseLayer(n_in=n_in, n_out=hidden))
                .layer(DenseLayer(n_in=hidden, n_out=hidden))
                .layer(OutputLayer(n_in=hidden, n_out=classes,
                                   activation="softmax", loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    def state_bytes_dev0(net):
        """Exact params+updater bytes resident on device 0 (a replicated
        leaf costs its full size per device; a sharded leaf 1/N)."""
        total = 0
        for leaf in (jax.tree_util.tree_leaves(net.params)
                     + jax.tree_util.tree_leaves(net.updater_state)):
            shards = getattr(leaf, "addressable_shards", None)
            total += (shards[0].data.nbytes if shards
                      else getattr(leaf, "nbytes", 0))
        return total

    def mem_gauges():
        mem = sample_device_memory().get("devices") or {}
        in_use = [r.get("bytes_in_use") for r in mem.values()
                  if r.get("bytes_in_use") is not None]
        peak = [r.get("peak_bytes_in_use") for r in mem.values()
                if r.get("peak_bytes_in_use") is not None]
        return (max(in_use) if in_use else None,
                max(peak) if peak else None)

    def run(style, two_d):
        net = build_net()
        b = ParallelWrapper.Builder(net)
        if two_d:
            b = b.tensor_parallel(model_extent)
        if style == "ws":
            b = b.weight_update_sharding()
        elif style == "fsdp":
            b = b.fsdp()
        pw = b.build()
        it = ListDataSetIterator(batches)
        pw.fit(it, epochs=1)                 # compile + placement, un-timed
        it0 = pw.iteration_count
        t0 = time.perf_counter()
        pw.fit(it, epochs=2)
        _sync(net.score_)
        dt = time.perf_counter() - t0
        n_steps = pw.iteration_count - it0
        in_use, peak = mem_gauges()
        return {"steps_per_sec": round(n_steps / dt, 2),
                "state_bytes_per_device": int(state_bytes_dev0(net)),
                "bytes_in_use": in_use, "peak_bytes": peak}

    grid = {}
    for style in ("replicated", "ws", "fsdp"):
        for two_d in (False, True):
            key = f"{style}_{'2d' if two_d else '1d'}"
            grid[key] = run(style, two_d)
    n_params = (n_in * hidden + hidden + hidden * hidden + hidden
                + hidden * classes + classes)
    PARALLEL_MEMORY_STATS.update({
        "steps": steps, "params": n_params, "model_extent": model_extent,
        "devices": len(jax.devices()), "grid": grid,
        "virtual_cpu_mesh": False,
        # the memory win as one number: ZeRO-3 state bytes vs replicated,
        # on the composed 2-D mesh
        "fsdp_vs_replicated_state_ratio": round(
            grid["fsdp_2d"]["state_bytes_per_device"]
            / max(grid["replicated_2d"]["state_bytes_per_device"], 1), 4),
    })
    return grid["fsdp_2d"]["steps_per_sec"]


def bench_word2vec(n_sentences=20000, sent_len=40, vocab_target=5000):
    """Word2Vec skip-gram (HS) words/sec through the jitted kernels.
    800k-word corpus so steady-state batch throughput dominates the one-time
    vocab build + kernel compile (PerformanceListener-style accounting)."""
    from deeplearning4j_tpu.nlp import Word2Vec

    rng = np.random.default_rng(0)
    zipf = rng.zipf(1.3, size=n_sentences * sent_len) % vocab_target
    words = zipf.reshape(n_sentences, sent_len)
    sentences = [" ".join(f"w{t}" for t in row) for row in words]
    w2v = Word2Vec(vector_length=128, window=5, epochs=1, batch_size=8192,
                   min_word_frequency=1)
    t0 = time.perf_counter()
    w2v.fit(sentences)
    dt = time.perf_counter() - t0
    return n_sentences * sent_len / dt


def _inception_v3_h5():
    """The REAL tf.keras InceptionV3 (313 layers, 23.9M params at 1000
    classes), weights=None (random init — zero egress), saved once to a
    local cache in legacy h5 format. The round-3 bench fed a 36 KB 16×16
    toy while BASELINE.md promised 'Keras-imported InceptionV3' — this
    makes the metric measure the promised model (VERDICT r3 item 7)."""
    cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench_cache")
    path = os.path.join(cache, "inception_v3_299.h5")
    if os.path.exists(path):
        return path
    os.makedirs(cache, exist_ok=True)
    os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
    import tensorflow as tf
    tf.keras.utils.set_random_seed(7)
    m = tf.keras.applications.InceptionV3(weights=None,
                                          input_shape=(299, 299, 3),
                                          classes=1000)
    m.save(path)
    return path


def bench_keras_import_parallel(batch_per_step=128, iters=10):
    """Real Keras-imported InceptionV3 (299×299, 1000 classes) trained
    under ParallelWrapper (BASELINE.md config 6; single chip → one worker,
    the multi-chip path is exercised by the virtual-mesh dryrun)."""
    import jax
    from deeplearning4j_tpu.keras.model_import import KerasModelImport
    from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMode
    from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator

    net = KerasModelImport.import_keras_model_and_weights(_inception_v3_h5())
    net.gc.compute_dtype = "bfloat16"
    # epoch reuse of the 147 MB global batch: without the device cache the
    # measurement is bound by the host→device link, not a property of the
    # training step
    net.gc.cache_mode = "device"
    rng = np.random.default_rng(0)
    n_dev = len(jax.devices())
    dsets = [DataSet(rng.normal(size=(batch_per_step // n_dev, 3, 299, 299)
                                ).astype(np.float32),
                     np.eye(1000, dtype=np.float32)[
                         rng.integers(0, 1000, batch_per_step // n_dev)])
             for _ in range(n_dev)]
    pw = (ParallelWrapper.Builder(net).training_mode(TrainingMode.AVERAGING)
          .averaging_frequency(1)
          # images + bf16 compute: host-side cast halves the H2D bytes of
          # the warm-up/first-epoch transfer, bit-identical results
          # (parity-tested). The TIMED loop reuses the device cache
          # (cache_mode='device'), so this shortens the un-timed first
          # pass — the first-epoch path the overlap work targets — without
          # touching the steady-state number
          .host_transfer_dtype("bfloat16").build())
    pw.fit(ListDataSetIterator(dsets))  # compile + one pass
    _sync(net.params)
    t0 = time.perf_counter()
    for _ in range(iters):
        pw.fit(ListDataSetIterator(dsets))
    # pw.last_score is already a host float: close on a param leaf
    _sync(net.params)
    dt = time.perf_counter() - t0
    return batch_per_step * iters / dt


def transformer_lm_net(vocab=4096, embed=512, heads=8, blocks=8):
    """Decoder-only TransformerLM (pre-LN residual CG), bf16 compute."""
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = TransformerLM(vocab_size=vocab, embed_dim=embed, num_heads=heads,
                         num_blocks=blocks, seed=1).conf()
    conf.global_conf.compute_dtype = "bfloat16"
    return ComputationGraph(conf).init()


def token_batch(batch, seq_len, vocab):
    """Seeded token ids [b, T] (f32-encoded) and one-hot next-token labels
    [b, T, vocab]."""
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq_len)).astype(np.float32)
    labels = np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, size=(batch, seq_len))]
    return ids, labels


def bench_transformer_lm(batch=4, seq_len=8192, vocab=4096, embed=512,
                         heads=8, blocks=8, iters=10):
    """Net-new flagship: decoder-only TransformerLM (T=8192 rides the
    Pallas flash-attention kernel — the dense path would materialize
    8 × [b, h, T, T] logits) tokens/sec. Not a BASELINE.md config (the
    reference predates transformers) — measured as the framework's own
    long-context headline."""
    import jax
    import jax.numpy as jnp

    net = transformer_lm_net(vocab, embed, heads, blocks)
    ids, l = (jnp.asarray(a) for a in token_batch(batch, seq_len, vocab))
    step = net._ensure_step()
    state = {"p": net.params, "s": net.states, "u": net.updater_state}
    key = jax.random.PRNGKey(0)

    def one(i):
        it = jnp.asarray(i, jnp.int32)
        state["p"], state["s"], state["u"], loss = step(
            state["p"], state["s"], state["u"], it, key, (ids,), (l,),
            None, None)
        return loss

    dt = _time_steps(one, n_timed=iters)
    return batch * seq_len * iters / dt


LINT_FULL_STATS = {}


def bench_lint_full(repeats=3):
    """tpulint whole-package cost (analysis/): wall-seconds for one full
    default run — every rule, including the interprocedural lock graph
    (THR003/THR004) and the racegraph lockset pass (THR005) — against
    the shipped baseline. Pure host CPU, no backend needed. Latches
    {wall_s, files, rules, findings_new, findings_baselined} into
    ``LINT_FULL_STATS`` for the ``--one`` record so a linter cost
    regression shows up in the trajectory next to the numbers it taxes
    (the pre-commit hook and the tier-1 self-host guard both pay this
    wall time). Headline value: best-of-N wall seconds (lower is
    better)."""
    from deeplearning4j_tpu.analysis import (Linter, load_baseline,
                                             DEFAULT_BASELINE_PATH,
                                             PACKAGE_ROOT, all_rules)
    baseline = load_baseline(DEFAULT_BASELINE_PATH)
    best, res = None, None
    for _ in range(int(repeats)):
        t0 = time.perf_counter()
        res = Linter().run([PACKAGE_ROOT], baseline=baseline)
        dt = time.perf_counter() - t0
        best = dt if best is None or dt < best else best
    LINT_FULL_STATS.update({
        "wall_s": round(best, 3),
        "files": res.files_checked,
        "rules": len(all_rules()),
        "findings_new": len(res.new),
        "findings_baselined": len(res.baselined),
    })
    return round(best, 3)


# The seven chip cells come last in the sweep, smallest first; the CPU
# counters before them need no chip.
ALL_BENCHES = [
    ("lenet_mnist_images_per_sec", "images/sec", bench_lenet),
    ("input_pipeline_images_per_sec", "images/sec", bench_input_pipeline),
    ("paramserver_steps_per_sec", "steps/sec", bench_paramserver),
    ("paramserver_overlap_steps_per_sec", "steps/sec",
     bench_paramserver_overlap),
    ("parallel_memory", "steps/sec", bench_parallel_memory),
    ("serving_latency_qps", "req/sec", bench_serving_latency),
    ("control_loop_time_to_recover_s", "s", bench_control_loop),
    ("fleet_scrape_p99_ms", "ms", bench_fleet_scrape),
    ("probe_overhead_p99_pct", "%", bench_probe_overhead),
    ("incident_overhead_pct", "%", bench_incident_overhead),
    ("lint_full_wall_s", "s", bench_lint_full),
    ("graves_lstm_charrnn_chars_per_sec", "chars/sec", bench_graves_lstm),
    ("keras_inception_parallelwrapper_images_per_sec", "images/sec",
     bench_keras_import_parallel),
    ("transformer_lm_tokens_per_sec", "tokens/sec", bench_transformer_lm),
    ("resnet50_imagenet_images_per_sec", "images/sec", bench_resnet50),
    ("vgg16_imagenet_images_per_sec", "images/sec", bench_vgg16),
    ("word2vec_skipgram_words_per_sec", "words/sec", bench_word2vec),
]

#: configs whose number is a statement about the chip: ``--one`` refuses
#: to run them on any other platform. The rest are CPU-harness counters.
CHIP_CONFIGS = frozenset({
    "lenet_mnist_images_per_sec",
    "graves_lstm_charrnn_chars_per_sec",
    "keras_inception_parallelwrapper_images_per_sec",
    "transformer_lm_tokens_per_sec",
    "resnet50_imagenet_images_per_sec",
    "vgg16_imagenet_images_per_sec",
    "word2vec_skipgram_words_per_sec",
})

HEADLINE = "resnet50_imagenet_images_per_sec"


def _device_doc():
    """The device this process measures on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _jitwatch_snapshot():
    """Compact jitwatch block (compiles / compile seconds / cache-miss
    ratio, per-fn detail) embedded in each --one record, so a record
    separates compile cost from steady-state step time. None when nothing
    was monitored."""
    from deeplearning4j_tpu.monitor.jitwatch import get_jit_registry
    table = get_jit_registry().table()
    if not table:
        return None
    compiles = sum(r["compiles"] for r in table.values())
    calls = sum(r["calls"] for r in table.values())
    return {
        "compiles": compiles,
        "compile_s": round(sum(r["compile_seconds"]
                               for r in table.values()), 3),
        "cache_miss_ratio": (round(compiles / calls, 4) if calls else None),
        "per_fn": {n: {"compiles": r["compiles"], "calls": r["calls"],
                       "compile_s": r["compile_seconds"]}
                   for n, r in table.items()},
    }


def run_one(name):
    """``--one`` child: run exactly one config in this process and print
    its record. The only mode that touches jax."""
    unit, fn = next((u, f) for n, u, f in ALL_BENCHES if n == name)
    device = _device_doc()
    if name in CHIP_CONFIGS and device["platform"] != "tpu":
        raise SystemExit(
            f"bench.py: {name} is a chip config and jax found platform "
            f"{device['platform']!r} ({device['device_kind']}), not a TPU")
    if device["platform"] == "tpu":
        # one fixed directory per checkout (the path is part of the cache
        # key's lookup): the configs of a sweep, each in its own process,
        # share compiles. JAX_COMPILATION_CACHE_DIR outranks it.
        from deeplearning4j_tpu.compilecache import enable
        enable(os.path.join(_ROOT, ".jax_cache"))
    value = round(fn(), 1)
    from deeplearning4j_tpu.monitor import get_registry
    print(json.dumps({
        "one": name, "value": value, "unit": unit, "device": device,
        "monitor": get_registry().snapshot() or None,
        "jitwatch": _jitwatch_snapshot(),
        # per-config comparison blocks, each latched by its own config
        # and None elsewhere
        "input_pipeline": INPUT_PIPELINE_STATS or None,
        "paramserver": PARAMSERVER_STATS or None,
        "paramserver_overlap": PARAMSERVER_OVERLAP_STATS or None,
        "parallel_memory": PARALLEL_MEMORY_STATS or None,
        "serving": SERVING_STATS or None,
        "cold_start": COLD_START_STATS or None,
        "control_loop": CONTROL_LOOP_STATS or None,
        "fleet_scrape": FLEET_SCRAPE_STATS or None,
        "probe_overhead": PROBE_OVERHEAD_STATS or None,
        "incident_overhead": INCIDENT_OVERHEAD_STATS or None,
        "lint_full": LINT_FULL_STATS or None}))


def _run_one_subprocess(name, timeout_s=2400):
    """Run one config in its own ``--one`` child (a chip belongs to one
    process at a time, and trace-time knobs need a fresh process) and
    return its record. Raises RuntimeError when the child fails, times
    out or prints no record; its stderr passes straight through."""
    import subprocess

    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", name],
            stdout=subprocess.PIPE, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise RuntimeError(f"{name}: no result after {timeout_s}s") from None
    if p.returncode != 0:
        raise RuntimeError(f"{name}: child exited rc={p.returncode}")
    for line in reversed(p.stdout.decode().splitlines()):
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and doc.get("one") == name:
            return doc
    raise RuntimeError(f"{name}: no result line in the child's output")


def main(argv):
    if "--one" in argv:
        run_one(argv[argv.index("--one") + 1])
        return 0
    names = ([n for n, _, _ in ALL_BENCHES] if "--all" in argv
             else [HEADLINE])
    failed = []
    headline = None
    for name in names:
        try:
            doc = _run_one_subprocess(name)
        except RuntimeError as e:
            print(f"# FAILED {e}", file=sys.stderr)
            failed.append(name)
            continue
        d = doc["device"]
        print(f"# {name}: {doc['value']} {doc['unit']} on "
              f"{d['device_count']}x {d['device_kind']} ({d['platform']})",
              file=sys.stderr)
        if name == HEADLINE:
            headline = doc
    if headline is not None:
        print(json.dumps({"metric": HEADLINE, "value": headline["value"],
                          "unit": headline["unit"],
                          "device": headline["device"],
                          "monitor": headline["monitor"],
                          "jitwatch": headline["jitwatch"]}))
    if failed:
        print(f"# {len(failed)} of {len(names)} configs failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

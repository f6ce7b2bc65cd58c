"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process — the only one that touches JAX — drives the public entry
points once at bench width on whatever TPU JAX reports, checks what comes
out, and ends its standard output with two JSON lines: the report
(``{"report": "chip_smoke", "versions": ..., "compile_cache": ...,
"legs": ...}``) and, last, the result, which holds these keys and no other::

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

It exits non-zero, printing neither line, when JAX finds no TPU (within
seconds, before any model is built) and when any leg fails. The numbers in
the report are smoke observations (compile seconds, a steady step, compile
and cache counts, peak device memory): they say the path ran and how it
started, not how fast the system is.

Legs:
  A  zoo ResNet50 (ComputationGraph, 224×224, bf16 compute, batch 256)
     trained with ``net.fit(iterator)``: finite falling loss, state on the
     TPU, one compile, and the same timed window closed once with
     ``block_until_ready`` and once with a value fetch.
  B  every Pallas kernel family compiled by Mosaic (``interpret=False``)
     and held to its oracle at the bench shape — flash attention forward
     and gradients plain / masked / with dropout (``perf_flash_check``),
     ``lstm_cell`` with an f32 and a bf16 reserve and ``lstm_fused``
     (``perf_lstm``) — then one ``fit`` each of the TransformerLM and the
     char-RNN bench configs, whose compiled step must hold the custom call.
  C  (more than one device) leg A's model through ``ParallelWrapper`` over
     all local devices, 256 per chip: batch and parameters on every
     device, an all-reduce in the compiled step, leg A's loss trajectory.
  D  zoo LeNet served in bf16 by an ``InferenceServer``: concurrent
     ``POST …/predict`` over HTTP, answers held to ``net.output``.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the shapes the legs run at
SHAPES = {
    "resnet50": {"batch": 256},
    "flash": {"b": 4, "T": 8192, "h": 8, "d": 64},
    "lstm": {"batch": 64, "width": 512, "tbptt": 50},
    "transformer_lm": {"batch": 4, "seq_len": 8192, "vocab": 4096,
                       "embed": 512, "heads": 8, "blocks": 8},
    "graves_lstm": {"batch": 64, "seq_len": 200, "tbptt": 50, "width": 512},
}

#: |bf16 served - f32 reference| allowed on softmax outputs (leg D)
BF16_ATOL = 3e-2
#: leg C against leg A, per step: the same bf16 math compiled as another
#: program (other fusions, other reduction orders) on a loss that falls
#: from ~8 towards 0 in eight steps
TRAJECTORY_TOL = {"rtol": 0.1, "atol": 0.1}


class CompileLog:
    """Every backend compile request jax makes in this process (name,
    seconds, thread), and what the persistent cache did with it — read
    straight off ``jax.monitoring``, so the jitwatch cost worker's
    background re-compiles are counted too."""

    def __init__(self):
        self.compiles = []
        self.cache = {"requests": 0, "hits": 0, "misses": 0}

    def install(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((kw.get("fun_name", "?"), float(seconds),
                                  threading.current_thread().name))

    def _event(self, event, **kw):
        key = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}.get(event)
        if key:
            self.cache[key] += 1

    def since(self, mark):
        """Summary of the compile requests logged after ``mark``."""
        rows = self.compiles[mark:]
        worker = [r for r in rows if r[2].startswith("jitwatch-cost")]
        slowest = sorted(rows, key=lambda r: -r[1])[:3]
        return {"count": len(rows),
                "seconds": round(sum(r[1] for r in rows), 2),
                "cost_worker_count": len(worker),
                "cost_worker_seconds": round(sum(r[1] for r in worker), 2),
                "slowest": [[n, round(s, 2), t] for n, s, t in slowest]}


def _jit_compiles():
    from deeplearning4j_tpu.monitor.jitwatch import get_jit_registry
    return sum(r["compiles"] for r in get_jit_registry().table().values())


def _on_tpu(tree):
    import jax
    return all(d.platform == "tpu" for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


def _resnet50(batch):
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = ResNet50(num_classes=1000).conf()
    conf.global_conf.compute_dtype = "bfloat16"
    rng = np.random.default_rng(0)
    ds = DataSet(rng.normal(size=(batch, 3, 224, 224)).astype(np.float32),
                 np.eye(1000, dtype=np.float32)[
                     rng.integers(0, 1000, batch)])
    return ComputationGraph(conf).init(), ds


# ------------------------------------------------------------------- leg A
def leg_a(steps=8):
    import jax
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
    from deeplearning4j_tpu.monitor.jitwatch import (get_jit_registry,
                                                     wait_cost_captures)
    from deeplearning4j_tpu.optimize.listeners import \
        CollectScoresIterationListener

    net, ds = _resnet50(**SHAPES["resnet50"])
    t0 = time.perf_counter()
    net.fit(ds)                               # step 1: trace, compile, run
    losses = [float(net.score_)]
    compile_s = time.perf_counter() - t0
    assert _on_tpu((net.params, net.updater_state, net.score_)), \
        "leg A: params / updater state / loss are not on a TPU device"
    # the jitwatch cost worker re-lowers the step in the background; wait
    # it out so it cannot sit inside a timed window, and report what it
    # cost (a compile there shows on its own thread in CompileLog)
    t0 = time.perf_counter()
    assert wait_cost_captures(timeout=600.0), "cost capture still running"
    cost_wait_s = time.perf_counter() - t0

    scores = CollectScoresIterationListener()
    net.set_listeners(scores)
    net.fit(ListDataSetIterator([ds] * (steps - 1)))
    net.set_listeners()
    losses += [v for _, v in scores.scores]

    def window(close):
        t0 = time.perf_counter()
        net.fit(ListDataSetIterator([ds] * steps))
        close()
        return (time.perf_counter() - t0) / steps

    step_bur = window(lambda: jax.block_until_ready((net.params,
                                                     net.score_)))
    step_fetch = window(lambda: float(net.score_))
    last = float(net.score_)
    row = get_jit_registry().table()["cg/step"]
    print(f"leg A: losses {[round(v, 4) for v in losses]} … {last:.4f}; "
          f"step {step_bur * 1e3:.1f} ms closed by block_until_ready, "
          f"{step_fetch * 1e3:.1f} ms closed by float(loss)", flush=True)
    assert len(losses) == steps and np.all(np.isfinite(losses + [last])), \
        losses
    assert last < losses[0], (losses, last)
    assert row["compiles"] == 1 and row["calls"] == 3 * steps, row
    # a barrier that returned before the device finished would close its
    # window far too early
    assert 0.5 < step_bur / step_fetch < 2.0, (step_bur, step_fetch)
    return {"compile_s": round(compile_s, 2),
            "cost_capture_wait_s": round(cost_wait_s, 2),
            "step_s_block_until_ready": round(step_bur, 4),
            "step_s_value_fetch": round(step_fetch, 4),
            "losses": [round(v, 4) for v in losses],
            "last_loss": round(last, 4)}


# ------------------------------------------------------------------- leg B
def transformer_lm_net(vocab, embed, heads, blocks):
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
    import numpy as np

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq_len)).astype(np.float32)
    labels = np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, size=(batch, seq_len))]
    return ids, labels


def _fit_holds_custom_call(name, net, ds, lowered_for):
    """One public ``fit`` of a bench config, then the text of the program
    it compiled: the Mosaic custom call must be in it."""
    import numpy as np

    t0 = time.perf_counter()
    net.fit(ds)
    loss = float(net.score_)
    fit_s = time.perf_counter() - t0
    n = lowered_for(net).compile().as_text().count("tpu_custom_call")
    print(f"leg B: {name} fit step loss {loss:.4f} in {fit_s:.1f}s, "
          f"{n} tpu_custom_call in the compiled step", flush=True)
    assert np.isfinite(loss), loss
    assert n > 0, f"{name}: no Pallas kernel in the compiled step"
    return {"fit_s": round(fit_s, 2), "loss": round(loss, 4),
            "custom_calls": n}


def leg_b():
    import jax
    import jax.numpy as jnp
    import perf_flash_check
    import perf_lstm
    from deeplearning4j_tpu.datasets.dataset import DataSet

    out = {"flash": perf_flash_check.check(**SHAPES["flash"]),
           "lstm": perf_lstm.kernel_check(**SHAPES["lstm"])}

    lm = SHAPES["transformer_lm"]
    ids, labels = token_batch(lm["batch"], lm["seq_len"], lm["vocab"])
    out["transformer_lm"] = _fit_holds_custom_call(
        "transformer_lm",
        transformer_lm_net(lm["vocab"], lm["embed"], lm["heads"],
                           lm["blocks"]),
        DataSet(ids, labels),
        # lowering reads shapes only: nothing is transferred or run
        lambda net: net._ensure_step().lower(
            net.params, net.states, net.updater_state,
            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
            (ids,), (labels,), None, None))
    del ids, labels
    gc.collect()

    rnn = SHAPES["graves_lstm"]
    ds = perf_lstm.char_stream(rnn["batch"], rnn["seq_len"])
    out["graves_lstm"] = _fit_holds_custom_call(
        "graves_lstm",
        perf_lstm.graves_lstm_net(width=rnn["width"], tbptt=rnn["tbptt"]),
        ds,
        lambda net: perf_lstm.lower_tbptt_batch(net, ds))
    return out


# ------------------------------------------------------------------- leg C
def leg_c(losses_a):
    import jax
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import \
        CollectScoresIterationListener
    from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMode
    from deeplearning4j_tpu.parallel.sharding import put_replicated

    devices = set(jax.devices())
    n = len(devices)
    batch = SHAPES["resnet50"]["batch"]
    net, ds = _resnet50(batch)
    pw = (ParallelWrapper.Builder(net)
          .training_mode(TrainingMode.AVERAGING).averaging_frequency(1)
          .build())
    assert set(pw.mesh.devices.flat) == devices, pw.mesh
    scores = CollectScoresIterationListener()
    net.set_listeners(scores)
    t0 = time.perf_counter()
    # every chip takes leg A's batch each step: the averaged gradient is
    # leg A's gradient, so the trajectory must be leg A's
    pw.fit(ListDataSetIterator([ds] * (n * len(losses_a))))
    fit_s = time.perf_counter() - t0
    net.set_listeners()
    losses = [v for _, v in scores.scores]

    for leaf in jax.tree_util.tree_leaves((net.params, net.updater_state)):
        assert leaf.sharding.device_set == devices, leaf.sharding
    f, l, _, _ = pw._global_batch([ds] * n)
    for x in jax.tree_util.tree_leaves((f, l)):
        assert x.sharding.device_set == devices, x.sharding
        assert x.shape[0] == n * batch, x.shape
        assert {s.data.shape[0] for s in x.addressable_shards} == {batch}
    # lowered from the placed arrays themselves, as _fit_sync calls it
    text = pw._ensure_sync_step().lower(
        net.params, net.states, net.updater_state,
        jax.numpy.asarray(net.iteration_count, jax.numpy.int32),
        put_replicated(jax.random.PRNGKey(0), pw.mesh), f, l, None,
        None).compile().as_text()
    n_ar = text.count("all-reduce")
    assert len(losses) == len(losses_a), losses
    diff = float(np.max(np.abs(np.asarray(losses) - losses_a)))
    print(f"leg C: {n} devices, losses {[round(v, 4) for v in losses]}, "
          f"max |diff| to leg A {diff:.4f}, {n_ar} all-reduce in the "
          f"compiled step", flush=True)
    assert n_ar > 0, "no all-reduce in the data-parallel step"
    assert np.allclose(losses, losses_a, **TRAJECTORY_TOL), \
        (losses, losses_a)
    return {"devices": n, "fit_s": round(fit_s, 2),
            "losses": [round(v, 4) for v in losses],
            "max_abs_diff_to_leg_a": round(diff, 5), "all_reduces": n_ar}


# ------------------------------------------------------------------- leg D
def leg_d():
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np
    from deeplearning4j_tpu.models import LeNet
    from deeplearning4j_tpu.serving import InferenceServer

    net = LeNet(num_classes=10).init()
    rng = np.random.default_rng(0)
    # request sizes per wave. A wave totals fewer rows than the largest
    # bucket, so it flushes once, when its first request's linger runs out
    # (7 rows padded to 8, 3 to 4): the programs this leg compiles do not
    # depend on thread timing. The repeats pad into the bucket buffers the
    # first two flushes donated.
    waves = ((1, 2, 1, 2, 1), (2, 1)) * 2
    xs = rng.normal(size=(7, 1, 28, 28)).astype(np.float32)
    # the f32 answer, taken before registration flips the net to bf16
    want = np.asarray(net.output(xs))

    srv = InferenceServer()
    t0 = time.perf_counter()
    srv.register("lenet", net, precision="bf16", batch_buckets=(1, 2, 4, 8),
                 input_shape=(1, 28, 28), warmup=True, linger_ms=200.0)
    warm_s = time.perf_counter() - t0
    port = srv.start(port=0)
    url = f"http://127.0.0.1:{port}/v1/models/lenet/predict"

    def post(idx):
        req = urllib.request.Request(
            url, data=json.dumps({"inputs": xs[idx].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return idx, np.asarray(json.loads(resp.read())["outputs"],
                                   np.float32)

    worst, answered = 0.0, 0
    t0 = time.perf_counter()
    try:
        with ThreadPoolExecutor(max_workers=max(map(len, waves))) as pool:
            for sizes in waves:
                parts = np.split(np.arange(sum(sizes)),
                                 np.cumsum(sizes)[:-1])
                for idx, got in pool.map(post, parts):
                    assert got.shape == want[idx].shape, got.shape
                    worst = max(worst,
                                float(np.max(np.abs(got - want[idx]))))
                    answered += 1
    finally:
        srv.stop()
    serve_s = time.perf_counter() - t0
    print(f"leg D: {answered} requests answered, "
          f"max |bf16 served - f32 net.output| = {worst:.4f}", flush=True)
    assert worst < BF16_ATOL, worst
    return {"warm_s": round(warm_s, 2), "requests": answered,
            "serve_s": round(serve_s, 2), "max_abs_err": round(worst, 5)}


# -------------------------------------------------------------------- main
def result_line(ok, dev):
    """The last line of standard output: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) — whoever runs the smoke parses
    it, so everything else goes into the report line before it."""
    return json.dumps({"ok": bool(ok),
                       "device": {"platform": str(dev["platform"]),
                                  "kind": str(dev["kind"]),
                                  "count": int(dev["count"])}})


def main():
    t_start = time.perf_counter()
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: jax found platform {dev['platform']!r} "
              f"({dev['count']}x {dev['kind']}), not a TPU — nothing ran",
              file=sys.stderr)
        return 2

    log = CompileLog()
    log.install()
    from deeplearning4j_tpu.compilecache import enable
    # the cache lives where JAX_COMPILATION_CACHE_DIR says; only without
    # it, at the fixed path of this checkout (the path is part of how an
    # entry is found again, so it never moves)
    cache_dir = enable(os.path.join(ROOT, ".jax_cache"))
    import jaxlib
    from importlib.metadata import version
    print(f"chip_smoke: {dev['count']}x {dev['kind']}, jax {jax.__version__}, "
          f"compile cache {cache_dir}", flush=True)

    legs = {}

    def run(name, fn, *args):
        mark, jit0 = len(log.compiles), _jit_compiles()
        t0 = time.perf_counter()
        out = fn(*args)
        out.update(
            seconds=round(time.perf_counter() - t0, 1),
            jit_compiles=_jit_compiles() - jit0,
            backend_compiles=log.since(mark),
            # the allocator's peak is cumulative over the process
            peak_bytes_in_use=max(
                d.memory_stats()["peak_bytes_in_use"] for d in devices))
        legs[name] = out
        gc.collect()
        return out

    a = run("A", leg_a)
    run("B", leg_b)
    if dev["count"] > 1:
        run("C", leg_c, a["losses"])
    run("D", leg_d)

    print(json.dumps({
        "report": "chip_smoke", "device": dev,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": version("libtpu")},
        "compile_cache": dict(log.cache, dir=cache_dir),
        "seconds": round(time.perf_counter() - t_start, 1),
        "note": "smoke observations, not benchmark numbers",
        "legs": legs}))
    print(result_line(True, dev), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Perf experiment harness (not part of the framework; PERF.md records results).

Modes (run on the chip):

  python perf_exp.py 64 128 256      # batch-size sweep (legacy spelling)
  python perf_exp.py sweep 64 256    # same, explicit
  python perf_exp.py remat           # batch 384/512,
                                     # remat off vs auto (HBM-wall push)
  python perf_exp.py cost [BATCH]    # XLA cost model + roofline bound
  python perf_exp.py full            # cost + sweep + remat
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from bench import device_peaks


def _setup(batch, compute_dtype="bfloat16", remat="off"):
    """One model+data builder for bench AND cost — the cost model must
    lower exactly the program the benchmark runs."""
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = ResNet50(num_classes=1000).conf()
    conf.global_conf.compute_dtype = compute_dtype
    conf.global_conf.remat = remat
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    f = jnp.asarray(rng.normal(size=(batch, 3, 224, 224)), jnp.float32)
    l = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)])
    return net, f, l


def bench_resnet(batch=256, iters=10, warmup=3, compute_dtype="bfloat16",
                 remat="off"):
    net, f, l = _setup(batch, compute_dtype, remat)
    step = net._ensure_step()
    params, states, upd = net.params, net.states, net.updater_state
    key = jax.random.PRNGKey(0)
    for i in range(warmup):
        it = jnp.asarray(i, jnp.int32)
        params, states, upd, loss = step(params, states, upd, it, key, (f,), (l,), None, None)
    float(loss)  # dispatch is asynchronous: the value fetch closes the window
    t0 = time.perf_counter()
    for i in range(warmup, warmup + iters):
        it = jnp.asarray(i, jnp.int32)
        params, states, upd, loss = step(params, states, upd, it, key, (f,), (l,), None, None)
    float(loss)  # dispatch is asynchronous: the value fetch closes the window
    dt = time.perf_counter() - t0
    ips = batch * iters / dt
    print(f"batch={batch} dtype={compute_dtype} remat={remat}: "
          f"{ips:.1f} img/s ({dt / iters * 1e3:.1f} ms/step)")
    return ips


def remat_ab():
    """Push past the HBM wall — larger batches amortize
    fixed traffic but blow activation memory; remat='auto' (saveable
    conv/gemm outputs, recompute the cheap elementwise chains) trades
    recompute FLOPs for HBM. Keep or revert BY MEASUREMENT; failures
    (OOM) are recorded, not fatal."""
    for batch in (384, 512):
        for remat in ("off", "auto"):
            try:
                bench_resnet(batch=batch, remat=remat)
            except Exception as e:
                print(f"batch={batch} remat={remat} FAILED: "
                      f"{str(e)[:200]}", flush=True)


def cost(batch=256, remat="off"):
    """XLA cost model of the ResNet50 train step + roofline bound at the
    device's published peaks — the before/after instrument for any
    layout/fusion change."""
    peaks = device_peaks()
    net, f, l = _setup(batch, remat=remat)
    step = net._ensure_step()
    lowered = step.lower(net.params, net.states, net.updater_state,
                         jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                         (f,), (l,), None, None)
    ca = lowered.compile().cost_analysis()
    flops = float(ca["flops"])
    by = float(ca["bytes accessed"])
    t_f, t_h = flops / peaks["flops_bf16"], by / peaks["hbm_bytes_per_s"]
    bound = "HBM" if t_h > t_f else "compute"
    print(f"batch={batch} remat={remat}: {flops/1e12:.2f} TFLOP, "
          f"{by/1e9:.1f} GB/step -> ideal {batch/max(t_f, t_h):,.0f} img/s "
          f"({bound}-bound)")


def main(argv):
    if not argv or argv[0].isdigit():
        for b in (int(x) for x in argv or ["256"]):
            bench_resnet(batch=b)
    elif argv[0] == "sweep":
        for b in (int(x) for x in argv[1:] or ["64", "128", "256"]):
            bench_resnet(batch=b)
    elif argv[0] == "remat":
        remat_ab()
    elif argv[0] == "cost":
        cost(int(argv[1]) if len(argv) > 1 else 256)
        cost(int(argv[1]) if len(argv) > 1 else 256, remat="auto")
    elif argv[0] == "full":
        cost(256)
        cost(512, remat="auto")
        for b in (128, 256):
            bench_resnet(batch=b)
        remat_ab()
    elif argv[0] == "bench2":
        for b in (128, 256):
            bench_resnet(batch=b)
    else:
        raise SystemExit(f"unknown mode {argv[0]}")


if __name__ == "__main__":
    main(sys.argv[1:])

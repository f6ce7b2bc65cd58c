"""On-chip check of the flash-attention kernels against the dense oracle.

Interpret-mode tests cannot see what Mosaic does with a kernel; this
compiles the forward and both backward kernels for the device and holds
them to the dense reference — plain causal, with a key-padding mask that
leaves some query rows with no visible key, and with in-kernel dropout.
``chip_smoke.py`` imports :func:`check`; by hand:

    python perf_flash_check.py             # the transformer bench shape
    python perf_flash_check.py blocksweep  # A/B DL4J_TPU_FLASH_BLOCK
"""
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

#: max |flash - dense| the kernels are held to with bf16 operands, as a
#: share of max |dense| over the compared tensor
FWD_TOL = 2e-2
GRAD_TOL = 5e-2


def dense_ref(q, k, v, causal, km=None, keep=None, rate=0.0):
    """Dense attention in f32. ``keep`` ([b, h, T, T] 0/1) applies dropout
    to the normalized probabilities the way the kernels do."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    T = s.shape[-1]
    vis = jnp.ones((T, T), bool)[None, None]
    if causal:
        vis = vis & jnp.tril(jnp.ones((T, T), bool))[None, None]
    if km is not None:
        vis = vis & (km[:, None, None, :] > 0)
    p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1)
    # fully-masked rows output 0 (the framework-wide convention; see
    # ops/flash_attention.py _fwd_kernel)
    p = jnp.where(jnp.any(vis, axis=-1, keepdims=True), p, 0.0)
    if keep is not None:
        p = p * keep / (1.0 - rate)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def _out_and_grads(attend):
    """jit of (out, d(sum out²)/d(q, k, v)) for one attention callable — the
    forward kernel and both backward kernels in one program. Anything
    after (q, k, v) is passed through undifferentiated."""
    def loss(q, k, v, *rest):
        o = attend(q, k, v, *rest).astype(jnp.float32)
        return jnp.sum(o ** 2), o
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))


def check(b=4, T=8192, h=8, d=64, oracle_heads=2, rate=0.3, seed=1234,
          variants=("plain", "masked", "dropout")):
    """Flash forward, dq and dk/dv at [b, T, h, d] bf16 against the dense
    oracle, in three variants: ``plain`` (causal), ``masked`` (causal +
    key-padding mask whose leading keys are all padding, so the first query
    rows have NO visible key) and ``dropout`` (non-causal, in-kernel
    counter-hash PRNG vs :func:`dropout_keep_mask`).

    The kernels run at the full shape. The oracle's [b, h, T, T] logits do
    not fit the device there, and attention is independent per (batch,
    head), so it is computed for batch 0, heads ``[0, oracle_heads)`` and
    compared with that slice of the kernels' outputs and gradients.
    Returns {variant: {fwd_err, dq_err, dk_err, dv_err, seconds}} (errors
    as a share of max |oracle|); raises AssertionError past
    :data:`FWD_TOL` / :data:`GRAD_TOL`."""
    import deeplearning4j_tpu.ops.flash_attention as fa

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, T, h, d)), jnp.bfloat16)
               for _ in range(3))
    km_np = (rng.random((b, T)) > 0.2).astype(np.float32)
    # more than one block of leading padding: whole (q, k) blocks and the
    # head of the next one are fully masked
    km_np[:, :fa.MIN_BLOCK + fa.MIN_BLOCK // 2] = 0.0
    km = jnp.asarray(km_np)
    assert fa.supported(T, d, rate, km_np), (T, d)
    oh = min(oracle_heads, h)
    sl = lambda x: x[:1, :, :oh]
    keep = fa.dropout_keep_mask(oh, T, T, seed, rate)[None]   # batch 0

    # (flash, dense, the dense oracle's extra operands): the [T, T] keep
    # mask is an operand, not a closure constant baked into the program
    runs = {
        "plain": (
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
            lambda q, k, v: dense_ref(q, k, v, True), ()),
        "masked": (
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               key_mask=km),
            lambda q, k, v, km: dense_ref(q, k, v, True, km), (km[:1],)),
        "dropout": (
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=False, dropout_rate=rate,
                dropout_seed=seed),
            lambda q, k, v, keep: dense_ref(q, k, v, False, keep=keep,
                                            rate=rate), (keep,)),
    }
    report = {}
    for name in variants:
        flash, dense, extra = runs[name]
        t0 = time.perf_counter()
        g_f, o_f = _out_and_grads(flash)(q, k, v)
        g_d, o_d = _out_and_grads(dense)(sl(q), sl(k), sl(v), *extra)
        err = lambda a, w: round(float(
            jnp.max(jnp.abs(sl(a).astype(jnp.float32) - w))
            / jnp.max(jnp.abs(w))), 6)
        row = {"fwd_err": err(o_f, o_d)}
        for n, a, w in zip(("dq", "dk", "dv"), g_f, g_d):
            row[f"{n}_err"] = err(a, w)
        row["seconds"] = round(time.perf_counter() - t0, 2)
        print(f"flash {name} b{b} T{T} h{h} d{d}: {row}", flush=True)
        assert row["fwd_err"] < FWD_TOL, (name, row)
        assert max(row["dq_err"], row["dk_err"],
                   row["dv_err"]) < GRAD_TOL, (name, row)
        report[name] = row
    return report


def block_one():
    """Child for blocksweep: time flash fwd and fwd+bwd at the transformer
    bench's attention shapes (bench.py bench_transformer_lm: b=4, h=8,
    T=8192, d=64 -> bh=32). The block size comes from DL4J_TPU_FLASH_BLOCK
    (import-time knob — that is why each value needs a fresh process)."""
    import json

    from bench import _warm_time
    import deeplearning4j_tpu.ops.flash_attention as fa

    rng = np.random.default_rng(0)
    b, T, h, d = 4, 8192, 8, 64
    # the sweep must measure the cap it advertises: pick_block at these
    # shapes has to resolve to exactly the exported cap
    assert fa.pick_block(T, d) == fa.BLOCK, (fa.pick_block(T, d), fa.BLOCK)
    q, k, v = (jnp.asarray(rng.normal(size=(b, T, h, d)), jnp.bfloat16)
               for _ in range(3))
    f = jax.jit(lambda a, b_, c: fa.flash_attention(a, b_, c, causal=True))
    g = jax.jit(jax.grad(lambda a, b_, c: jnp.sum(
        fa.flash_attention(a, b_, c, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))
    tf = _warm_time(f, q, k, v)
    tg = _warm_time(g, q, k, v)
    print(json.dumps({"block": fa.BLOCK, "fwd_ms": tf * 1e3,
                      "fwdbwd_ms": tg * 1e3}))


def blocksweep():
    """A/B DL4J_TPU_FLASH_BLOCK (import-time knob -> fresh subprocess per
    value) at the transformer bench attention shapes. This parent never
    initialises a backend: the chip belongs to one process at a time, and
    each child needs it."""
    import json
    import subprocess
    import sys

    print(f"{'block':>6} {'fwd_ms':>9} {'fwdbwd_ms':>10}")
    # 1024 is excluded: pick_block's [blk,blk]-intermediate budget caps
    # picks at 768, which doesn't divide T=8192 (block-one asserts the
    # pick resolves to the advertised cap)
    for blk in (128, 256, 512):
        env = dict(os.environ, DL4J_TPU_FLASH_BLOCK=str(blk))
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "block-one"],
                capture_output=True, text=True, env=env, timeout=900)
        except subprocess.TimeoutExpired:
            print(f"{blk:>6} FAILED timeout", flush=True)
            continue
        line = None
        for ln in reversed((p.stdout or "").splitlines()):
            try:
                line = json.loads(ln)
                break
            except ValueError:
                continue
        if p.returncode or not line:
            print(f"{blk:>6} FAILED rc={p.returncode} "
                  f"{(p.stderr or '')[-300:]}", flush=True)
            continue
        print(f"{blk:>6} {line['fwd_ms']:>9.1f} {line['fwdbwd_ms']:>10.1f}",
              flush=True)


if __name__ == "__main__":
    import sys
    cmd = sys.argv[1] if len(sys.argv) > 1 else "check"
    if cmd == "blocksweep":
        blocksweep()
    elif cmd == "block-one":
        block_one()
    else:
        print("backend:", jax.default_backend())
        check()
        print("FLASH HARDWARE CHECK OK")

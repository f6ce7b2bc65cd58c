"""On-chip check of the flash-attention kernels against the dense oracle.

Interpret-mode tests cannot see what Mosaic does with a kernel; this
compiles the forward and both backward kernels for the device and holds
them to the dense reference — plain causal, with a key-padding mask that
leaves some query rows with no visible key, and with in-kernel dropout.
``chip_smoke.py`` imports :func:`check`; by hand:

    python perf_flash_check.py             # the transformer bench shape
    python perf_flash_check.py blocksweep  # ms per call over (block_q, block_k)
    python perf_flash_check.py blocksweep 1024  # banded by a window
    python perf_flash_check.py picks       # the chooser's edges and the dense path
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


#: the sweep's shapes (bh, T, d) with the q and k edges tried at each: the
#: looped-LM cell's attention (b2·h16·T4096·d128) over the whole grid, then
#: d 64 at T 4096 and at the transformer bench's T 8192 (the shape
#: ``MIN_SEQ``'s comment was first measured at) without the 128 edges, which
#: the first shape rules out
SWEEP = (((32, 4096, 128), (128, 256, 512, 1024), (128, 256, 512, 1024, 2048)),
         ((32, 4096, 64), (256, 512, 1024), (256, 512, 1024, 2048)),
         ((32, 8192, 64), (256, 512, 1024), (256, 512, 1024, 2048)))
#: the banded walk's shape and edges: the Mellum2 cell's windowed layers
#: (PR 41, a window of 1024)
WINDOW_SWEEP = (((32, 8192, 128), (256, 512, 1024), (256, 512, 1024)),)


def _ms_per_call(fn, *args, iters=20):
    """Mean ms per call of a compiled ``fn`` over ``iters`` queued calls,
    closed by ``block_until_ready``: a fetch of the result (33 MB of dq at
    the cell's shape) would add milliseconds of host link to every row."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _time_kernels(bh, T, d, block_q=None, block_k=None, window=None):
    """ms per call of flash_fwd, flash_dq, flash_dkv (causal, bf16, no mask,
    no dropout) at [bh, T, d] with these edges (None: the chooser's), banded
    by ``window`` where it is given."""
    import deeplearning4j_tpu.ops.flash_attention as fa

    rng = np.random.default_rng(0)
    q, k, v, do = (jnp.asarray(rng.normal(size=(bh, T, d)), jnp.bfloat16)
                   for _ in range(4))
    scale = 1.0 / float(np.sqrt(d))
    kw = dict(block_q=block_q, block_k=block_k, window=window)
    fwd = jax.jit(lambda q, k, v: fa._fwd(q, k, v, None, None, True, scale,
                                          0.0, **kw))
    o, lse = fwd(q, k, v)
    delta = fa.rowwise_delta(do, o)
    dq = jax.jit(lambda *a: fa.dq_block(*a, True, scale, **kw))
    dkv = jax.jit(lambda *a: fa.dkv_block(*a, True, scale, **kw))
    bwd_args = (q, k, v, None, do, delta, lse)
    return {"fwd_ms": _ms_per_call(fwd, q, k, v),
            "dq_ms": _ms_per_call(dq, *bwd_args),
            "dkv_ms": _ms_per_call(dkv, *bwd_args)}


def _time_dense(b, T, h, d):
    """ms of the dense path's forward, and of forward + backward, at
    [b, T, h, d] bf16 causal (``nn/layers/attention._dense_attention``: the
    [b, h, T, T] f32 logits materialize, both halves of the square run)."""
    from deeplearning4j_tpu.nn.layers.attention import _dense_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, T, h, d)), jnp.bfloat16)
               for _ in range(3))
    vis = jnp.tril(jnp.ones((T, T), bool))[None, None]
    dense = lambda q, k, v: _dense_attention(q, k, v, vis, jnp.bfloat16)
    f = jax.jit(dense)
    g = jax.jit(jax.grad(lambda *a: jnp.sum(
        dense(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))
    return {"dense_fwd_ms": _ms_per_call(f, q, k, v, iters=5),
            "dense_fwdbwd_ms": _ms_per_call(g, q, k, v, iters=5)}


def blocksweep(grid=True, window=None,
               out_path=os.path.join("chiprun_out", "flash_sweep.jsonl")):
    """The table :func:`ops.flash_attention.pick_blocks` is made from, in
    one process: per shape and pair of edges the ms per call of each kernel
    and the TFLOP/s of the products it needs (2 forward, 3 in dq, 4 in
    dk/dv, each 2·d FLOP a visible (query, key) pair a head: the causal
    half, or with ``window`` the band's ``WINDOW_SWEEP`` walks), every row
    also appended to ``out_path``; edges that do not divide T or that
    :func:`vmem_bytes` puts past ``VMEM_LIMIT`` are left out, a pair Mosaic
    refuses is printed as such. Then the chooser's own pick per shape (and
    the causal walk at its edges beside a banded one), and without a window
    at T 2048 and the dense path at the cell's shape and at T 2048 beside
    them (``grid`` False: these last rows alone)."""
    import json

    import deeplearning4j_tpu.ops.flash_attention as fa

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    products = {"fwd": 2, "dq": 3, "dkv": 4}

    def row(bh, T, d, bq, bk, w):
        rec = {"bh": bh, "T": T, "d": d, "window": w, "block_q": bq,
               "block_k": bk}
        try:
            rec.update(_time_kernels(bh, T, d, bq, bk, w))
        except Exception as e:  # noqa: BLE001 - Mosaic's refusal is the row
            rec["refused"] = f"{type(e).__name__}: {str(e)[:200]}"
        pairs = (w * (w + 1) // 2 + (T - w) * w) if w else T * T / 2
        for n, c in products.items():
            if f"{n}_ms" in rec:
                rec[f"{n}_tflops"] = (2 * c * bh * pairs * d
                                      / (rec[f"{n}_ms"] * 1e-3) / 1e12)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        if "refused" in rec:
            print(f"{bh:>3} {T:>5} {d:>4} {bq!s:>5} {bk!s:>5} {w!s:>5} "
                  f"{rec['refused']}", flush=True)
            return
        print(f"{bh:>3} {T:>5} {d:>4} {bq!s:>5} {bk!s:>5} {w!s:>5} "
              + " ".join(f"{rec[f'{n}_ms']:>8.3f} {rec[f'{n}_tflops']:>6.1f}"
                         for n in products), flush=True)

    sweep = WINDOW_SWEEP if window else SWEEP
    print(f"{'bh':>3} {'T':>5} {'d':>4} {'bq':>5} {'bk':>5} {'w':>5} "
          + " ".join(f"{n + '_ms':>8} {'TF/s':>6}" for n in products))
    for (bh, T, d), edges_q, edges_k in sweep if grid else ():
        for bq in edges_q:
            for bk in edges_k:
                if T % bq or T % bk or max(
                        fa.vmem_bytes(kern, bq, bk, d, jnp.bfloat16)
                        for kern in ("flash_fwd", "flash_dq", "flash_dkv")
                        ) > fa.VMEM_LIMIT:
                    continue
                row(bh, T, d, bq, bk, window)
    print("the chooser's own edges (block columns None):")
    shapes = [shape for shape, _, _ in sweep]
    for bh, T, d in shapes + ([] if window else [(32, 2048, 128)]):
        print({kern: fa.pick_blocks(kern, T, T, d, jnp.bfloat16, window=window)
               for kern in ("flash_fwd", "flash_dq", "flash_dkv")})
        row(bh, T, d, None, None, window)
        if window:
            row(bh, T, d, None, None, None)
    for T in () if window else (4096, 2048):
        rec = {"b": 2, "T": T, "h": 16, "d": 128}
        try:
            rec.update(_time_dense(2, T, 16, 128))
        except Exception as e:  # noqa: BLE001 - e.g. the logits do not fit
            rec["refused"] = f"{type(e).__name__}: {str(e)[:200]}"
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(rec, flush=True)


if __name__ == "__main__":
    import sys
    cmd = sys.argv[1] if len(sys.argv) > 1 else "check"
    if cmd in ("blocksweep", "picks"):
        print("backend:", jax.default_backend(),
              jax.devices()[0].device_kind)
        if jax.default_backend() != "tpu":
            raise SystemExit("the sweep times the chip: no TPU here")
        blocksweep(grid=cmd == "blocksweep",
                   window=int(sys.argv[2]) if len(sys.argv) > 2 else None)
    else:
        print("backend:", jax.default_backend())
        check()
        print("FLASH HARDWARE CHECK OK")

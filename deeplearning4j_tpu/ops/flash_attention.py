"""Pallas flash attention for TPU — the framework's hot-op custom kernel.

``nn/layers/attention.py``'s dense ``mha`` materializes the [b, h, T, T]
logits tensor: O(T²) HBM traffic and memory, which is exactly what caps
long-context training. This module implements blockwise (flash) attention as
Pallas TPU kernels — online softmax over K/V blocks streamed through VMEM,
O(T) memory, with the standard FlashAttention-2 backward (recompute
probabilities per block from the saved log-sum-exp instead of storing them).

Tiling: every kernel cuts the query side into ``block_q`` rows and the key
side into ``block_k`` rows, chosen per kernel from what the call can see
(:func:`pick_blocks`: lengths, head size, operand dtype; a VMEM reckoning
and divisibility bound them). A Mosaic grid step costs ~0.35 µs whatever it
holds (v5e), so the edges decide how often a call pays it: at bh 32, T 4096,
d 128 a causal call walked 32,768 steps of 128 × 128 for 0.7 ms of products
(11 ms a call) before the edges came from the shape.

Walk: the grid's leading dimension is batch×heads. Not causal, the other
two are (out-block, in-block) and the innermost walks the streamed blocks.
Causal, they collapse into ONE dimension over the list of visible (q block,
k block) pairs (:func:`causal_pairs`), whose tables arrive by scalar
prefetch and drive the index maps: a block above the diagonal is never a
grid step, and only a block that straddles the diagonal builds the causal
mask (blocks wholly below it take the unmasked body). A causal call with a
``window`` (a sliding window of keys) walks the band alone: a block below
the band is no grid step either, and one that straddles its lower edge
masks that edge too. The BlockSpec index
maps stage one block of each operand into VMEM per step (no full-sequence
VMEM residency — T is bounded by HBM, not VMEM), and the running
accumulators (m/l/acc, dq, dk/dv) live in VMEM scratch that persists across
a row's steps: initialized at its first pair, written out at its last.

Layout: kernels work on [bh, T, d] (batch×heads flattened); the public
:func:`flash_attention` takes the layer's [b, T, h, d] and
transposes/reshapes at the boundary (XLA fuses these). f32 accumulation
throughout; inputs/outputs keep the caller's dtype (bf16 on TPU).

What engaged is visible: each kernel's ``name=`` carries its edges
(``flash_fwd_q512_k512``; the device trace's op names), and the registry
gauge ``flash_grid_steps{kernel}`` holds the steps of its grid per call, set
when the call is traced. A differentiated call names the residuals its
backward kernels read (``dl4j_flash_res``; the block stacks' checkpoint keeps
them) and sets ``flash_residual_bytes{kernel}`` to their bytes.

Used automatically by ``SelfAttentionLayer`` when applicable (TPU backend,
T divisible by the 128 block; [b, T] key-padding masks AND attention-
probability dropout both run in-kernel — streamed/regenerated blockwise, no
dense fallback) — the cuDNN-helper pattern (reference
``ConvolutionLayer.java:76`` reflective helper swap) realized as a Pallas
kernel behind the same layer math, with the dense path as the
always-available fallback for short/odd-length sequences.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the MXU lane-aligned minimum edge, and the grid every edge snaps to
MIN_BLOCK = 128
_NEG = -1e30

#: scoped VMEM every flash ``pallas_call`` asks Mosaic for: a quarter of the
#: v5e's 128 MiB (the default, 16 MiB, is under :func:`vmem_bytes`'
#: reckoning of the swept tiles); :func:`pick_blocks` keeps the edges under it
VMEM_LIMIT = 32 * 2 ** 20

#: per kernel: the edges (block_q, block_k) the sweep found best. Swept on
#: the v5e, causal, bf16, no mask, no dropout
#: (``perf_flash_check.py blocksweep``; the table is in PERF.md): at bh 32,
#: T 4096, d 128 over block_q 128..1024 × block_k 128..2048, and at d 64 for
#: T 4096 and 8192 over 256..1024 × 256..2048, 1024 × 1024 was the fastest
#: of every kernel at every shape (ms per call at d 128: forward 1.42
#: against 10.42 at 128 × 128, dq 1.77 against 9.16, dk/dv 2.20 against
#: 11.80). Past it the k edge loses again (1024 × 2048: 1.74 / 2.16 / 2.59):
#: more of the walk is diagonal blocks, whose hidden cells are computed.
_EDGES = {"flash_fwd": (1024, 1024), "flash_dq": (1024, 1024),
          "flash_dkv": (1024, 1024)}
#: per kernel: the largest edge a banded call takes, as a share of its
#: window. Swept on the v5e at bh 32, T 8192, d 128, a window of 1024, bf16
#: (``perf_flash_check.py blocksweep 1024``; the table is in PERF.md): the
#: backward kernels walk half the window best, where a 1024 × 1024 pair is
#: cells of both edges of the band (dq 2.75 ms a call at 512 × 512 against
#: 3.06 at 1024 × 1024, dk/dv 3.65 against 3.70); the forward keeps the
#: causal edges (2.47 against 3.11 at 512 × 512: its accumulator is
#: rescaled every step, and a third of its steps more cost more than the
#: cells they skip)
_WINDOW_SHARE = {"flash_fwd": 1.0, "flash_dq": 0.5, "flash_dkv": 0.5}


def vmem_bytes(kernel: str, block_q: int, block_k: int, d: int, dtype,
               d_v: int | None = None) -> int:
    """VMEM one grid step of ``kernel`` holds at these edges, reckoned from
    its tiles as Mosaic lays them out (last dimension padded to 128 lanes;
    every streamed tile twice, for the pipeline's double buffer): the
    [block, d] operand and output tiles (q, k, dq and dk at the head size
    ``d``; v, o, do and dv at the value head size ``d_v``, ``d`` where None), the [block, 8] f32 statistics, the
    f32 accumulators in scratch, and the [block_q, block_k] intermediates the
    body names — f32 ones (s, p; dp, ds too in the backward) plus the copies
    cast to the operand dtype for the MXU. An upper bound: Mosaic streams the
    elementwise chain and holds fewer of the intermediates whole (the three
    kernels at 1024 × 1024, bf16, d 128 compile for the v5e under 16 MiB
    and are refused at 8 for 8.6 / 8.4 / 9.7 MB, where this reckons 15.5 /
    24 / 27)."""
    item = jnp.dtype(dtype).itemsize
    lanes = -(-d // 128) * 128
    lanes_v = lanes if d_v is None else -(-d_v // 128) * 128
    row, col = block_q * lanes, block_k * lanes       # elements of a tile
    row_v, col_v = block_q * lanes_v, block_k * lanes_v
    stat_q, stat_k = block_q * 128 * 4, block_k * 128 * 4
    if kernel == "flash_fwd":                          # q o k v km lse
        streamed = (row + row_v + col + col_v) * item + stat_k + stat_q
        scratch = 2 * stat_q + row_v * 4                         # m l acc
        n_f32, casts = 2, 1                                      # s p | p
    elif kernel == "flash_dq":                         # q do dq k v
        streamed = ((2 * row + row_v + col + col_v) * item + stat_k
                    + 2 * stat_q)
        scratch = row * 4                                        # dq
        n_f32, casts = 4, 1                                      # s p dp ds | ds
    else:                                              # q do k v dk dv
        streamed = ((row + row_v + 2 * col + 2 * col_v) * item + stat_k
                    + 2 * stat_q)
        scratch = (col + col_v) * 4                              # dk dv
        n_f32, casts = 4, 2                                      # | pd ds
    return (2 * streamed + scratch
            + block_q * block_k * (4 * n_f32 + item * casts))


def _divisors(T: int, cap: int):
    """128-multiples <= cap that divide ``T``, largest first."""
    return [b for b in range(min(cap, T) // MIN_BLOCK * MIN_BLOCK, 0,
                             -MIN_BLOCK) if T % b == 0]


def pick_blocks(kernel: str, Tq: int, Tk: int, d: int, dtype,
                d_v: int | None = None, window: int | None = None):
    """(block_q, block_k) for one call of ``kernel`` (``flash_fwd``,
    ``flash_dq``, ``flash_dkv``) from what the call can see: among the
    128-multiples that divide ``Tq`` and ``Tk`` and are no larger than the
    edges the sweep found best for the kernel (:data:`_EDGES`), the pair of
    the largest tile whose step fits :data:`VMEM_LIMIT` by
    :func:`vmem_bytes` at the head size ``d`` of q and k and the value head
    size ``d_v`` (``d`` where None) (of two tiles of one area, the one with more query
    rows). The two lengths are tiled apart, and a length that no larger edge
    divides falls back to smaller ones down to 128, so every 128-multiple
    takes the flash path. A banded call (``window``) caps both edges at
    :data:`_WINDOW_SHARE` of its window. Dropout coordinates hash GLOBAL
    positions, so the three kernels may pick different edges without
    changing any decision."""
    cap_q, cap_k = _EDGES[kernel]
    if window is not None:
        cap = max(MIN_BLOCK, int(window * _WINDOW_SHARE[kernel]))
        cap_q, cap_k = min(cap_q, cap), min(cap_k, cap)
    fits = [(bq, bk) for bq in _divisors(Tq, cap_q)
            for bk in _divisors(Tk, cap_k)
            if vmem_bytes(kernel, bq, bk, d, dtype, d_v) <= VMEM_LIMIT]
    return max(fits, key=lambda e: (e[0] * e[1], e[0]),
               default=(MIN_BLOCK, MIN_BLOCK))


def _edges(kernel, Tq, Tk, d, dtype, block_q, block_k, d_v=None,
           window=None):
    """The chooser's edges, each overridden where the caller gave one (the
    sweep of ``perf_flash_check.py`` and the tests' multi-block grids)."""
    bq, bk = block_q, block_k
    if not (bq and bk):
        pq, pk = pick_blocks(kernel, Tq, Tk, d, dtype, d_v, window)
        bq, bk = bq or pq, bk or pk
    if Tq % bq or Tk % bk or bq % MIN_BLOCK or bk % MIN_BLOCK:
        raise ValueError(f"{kernel}: edges ({bq}, {bk}) must be multiples of "
                         f"{MIN_BLOCK} that divide ({Tq}, {Tk})")
    return bq, bk


def _window(causal, window):
    """``window`` as a static int, or None; a window bands a causal walk
    only."""
    if window is None:
        return None
    if not causal or int(window) < 1:
        raise ValueError(f"flash attention: a window ({window}) needs a "
                         f"causal call and at least one key")
    return int(window)


def causal_pairs(nq: int, nk: int, block_q: int, block_k: int,
                 k_major: bool = False, window: int | None = None):
    """The causal walk as four int32 tables, one entry per grid step:
    (outer block, inner block, first of its row?, last of its row?), rows in
    order. A (q block, k block) pair is visible where the k block starts no
    later than the q block ends and, with a ``window`` (query i sees keys
    i - window < j <= i), where it ends later than its first query's window
    begins: the banded walk, in which a pair outside the band is never a
    grid step. ``k_major`` False: a row is a q block and its visible k
    blocks (forward, dq); True: a k block and its visible q blocks (dk/dv).
    A k block past the last query (``Tk`` > ``Tq``) sees none; it keeps the
    last q block as its one step, where the diagonal's mask hides every
    cell, so that its zeros are still written."""
    q_start = np.arange(nq)[:, None] * block_q
    vis = np.arange(nk)[None, :] * block_k <= q_start + block_q - 1
    if window is not None:
        vis &= (np.arange(nk)[None, :] + 1) * block_k - 1 > q_start - window
    if k_major:
        vis = vis.T.copy()
        vis[~vis.any(axis=1), -1] = True
    outer, inner = np.nonzero(vis)
    edge = outer[1:] != outer[:-1]
    return tuple(a.astype(np.int32) for a in (
        outer, inner, np.r_[True, edge], np.r_[edge, True]))

# ---------------------------------------------------------------- dropout RNG
# Counter-based hash PRNG for attention-probability dropout INSIDE the
# kernels. The keep decision for softmax cell (bh, qpos, kpos) is a pure
# function of (seed, bh, qpos, kpos), so the forward kernel and BOTH backward
# kernels regenerate bit-identical masks with no [T, T] mask ever touching
# HBM — the standard FlashAttention dropout scheme. A murmur3-finalizer mix
# over global coordinates is used instead of the TPU PRNG primitive
# (pltpu.prng_random_bits) because it is platform-portable: plain int32 VPU
# ops lower on TPU AND under interpret mode, so the CPU test suite exercises
# the exact arithmetic the TPU runs (prng_seed has no CPU lowering).
# numpy scalars (NOT jnp arrays): they embed as literals in the kernel
# body — a jnp constant would be a captured device value, which pallas_call
# rejects
_PHI = np.int32(-1640531527)       # 0x9E3779B9: golden-ratio odd constant
_FMIX1 = np.int32(-2048144789)     # 0x85EBCA6B: murmur3 fmix32
_FMIX2 = np.int32(-1028477387)     # 0xC2B2AE35: murmur3 fmix32
_FNV = np.int32(0x01000193)        # FNV prime: row stride > any kpos


def _fmix32(h):
    """murmur3 32-bit finalizer (full avalanche); int32 wraparound == the
    uint32 arithmetic (two's complement), shifts logical."""
    h = h ^ lax.shift_right_logical(h, 16)
    h = h * _FMIX1
    h = h ^ lax.shift_right_logical(h, 13)
    h = h * _FMIX2
    h = h ^ lax.shift_right_logical(h, 16)
    return h


def _keep_from_coords(seed, bh, qpos, kpos, rate):
    """Keep mask (f32 0/1, broadcast shape of qpos/kpos) for softmax cells at
    global coordinates (bh, qpos, kpos). Single source of truth: the Pallas
    kernels call this with block-local iotas, :func:`dropout_keep_mask` with
    full-range iotas — identical values by construction."""
    h = _fmix32(seed ^ (bh * _PHI))
    x = _fmix32(h ^ (qpos * _FNV + kpos))
    x = _fmix32(x ^ (kpos * _PHI))
    u = (x & np.int32(0x7FFFFF)).astype(jnp.float32) * (1.0 / (1 << 23))
    return (u >= rate).astype(jnp.float32)


def _block_keep(seed_ref, bh, qi, kj, rate, block_q, block_k):
    """[block_q, block_k] keep mask for attention block (bh, qi, kj). The
    SMEM seed operand is [3] i32: (seed, q_offset, k_offset) — the offsets
    make the hashed coordinates GLOBAL, so a kernel running on a ring shard
    draws bit-identical decisions to a single kernel over the full
    sequence (``parallel.sequence.ring_flash_attention`` passes each ring
    step's shard offsets; single-device callers pass 0, 0). Hashing global
    positions also makes the decisions independent of the edges the
    calling kernel happened to pick."""
    shape = (block_q, block_k)
    qpos = (seed_ref[1] + qi * block_q
            + lax.broadcasted_iota(jnp.int32, shape, 0))
    kpos = (seed_ref[2] + kj * block_k
            + lax.broadcasted_iota(jnp.int32, shape, 1))
    return _keep_from_coords(seed_ref[0], bh, qpos, kpos, rate)


def seed3(seed, q_off=0, k_off=0):
    """Pack the kernels' [3] i32 SMEM dropout operand:
    (seed, global q offset, global k offset)."""
    return jnp.stack([jnp.asarray(seed, jnp.int32).reshape(()),
                      jnp.asarray(q_off, jnp.int32).reshape(()),
                      jnp.asarray(k_off, jnp.int32).reshape(())])


def dropout_keep_mask(bh, Tq, Tk, seed, rate, q_off=0, k_off=0):
    """Materialize the exact [bh, Tq, Tk] keep mask the kernels regenerate
    blockwise — test/debug oracle only (O(T²) memory, which the kernels
    never allocate). ``q_off``/``k_off`` shift the hashed coordinates the
    way the ring passes shard offsets."""
    qpos = q_off + jnp.arange(Tq, dtype=jnp.int32)[:, None]
    kpos = k_off + jnp.arange(Tk, dtype=jnp.int32)[None, :]
    seed = jnp.asarray(seed, jnp.int32).reshape(())
    return jax.vmap(lambda i: _keep_from_coords(
        seed, i, qpos, kpos, rate))(jnp.arange(bh, dtype=jnp.int32))


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _vspec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _causal_mask(s, qi, kj, block_q, block_k, window=None):
    qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = kj * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    visible = kpos <= qpos
    if window is not None:
        visible &= kpos > qpos - window
    return jnp.where(visible, s, _NEG)


def _scores(q, k, km_ref, scale, masked, qi, kj, window=None):
    """f32 [block_q, block_k] logits of one block pair. The matmul runs in
    the SOURCE dtype (bf16 → native MXU pass) with f32 accumulation; the
    scale moves after the dot so bf16 q is not pre-rounded by it. ``masked``
    (static) applies the causal mask, and the window's lower edge where
    there is one: asked for only on blocks that straddle an edge."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if masked:
        s = _causal_mask(s, qi, kj, q.shape[0], k.shape[0], window)
    if km_ref is not None:
        s = jnp.where(km_ref[0, :, 0][None, :] > 0, s, _NEG)
    return s


def _on_pair(causal, qi, kj, block_q, block_k, body, window=None):
    """Run ``body(masked)`` on block pair (qi, kj). Every pair the grid
    visits is visible; causal, the one that straddles the diagonal (its last
    key lies past its first query) or, with a ``window``, the band's lower
    edge (its first key lies at or before its last query's window begins)
    takes the masked body, one wholly inside the band the unmasked: no
    iota, compare and select for a mask that hides nothing."""
    if not causal:
        body(False)
        return
    straddles = (kj + 1) * block_k - 1 > qi * block_q
    if window is not None:
        straddles |= kj * block_k <= (qi + 1) * block_q - 1 - window
    pl.when(straddles)(lambda: body(True))
    pl.when(jnp.logical_not(straddles))(lambda: body(False))


def _step(tabs, n_in):
    """(bh, outer block, inner block, first of the row?, last?) of this grid
    step: read from the prefetched pair tables of a causal walk, from the
    grid's own coordinates otherwise."""
    if tabs:
        outer, inner, first, last = tabs
        t = pl.program_id(1)
        return (pl.program_id(0), outer[t], inner[t], first[t] == 1,
                last[t] == 1)
    i = pl.program_id(2)
    return pl.program_id(0), pl.program_id(1), i, i == 0, i == n_in - 1


def _split_refs(refs, causal, has_km, has_seed):
    """(pair tables, (q_ref, k_ref, v_ref), km_ref, seed_ref, the rest) of a
    kernel's flat argument list."""
    n_tabs = 4 if causal else 0
    tabs, refs = refs[:n_tabs], refs[n_tabs:]
    lead, refs = refs[:3], refs[3:]
    km_ref = refs[0] if has_km else None
    seed_ref = refs[int(has_km)] if has_seed else None
    return tabs, lead, km_ref, seed_ref, refs[int(has_km) + int(has_seed):]


def _name(kernel, bq, bk, window=None):
    """A kernel's name at its edges, and its window where it has one
    (``flash_fwd_q1024_k1024_w1024``): the device trace's op name and the
    ``kernel`` label of the registry's gauges."""
    return f"{kernel}_q{bq}_k{bk}" + ("" if window is None else f"_w{window}")


def _launch(kernel, body, bq, bk, bh, n_out, n_in, pairs, specs, operands,
            out_specs, out_shape, scratch, window=None):
    """One flash ``pallas_call``; returns the list of its outputs. ``specs``
    / ``out_specs`` list (block shape, side) pairs with side "outer" or
    "inner" (or a finished BlockSpec): the index maps follow the
    walk — the prefetched ``pairs`` tables of a causal call on a (bh, pair)
    grid, the grid's own (bh, out-block, in-block) otherwise. The name
    carries the edges and the registry's ``flash_grid_steps`` the steps."""
    if pairs:
        grid = (bh, len(pairs[0]))
        maps = {"outer": lambda i, t, o, n, *_: (i, o[t], 0),
                "inner": lambda i, t, o, n, *_: (i, n[t], 0)}
    else:
        grid = (bh, n_out, n_in)
        maps = {"outer": lambda i, o, n: (i, o, 0),
                "inner": lambda i, o, n: (i, n, 0)}
    spec = lambda s: (s if isinstance(s, pl.BlockSpec)
                      else _vspec(s[0], maps[s[1]]))
    name = _name(kernel, bq, bk, window)
    from ..monitor import get_registry     # here: the package imports ops
    get_registry().gauge(
        "flash_grid_steps",
        "Grid steps of one call of a flash-attention kernel at the edges in "
        "its name, set when the call is traced", kernel=name
    ).set(math.prod(grid))
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pairs), grid=grid,
            in_specs=[spec(s) for s in specs],
            out_specs=[spec(s) for s in out_specs],
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(),
        name=name,
    )(*(jnp.asarray(t) for t in pairs), *operands)


def _operands(q_side, k_side, bq, bk, q, k, v, km, seed, rate, bwd=()):
    """(specs, operands) of a kernel's inputs in the order its body unpacks
    them: q, k, v, the key mask and the dropout seed where there are any,
    then ``bwd`` = (do, delta, lse) for the backward kernels. q-side tiles
    follow ``q_side`` of the walk ("outer" / "inner"), k-side ``k_side``."""
    d, d_v = q.shape[-1], v.shape[-1]
    row, col = ((1, bq, d), q_side), ((1, bk, d), k_side)
    specs, operands = [row, col, ((1, bk, d_v), k_side)], [q, k, v]
    if km is not None:
        specs.append(((1, bk, 8), k_side))
        operands.append(km)
    if rate > 0.0:
        specs.append(_smem_spec())
        operands.append(seed)
    if bwd:
        stat = ((1, bq, 8), q_side)
        specs += [((1, bq, d_v), q_side), stat, stat]
        operands += list(bwd)
    return specs, operands


# ------------------------------------------------------------------ forward
def _fwd_kernel(*refs, causal, scale, nk, rate, has_km, window=None):
    tabs, (q_ref, k_ref, v_ref), km_ref, seed_ref, rest = _split_refs(
        refs, causal, has_km, rate > 0.0)
    o_ref, lse_ref, m_s, l_s, acc_s = rest
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    bh, qi, kj, first, last = _step(tabs, nk)

    @pl.when(first)
    def _():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _compute(masked):
        v = v_ref[0]
        s = _scores(q_ref[0], k_ref[0], km_ref, scale, masked, qi, kj, window)
        m = m_s[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))       # [Bq]
        p = jnp.exp(s - m_new[:, None])
        if masked and window is not None:
            # a row whose first visited block lies wholly outside its window
            # sees only _NEG there, and exp(_NEG - _NEG) = 1 would count
            # every hidden cell until a visible key's correction wipes them;
            # hidden cells count nought instead, whatever the order
            p = jnp.where(s > _NEG * 0.5, p, 0.0)
        alpha = jnp.exp(m - m_new)
        # softmax denominator accumulates UNDROPPED p — dropout applies to
        # the normalized probabilities (out = drop(softmax(s)) @ v), and
        # division by l at the end distributes over the linear accumulator
        l_s[:, 0] = l_s[:, 0] * alpha + jnp.sum(p, axis=-1)
        m_s[:, 0] = m_new
        if rate > 0.0:
            keep = _block_keep(seed_ref, bh, qi, kj, rate, bq, bk)
            p = p * keep * (1.0 / (1.0 - rate))
        acc_s[:] = acc_s[:] * alpha[:, None] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_pair(causal, qi, kj, bq, bk, _compute, window)

    @pl.when(last)
    def _():
        # Rows whose visible keys were ALL masked never raise m above _NEG;
        # for them every p above was exp(_NEG - _NEG) = 1, so acc/l is a
        # uniform average over the masked block — garbage. Define the
        # semantics instead: no visible key -> output 0, lse = _NEG (the
        # ring merge's no-contribution identity), and the backward's
        # s-guard (see _dq_kernel) makes the row's gradients exactly 0.
        m = m_s[:, 0]
        l = jnp.maximum(l_s[:, 0], 1e-30)
        valid = (m > _NEG * 0.5).astype(jnp.float32)
        o_ref[0] = (acc_s[:] * (valid / l)[:, None]).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            jnp.where(valid > 0, m + jnp.log(l), _NEG)[:, None],
            lse_ref.shape[1:])


def _fwd(q, k, v, km, seed, causal, scale, rate, block_q=None, block_k=None,
         window=None):
    """q: [bh, Tq, d], k: [bh, Tk, d], v: [bh, Tk, d_v] (a value head size
    of its own), km: [bh, Tk, 8] key mask or None, seed: [3] i32 (seed,
    q_off, k_off — :func:`seed3`) or None (rate > 0) →
    (o [bh, Tq, d_v], lse [bh, Tq, 8]). ``block_q`` / ``block_k`` override
    :func:`pick_blocks`; ``window`` (causal only) bands the walk."""
    bh, Tq, d = q.shape
    Tk, d_v = k.shape[1], v.shape[2]
    window = _window(causal, window)
    bq, bk = _edges("flash_fwd", Tq, Tk, d, q.dtype, block_q, block_k, d_v,
                    window)
    nq, nk = Tq // bq, Tk // bk
    kern = functools.partial(_fwd_kernel, causal=causal, scale=scale, nk=nk,
                             rate=rate, has_km=km is not None,
                             window=window)
    # lse is lane-padded to [bh, T, 8]: TPU block shapes need their last two
    # dims (8·k, 128·m) or full-dim; a (1, blk) slice of [bh, T] is
    # unlowerable. 8 f32 lanes per position is noise next to q/k/v
    specs, operands = _operands("outer", "inner", bq, bk, q, k, v, km, seed,
                                rate)
    return _launch(
        "flash_fwd", kern, bq, bk, bh, nq, nk,
        causal_pairs(nq, nk, bq, bk, window=window) if causal else (),
        specs, operands,
        out_specs=[((1, bq, d_v), "outer"), ((1, bq, 8), "outer")],
        out_shape=[jax.ShapeDtypeStruct((bh, Tq, d_v), q.dtype),
                   jax.ShapeDtypeStruct((bh, Tq, 8), jnp.float32)],
        scratch=[_scratch((bq, 8)), _scratch((bq, 8)), _scratch((bq, d_v))],
        window=window)


# ----------------------------------------------------------------- backward
def _dq_kernel(*refs, causal, scale, nk, rate, has_km, window=None):
    tabs, (q_ref, k_ref, v_ref), km_ref, seed_ref, rest = _split_refs(
        refs, causal, has_km, rate > 0.0)
    do_ref, delta_ref, lse_ref, dq_ref, dq_s = rest
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    bh, qi, kj, first, last = _step(tabs, nk)

    @pl.when(first)
    def _():
        dq_s[:] = jnp.zeros_like(dq_s)

    def _compute(masked):
        # source-dtype matmul operands (bf16 MXU pass), f32 accumulation —
        # same policy as the forward kernel; softmax/ds math stays f32
        do = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        k = k_ref[0]
        s = _scores(q_ref[0], k, km_ref, scale, masked, qi, kj, window)
        # s-guard: masked cells get p = 0 even on fully-masked rows, where
        # lse is the _NEG sentinel and exp(s - lse) would be exp(0) = 1
        p = jnp.where(s > _NEG * 0.5, jnp.exp(s - lse[:, None]), 0.0)
        dp = lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        if rate > 0.0:
            # dP flows only through kept cells: dP = (do·vᵀ)·keep/(1-r);
            # delta already equals rowsum(P∘dP) = rowsum(do∘o) unchanged
            keep = _block_keep(seed_ref, bh, qi, kj, rate, bq, bk)
            dp = dp * keep * (1.0 / (1.0 - rate))
        ds = p * (dp - delta[:, None]) * scale
        dq_s[:] = dq_s[:] + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_pair(causal, qi, kj, bq, bk, _compute, window)

    @pl.when(last)
    def _():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, causal, scale, nq, rate, has_km, window=None):
    tabs, (q_ref, k_ref, v_ref), km_ref, seed_ref, rest = _split_refs(
        refs, causal, has_km, rate > 0.0)
    do_ref, delta_ref, lse_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    # the walk here is k-major: a row is a k block and its q blocks
    bh, ki, qj, first, last = _step(tabs, nq)

    @pl.when(first)
    def _():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def _compute(masked):
        # source-dtype matmul operands (bf16 MXU pass), f32 accumulation
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = _scores(q, k_ref[0], km_ref, scale, masked, qj, ki, window)
        # same s-guard as _dq_kernel (fully-masked rows: lse = _NEG)
        p = jnp.where(s > _NEG * 0.5,
                      jnp.exp(s - lse[:, None]), 0.0)    # [Bq, Bk]
        if rate > 0.0:
            # the forward's decisions: same global (bh, q, k) coordinates
            keep = _block_keep(seed_ref, bh, qj, ki, rate, bq, bk)
            pd = p * keep * (1.0 / (1.0 - rate))          # = drop(P)
        else:
            pd = p
        dv_s[:] = dv_s[:] + lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        if rate > 0.0:
            dp = dp * keep * (1.0 / (1.0 - rate))
        ds = p * (dp - delta[:, None]) * scale
        dk_s[:] = dk_s[:] + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_pair(causal, qj, ki, bq, bk, _compute, window)

    @pl.when(last)
    def _():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def dq_block(q, k, v, km, do, delta, lse, causal, scale, seed=None,
             rate=0.0, block_q=None, block_k=None, window=None):
    """dq for one q-shard against one k/v block ([bh, Tq, d] × [bh, Tk, d];
    ``v`` and ``do`` at the value head size).
    ``delta``/``lse`` are the GLOBAL rowwise Δ and log-sum-exp ([bh, Tq, 8]
    lane-padded) — with them, per-block probabilities recompute exactly, so
    per-block gradients sum to the full-attention gradient. Used by the
    in-kernel backward below AND per ring step by
    ``parallel.sequence.ring_flash_attention``. ``block_q`` / ``block_k``
    override :func:`pick_blocks`; the two lengths are tiled apart.
    ``window`` as in :func:`_fwd`."""
    bh, Tq, d = q.shape
    Tk = k.shape[1]
    window = _window(causal, window)
    bq, bk = _edges("flash_dq", Tq, Tk, d, q.dtype, block_q, block_k,
                    v.shape[2], window)
    nq, nk = Tq // bq, Tk // bk
    kern = functools.partial(_dq_kernel, causal=causal, scale=scale, nk=nk,
                             rate=rate, has_km=km is not None,
                             window=window)
    specs, operands = _operands("outer", "inner", bq, bk, q, k, v, km, seed,
                                rate, bwd=(do, delta, lse))
    dq, = _launch(
        "flash_dq", kern, bq, bk, bh, nq, nk,
        causal_pairs(nq, nk, bq, bk, window=window) if causal else (),
        specs, operands,
        out_specs=[((1, bq, d), "outer")],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch=[_scratch((bq, d))], window=window)
    return dq


def dkv_block(q, k, v, km, do, delta, lse, causal, scale, seed=None,
              rate=0.0, block_q=None, block_k=None, window=None):
    """(dk, dv) for one k/v block against one q-shard; see :func:`dq_block`
    for the global-``lse``/``delta`` contract, the overrides and
    ``window``."""
    bh, Tk, d = k.shape
    Tq, d_v = q.shape[1], v.shape[2]
    window = _window(causal, window)
    bq, bk = _edges("flash_dkv", Tq, Tk, d, q.dtype, block_q, block_k, d_v,
                    window)
    nq, nk = Tq // bq, Tk // bk
    kern = functools.partial(_dkv_kernel, causal=causal, scale=scale, nq=nq,
                             rate=rate, has_km=km is not None,
                             window=window)
    specs, operands = _operands("inner", "outer", bq, bk, q, k, v, km, seed,
                                rate, bwd=(do, delta, lse))
    return _launch(
        "flash_dkv", kern, bq, bk, bh, nk, nq,
        causal_pairs(nq, nk, bq, bk, k_major=True, window=window)
        if causal else (),
        specs, operands,
        out_specs=[((1, bk, d), "outer"), ((1, bk, d_v), "outer")],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch=[_scratch((bk, d)), _scratch((bk, d_v))], window=window)


def rowwise_delta(do, o):
    """Δ_i = Σ_d do·o — rowwise, cheap in plain XLA; lane-padded like lse."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(delta[..., None], delta.shape + (8,))


def _bwd(causal, scale, rate, window, res, g):
    q, k, v, km, seed, o, lse = res
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (8,))
    do = g.astype(q.dtype)
    delta = rowwise_delta(do, o)
    dq = dq_block(q, k, v, km, do, delta, lse, causal, scale, seed, rate,
                  window=window)
    dk, dv = dkv_block(q, k, v, km, do, delta, lse, causal, scale, seed,
                       rate, window=window)
    dkm = None if km is None else jnp.zeros_like(km)
    # int32 primal → float0 cotangent (the JAX convention for non-float args)
    dseed = (None if seed is None
             else np.zeros(seed.shape, jax.dtypes.float0))
    return dq, dk, dv, dkm, dseed


# ------------------------------------------------------------- public entry
def normalize_operand_dtypes(q, k, v):
    """Uniform source-dtype operands for the dtype-strict kernels
    (``dot_general`` rejects mixed dtypes; uniform bf16 is what takes the
    native MXU pass): promote to the WIDEST operand dtype, so an f32 k/v
    alongside a bf16 q keeps its precision instead of being silently
    downcast. ``DL4J_TPU_FLASH_F32=1`` forces f32 — the first-hardware
    rollback hatch restoring the pre-bf16 kernel behavior should a Mosaic
    bf16 lowering gap surface on a new jaxlib. Returns
    ``(q, k, v, out_dtype)`` with ``out_dtype`` = q's ORIGINAL dtype;
    callers cast the kernel output back to it so neither the promotion nor
    the hatch ever changes downstream activation dtypes. Shared by
    :func:`flash_attention` and ``parallel.sequence.ring_flash_attention``
    — one policy, one place."""
    import os
    out_dtype = q.dtype
    common = jnp.promote_types(jnp.promote_types(q.dtype, k.dtype), v.dtype)
    if os.environ.get("DL4J_TPU_FLASH_F32"):
        common = jnp.float32
    return (q.astype(common), k.astype(common), v.astype(common), out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, km, seed, causal, scale, rate, window):
    o, _ = _fwd(q, k, v, km, seed, causal, scale, rate, window=window)
    return o


def _flash_fwd(q, k, v, km, seed, causal, scale, rate, window):
    # What the backward kernels read carries one name: a ``jax.checkpoint``
    # whose policy saves it (the block stacks', ``base.block_checkpoint``)
    # keeps the tuple, and its backward holds neither this call again nor
    # what made q, k and v. Anywhere else the name is the identity. ``km``
    # and ``seed`` are the caller's inputs. Of lse's 8 equal lanes one is
    # kept: the TPU pads a last dimension of 8 to 128, and a kept [bh, T, 8]
    # would hold as many bytes as q.
    from ..monitor import get_registry     # here: the package imports ops
    from ..nn.layers.base import FLASH_RES
    o, lse = _fwd(q, k, v, km, seed, causal, scale, rate, window=window)
    lse = lse[..., 0]
    q, k, v, o, lse = (checkpoint_name(x, FLASH_RES)
                       for x in (q, k, v, o, lse))
    get_registry().gauge(
        "flash_residual_bytes",
        "Bytes of the residuals (q, k, v, o, lse) one differentiated "
        "flash-attention call hands its backward kernels, set when the call "
        "is traced", kernel=_name("flash_fwd", *pick_blocks(
            "flash_fwd", q.shape[1], k.shape[1], q.shape[2], q.dtype,
            v.shape[2], window), window)
    ).set(sum(x.size * x.dtype.itemsize for x in (q, k, v, o, lse)))
    return o, (q, k, v, km, seed, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


_FORCE_INTERPRET = False  # tests flip this to run kernels off-TPU


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """``interpret=`` for every ``pallas_call`` in ops/. Interpret mode is
    something only ``_FORCE_INTERPRET`` asks for (the kernel tests set it);
    off-chip without it a kernel call is an error, never a silent
    emulation that reads as "ran"."""
    if _FORCE_INTERPRET:
        return True
    if not _on_tpu():
        raise RuntimeError(
            f"Pallas TPU kernel called on backend "
            f"{jax.default_backend()!r}: the kernels compile for TPU only "
            f"(tests opt into interpret mode with "
            f"ops.flash_attention._FORCE_INTERPRET = True)")
    return False


#: below this sequence length ``mha`` takes the dense einsum. One reading on
#: an earlier backend at d 64 and 128 × 128 tiles set it (T 2048: dense 12.4
#: ms, flash 14.1; T 8192: dense 490, flash 65) and no cell runs a shorter
#: sequence that could guard a move. For whoever brings one, on the v5e at
#: b2·h16·d128 bf16 causal with the edges of :func:`pick_blocks`, forward /
#: forward and backward (PERF.md section 7, PR 29): T 4096 dense 16.1 /
#: 33.0 ms, the kernels 1.43 / 5.43; T 2048 dense 4.24 / 8.59, the kernels
#: 0.49 / 1.76: the crossing lies below T 2048.
MIN_SEQ = 4096


def supported(T: int, d: int, dropout_rate: float, key_mask,
              d_v: int | None = None) -> bool:
    """Whether the flash path applies (``d`` the head size of q and k,
    ``d_v`` the values', ``d`` where None): TPU backend (the interpreter would be
    far slower than the dense einsum — except under the tests' forced
    interpret mode), block-divisible sequence long enough to beat the dense
    path, head dim within VMEM tiling. Both [b, T] key-padding masks
    (round-3 VERDICT item 5) AND attention-probability dropout (round-3
    "ideally dropout"; in-kernel counter-hash PRNG) stream through the
    kernels — neither falls back to dense anymore."""
    min_seq = 2 * MIN_BLOCK if _FORCE_INTERPRET else MIN_SEQ
    if not (_FORCE_INTERPRET or _on_tpu()):
        return False
    if key_mask is not None and getattr(key_mask, "ndim", None) != 2:
        return False
    return (T % MIN_BLOCK == 0 and T >= min_seq and max(d, d_v or d) <= 256
            and 0.0 <= dropout_rate < 1.0)


def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    key_mask=None, dropout_rate: float = 0.0,
                    dropout_seed=None, window: int | None = None):
    """Blockwise attention. q, k: [b, T, h, d], v: [b, T, h, d_v] (the
    value heads may have a size of their own) → [b, T, h, d_v].
    ``window`` (causal only): query i sees keys i - window < j <= i, and
    the kernels walk only the pairs of blocks that meet that band; a window
    of T or more is the causal call itself.
    ``key_mask``: optional [b, T] (1 = real key, 0 = padding) — masked keys
    are excluded from the softmax inside the kernels (no dense fallback).
    ``dropout_rate`` > 0 applies dropout to the normalized attention
    probabilities in-kernel, regenerated mask-free in the backward;
    ``dropout_seed`` (int32 scalar, may be traced — e.g. derived from the
    layer's PRNG key per step) is then required."""
    b, T, h, d = q.shape
    q, k, v, out_dtype = normalize_operand_dtypes(q, k, v)
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    rate = float(dropout_rate)
    seed = None
    if rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 needs dropout_seed")
        seed = seed3(dropout_seed)

    def to_bh(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, T, x.shape[-1])

    km = None
    if key_mask is not None:
        km = jnp.broadcast_to(jnp.asarray(key_mask, jnp.float32)[:, None, :],
                              (b, h, T)).reshape(b * h, T)
        km = jnp.broadcast_to(km[..., None], (b * h, T, 8))
    if window is not None and window >= T:
        window = None
    o = _flash(to_bh(q), to_bh(k), to_bh(v), km, seed, bool(causal),
               float(scale), rate, _window(causal, window))
    return jnp.transpose(o.reshape(b, h, T, v.shape[-1]),
                         (0, 2, 1, 3)).astype(out_dtype)

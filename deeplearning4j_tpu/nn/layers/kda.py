"""Kimi Delta Attention mixer: a gated delta rule with one decay a key
channel (Kimi Linear, Moonshot AI 2025).

Net-new vs the 0.9.x reference, a sibling of :mod:`.mamba`. Per head the
state ``S`` [K (key), V (value)] follows

    S_t = Diag(exp(g_t)) S_{t-1};   S_t += beta_t k_t (v_t - S_t^T k_t)^T
    o_t = S_t^T q_t

and is computed in chunks of L steps, in plain XLA (no Pallas kernel). With
``G`` the running sum of ``g`` inside a chunk and ``u_t`` the correction
``beta_t (v_t - S~_t^T k_t)`` that step t writes (``S~_t`` the decayed state
it meets), the steps of a chunk obey

    (I + Diag(beta) tril(A, -1)) U = Diag(beta) (V - (K * e^G) S_0)
    A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])

so one unit-lower-triangular inverse a chunk and head gives ``T`` with
``U = T beta V - T beta (K e^G) S_0``, the read-out is
``o = (q e^G) S_0 + tril(B) U`` with ``B`` as ``A`` with q for k_t, and the
state leaves as ``Diag(e^{G_L}) S_0 + (K e^{G_L - G})^T U``. One state a
chunk crosses the boundary, carried by a ``lax.scan`` over the chunks.
``A``, ``B`` and ``T`` are what is costly to form and small to hold of a
chunk: they carry a name (``base.CHUNK_MATS``) under which the checkpoints
they sit under keep them, so that no backward forms them a second time.

``exp(-G_s)`` overflows float32 inside a chunk once the decays are strong
(at a log-decay of -4 a step, e^{256} after 64), so no product is formed from
``k e^{G}`` and ``k e^{-G}``. ``A`` and ``B`` are built on a second level of
``SUB``-step blocks: a block against an earlier one as a product of
``k_t e^{G_t - G_ref}`` and ``k_s e^{G_ref - G_s}`` with ``G_ref`` the sum
at the later block's start (both exponents at most nought), and a block
against itself from the differences directly, which are at most nought
wherever ``s <= t``: its first half of rows against its first half of
columns and its second half against all of them (:func:`within_block`),
so that the quarter above the diagonal that only a mask would read is not
formed; backward one pass over ``exp(-|G_t - G_u|)`` gives both operands'
cotangents.

Precision under a bfloat16 compute policy: the products' operands take the
compute dtype; the log-decays, their sums and exponentials, the key and
query norms, the [SUB, SUB, K] diagonal blocks, the triangular inverse, the
state carried between chunks and the output norm are float32.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...monitor import get_registry
from ..weights import _uniform, host_full
from .base import (LayerImpl, implements, acc_dtype, NORM_IN, SCAN_CARRY,
                   CHUNK_MATS)
from .mamba import split_conv_silu
from .normalization import rms_norm

#: steps of a chunk's second level: a block of so many steps against itself
#: is formed from the differences of the decays' sums, channel by channel
SUB = 16
#: chunks whose in-chunk matrices are alive at once (``mamba.SEGMENT_CHUNKS``'
#: reason): a longer sequence is walked segment by segment under a
#: checkpoint that keeps each segment's inputs, the state it was handed and
#: its in-chunk matrices (``_SEGMENT_POLICY``)
SEGMENT_CHUNKS = 16
#: what a segment's own checkpoint keeps beside its inputs: the values named
#: ``CHUNK_MATS``. A name inside nested checkpoints is kept only where every
#: policy on the way lists it: the block stacks' one does too
_SEGMENT_POLICY = jax.checkpoint_policies.save_only_these_names(CHUNK_MATS)


def _steps_major(x):
    """``x`` [..., m, K] -> [m, K, n]: a block's steps lead, its channels
    follow, and every block and head of ``x`` (``n`` of them) lies along the
    last axis, so that a pair of steps is a slice of the leading axes and a
    sum over the channels adds whole rows."""
    m, K = x.shape[-2:]
    return jnp.moveaxis(x.reshape(-1, m, K), 0, -1)


def _blocks_major(x, lead):
    """[a, b, n] -> ``lead`` + [a, b]: :func:`_steps_major` undone."""
    return jnp.moveaxis(x, -1, 0).reshape(lead + x.shape[:-1])


def _bands(m):
    """(first, past the last) row of each band of a block of ``m`` steps
    (a power of two): its two halves."""
    half = max(m // 2, 1)
    return [(lo, lo + half) for lo in range(0, m, half)]


def block_pairs(m):
    """Pairs of steps (t, s) whose weights :func:`within_block`'s forward
    forms for a block of ``m`` steps, a head and a channel: each band of
    rows against the columns up to its last row, three quarters of the
    block (192 of 256 at m = 16; the lower triangle holds 136)."""
    return sum((hi - lo) * hi for lo, hi in _bands(m))


def _within_block(left, right, G):
    lead, m = G.shape[:-2], G.shape[-2]
    l, r, g = _steps_major(left), _steps_major(right), _steps_major(G)
    rows = []
    for lo, hi in _bands(m):
        t, s = np.arange(lo, hi), np.arange(hi)
        seen = (t[:, None] >= s[None, :])[:, :, None, None]
        weights = jnp.exp(jnp.where(seen, g[lo:hi][:, None] - g[:hi][None],
                                    -jnp.inf))       # exponents <= 0
        band = jnp.sum(l[lo:hi][:, None] * r[:hi][None] * weights, axis=-2)
        rows.append(jnp.pad(band, ((0, 0), (0, m - hi), (0, 0))))
    return _blocks_major(jnp.concatenate(rows), lead)


@jax.custom_vjp
def within_block(left, right, G):
    """``out[t, s] = sum_c left[t, c] right[s, c] exp(G[t, c] - G[s, c])``
    for ``s <= t`` and nought above the diagonal: ``left``, ``right``, ``G``
    [..., m, K] -> [..., m, m]. Every block and head lies along the last
    axis and the channels are summed across rows (:func:`_steps_major`).
    Forward, the weights are formed band by band (:func:`_bands`): the
    first half of the rows against the first half of the columns, the
    second half against all of them, so the quarter of the block above the
    diagonal that no band reaches is never formed (:func:`block_pairs`).
    Differentiated by a rule of its own that keeps nothing but the three
    operands and makes one pass a call over ``exp(-|G_t - G_u|)``, which
    below the diagonal is the weight of the pair (t, u) and above it the
    weight of (u, t): one sum over ``u`` gives ``left``'s cotangent from
    the one half and ``right``'s from the other, each pair's weight formed
    once for each cotangent that reads it, where the masked form formed all
    m x m weights twice and discarded half of each. (One call for q and k
    against k, the weights formed once for both, wrote them out as an
    array and lost 50 ms a step on the v5e: PERF.md section 6.)"""
    return _within_block(left, right, G)


def _within_block_fwd(left, right, G):
    return _within_block(left, right, G), (left, right, G)


def _within_block_bwd(kept, d_out):
    left, right, G = kept
    lead, m = G.shape[:-2], G.shape[-2]
    l, r, g = _steps_major(left), _steps_major(right), _steps_major(G)
    mirrored = jnp.exp(-jnp.abs(g[:, None] - g[None]))          # [t, u, K, n]
    d = jnp.moveaxis(d_out.reshape(-1, m, m), 0, -1)              # [t, u, n]
    low = np.tril(np.ones((m, m), bool))[:, :, None]
    by_row = jnp.where(low, d, 0)[:, :, None]                     # d[t, u], u <= t
    by_col = jnp.where(np.swapaxes(low, 0, 1), jnp.swapaxes(d, 0, 1),
                       0)[:, :, None]                             # d[u, t], u >= t
    # both sums in one reduction, so that the weights are formed once
    zero = np.zeros((), mirrored.dtype)
    d_left, d_right = jax.lax.reduce(
        (by_row * mirrored * r[None], by_col * mirrored * l[None]),
        (zero, zero), lambda a, b: (a[0] + b[0], a[1] + b[1]), (1,))
    d_left, d_right = _blocks_major(d_left, lead), _blocks_major(d_right, lead)
    return d_left, d_right, left * d_left - right * d_right


within_block.defvjp(_within_block_fwd, _within_block_bwd)


def _unit_lower_inverse(N):
    """``(I + N)^-1`` of ``N`` [..., L, L], strictly lower triangular, by
    substitution, which no growth of ``N``'s powers can harm: the diagonal
    blocks of ``SUB`` steps row by row, then block row by block row
    (``T_ij = -T_ii sum_k N_ik T_kj``). Multiply-adds on the vector unit in
    ``N``'s dtype; no product is rounded."""
    L = N.shape[-1]
    m = math.gcd(L, SUB)
    n = L // m
    Nb = N.reshape(N.shape[:-2] + (n, m, n, m))
    own = jnp.stack([Nb[..., i, :, i, :] for i in range(n)], axis=-3)
    unit = jnp.eye(m, dtype=N.dtype)
    rows = []                                   # of every diagonal block
    for i in range(m):
        row = jnp.broadcast_to(unit[i], own.shape[:-2] + (m,))
        if i:                                   # e_i - sum_j N[i, j] row_j
            row = row - jnp.sum(own[..., i, :i][..., None]
                                * jnp.stack(rows, axis=-2), axis=-2)
        rows.append(row)
    X = jnp.stack(rows, axis=-2)                # [..., n, m, m]
    product = lambda a, b: jnp.sum(a[..., :, :, None] * b[..., None, :, :],
                                   axis=-2)
    done = X[..., 0, :, :]                      # the leading [i m, i m] of T
    for i in range(1, n):
        left = -product(X[..., i, :, :], product(
            Nb[..., i, :, :i, :].reshape(N.shape[:-2] + (m, i * m)), done))
        done = jnp.concatenate([
            jnp.concatenate([done, jnp.zeros(done.shape[:-1] + (m,),
                                             N.dtype)], axis=-1),
            jnp.concatenate([left, X[..., i, :, :]], axis=-1)], axis=-2)
    return done


def _kept(t):
    """``t`` [..., L, L] under the name ``CHUNK_MATS``, which it carries as
    rows of L L: where a checkpoint keeps it, a float32 [64, 64] tile would
    stand padded to 128 lanes."""
    return checkpoint_name(t.reshape(t.shape[:-2] + (-1,)),
                           CHUNK_MATS).reshape(t.shape)


@jax.custom_vjp
def unit_lower_inverse(N):
    """:func:`_unit_lower_inverse`; its cotangent is ``-T^T dT T^T`` with
    the inverse ``T`` it kept, which carries the name ``CHUNK_MATS``: the
    name stands on the value the rule hands its backward (one given to the
    call's result names another value, and the substitution is run again
    for the one the rule reads)."""
    return _unit_lower_inverse(N)


def _unit_lower_inverse_fwd(N):
    T = _kept(_unit_lower_inverse(N))
    return T, T


def _unit_lower_inverse_bwd(T, dT):
    exact = jax.lax.Precision.HIGHEST
    return (-jnp.einsum("...ji,...jk,...lk->...il", T, dT, T,
                        precision=exact),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _decayed_products(q, k, G, g, compute_dtype):
    """``A`` (strictly below the diagonal) and ``B`` (the diagonal too) of
    the module's text for every chunk and head: ``q``, ``k``, ``G``, ``g``
    [b, c, L, H, K] (``G`` the running sum of ``g`` over L) ->
    two [b, c, H, L, L] in the accumulator dtype."""
    sd = acc_dtype(compute_dtype)
    b, c, L, H, K = k.shape
    m = math.gcd(L, SUB)
    n = L // m
    blocks = lambda t: t.reshape(b, c, n, m, H, K)
    qb, kb, Gb = blocks(q.astype(sd)), blocks(k.astype(sd)), blocks(G)
    ref = Gb[:, :, :, 0] - blocks(g)[:, :, :, 0]         # [b, c, n, H, K]
    # a block against an earlier one, through the later block's start
    late = jnp.exp(Gb - ref[:, :, :, None])              # exponents <= 0
    lhs = jnp.concatenate([qb * late, kb * late], axis=3).astype(compute_dtype)
    earlier = jnp.tril(jnp.ones((n, n), bool), -1)[:, :, None, None, None]
    rhs = (kb[:, :, None] * jnp.exp(jnp.where(
        earlier, ref[:, :, :, None, None] - Gb[:, :, None], -jnp.inf))
    ).astype(compute_dtype)                              # [b, c, i, j, m, H, K]
    off = jnp.einsum("bcithk,bcijshk->bchitjs", lhs, rhs,
                     preferred_element_type=sd)          # t over q then k
    # a block against itself, from the differences
    by_head = jnp.moveaxis(Gb, 4, 2)                     # [b, c, H, n, m, K]
    kh, qh = jnp.moveaxis(kb, 4, 2), jnp.moveaxis(qb, 4, 2)
    own = lambda left: within_block(left, kh, by_head)
    on_diagonal = jnp.eye(n, dtype=sd)[:, None, :, None]
    whole = lambda off_part, own_part: (
        off_part + own_part[..., :, :, None, :] * on_diagonal
    ).reshape(b, c, H, L, L)
    B = whole(off[:, :, :, :, :m], own(qh))
    A = whole(off[:, :, :, :, m:], own(kh))
    return jnp.tril(A, -1), B


def carried_states(S, wk_q, w_v, k_end, decay, compute_dtype):
    """The walk over a segment's chunks: ``S`` [b, H, K, V] enters;
    ``wk_q`` [c, b, H, 2 L, K] (``T beta K e^G`` over ``q e^G``), ``w_v``
    [c, b, H, L, V] (``T beta V``), ``k_end`` [c, b, H, L, K]
    (``K e^{G_L - G}``), ``decay`` [c, b, H, K] (``e^{G_L}``) -> (the state
    that leaves, the corrections ``U`` [c, b, H, L, V], what the entering
    states read out to the queries [c, b, H, L, V]). One ``lax.scan`` in the
    accumulator dtype."""
    sd = acc_dtype(compute_dtype)
    L = w_v.shape[3]

    def step(S, chunk):
        wk_q, w_v, k_end, decay = chunk
        read = jnp.einsum("bhlk,bhkv->bhlv", wk_q, S.astype(compute_dtype),
                          preferred_element_type=sd)
        U = w_v - read[:, :, :L]
        S = S * decay[..., None] + jnp.einsum(
            "bhlk,bhlv->bhkv", k_end, U.astype(compute_dtype),
            preferred_element_type=sd)
        return S, (U, read[:, :, L:])

    return jax.lax.scan(step, S, (wk_q, w_v, k_end, decay))


def _padded_chunks(T, chunk):
    """(chunks, segments) that ``T`` steps are walked in: whole segments of
    like length, at most ``SEGMENT_CHUNKS`` chunks each."""
    chunks = -(-T // chunk)
    segments = -(-chunks // SEGMENT_CHUNKS)
    return segments * -(-chunks // segments), segments


def delta_rule_segment(S, q, k, v, g, beta, compute_dtype):
    """One segment, all of its chunks: ``S`` [b, H, K, V] enters; ``q``,
    ``k``, ``g`` [b, c, L, H, K], ``v`` [b, c, L, H, V], ``beta``
    [b, c, L, H] -> (the state that leaves, ``o`` [b, c, L, H, V]), both in
    the accumulator dtype. The in-chunk matrices carry the name
    ``CHUNK_MATS`` as the backward reads them: ``A`` (the cotangents of
    ``beta`` and of the products) and ``T`` (named by the inverse's own
    rule, which reads it) in the accumulator dtype, ``B`` in the compute
    dtype its one reader takes it in; no value is rounded for it. A
    checkpoint that keeps them recomputes no ``within_block``, no
    off-diagonal product and no inverse."""
    cd, sd = compute_dtype, acc_dtype(compute_dtype)
    g, beta = g.astype(sd), beta.astype(sd)
    G = jnp.cumsum(g, axis=2)
    A, B = _decayed_products(q, k, G, g, cd)
    by_head = lambda t: jnp.moveaxis(t, 3, 2)            # [b, c, H, L, ...]
    beta_h = by_head(beta)
    A, B = _kept(A), _kept(B.astype(cd))
    T = unit_lower_inverse(beta_h[..., None] * A)
    ks, grow = by_head(k.astype(sd)), by_head(jnp.exp(G))
    to_end = by_head(jnp.exp(G[:, :, -1:] - G))
    w = jnp.einsum(
        "bchts,bchsx->bchtx", T.astype(cd), jnp.concatenate(
            [by_head(v.astype(sd)) * beta_h[..., None],
             ks * grow * beta_h[..., None]], axis=-1).astype(cd),
        preferred_element_type=sd)
    V = v.shape[-1]
    chunk_major = lambda t: jnp.moveaxis(t, 1, 0)
    S, (U, read) = carried_states(
        S, chunk_major(jnp.concatenate(
            [w[..., V:], by_head(q.astype(sd)) * grow], axis=3).astype(cd)),
        chunk_major(w[..., :V]), chunk_major((ks * to_end).astype(cd)),
        chunk_major(jnp.exp(G[:, :, -1])), cd)
    o = jnp.moveaxis(read, 0, 1) + jnp.einsum(
        "bchts,bchsv->bchtv", B,
        jnp.moveaxis(U, 0, 1).astype(cd), preferred_element_type=sd)
    return S, jnp.moveaxis(o, 2, 3)


def delta_rule_chunked(q, k, v, g, beta, chunk, compute_dtype):
    """``o_t = S_t^T q_t`` of the module's recurrence from ``S = 0``, in
    chunks of ``chunk`` steps: ``q``, ``k``, ``g`` [b, T, H, K] (``g`` the
    log-decays, at most nought), ``v`` [b, T, H, V], ``beta`` [b, T, H] ->
    [b, T, H, V] in the accumulator dtype. A ``T`` that does not fill its
    last chunk (or segment of chunks) is padded with steps of ``g`` 0,
    ``beta`` 0 and ``k`` 0, which leave the state alone, and cut again."""
    b, T, H, K = k.shape
    chunks, segments = _padded_chunks(T, chunk)
    pad = chunks * chunk - T
    parts = (q, k, v, g, beta)
    if pad:
        parts = tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                      for t in parts)
    # segment-major: [segments, b, chunks a segment, chunk, ...]
    parts = tuple(jnp.moveaxis(
        t.reshape(b, segments, -1, chunk, *t.shape[2:]), 1, 0) for t in parts)
    segment = jax.checkpoint(
        lambda S, xs: delta_rule_segment(S, *xs, compute_dtype),
        policy=_SEGMENT_POLICY)

    def walk(S, xs):
        # a checkpoint around the layer whose policy keeps these names and
        # the in-chunk matrices' (the block stacks') holds the state every
        # segment was handed and what it read out, and runs no segment
        # again but for its own backward, which forms no in-chunk matrix
        S, o = segment(checkpoint_name(S, SCAN_CARRY), xs)
        return S, checkpoint_name(o, NORM_IN)

    _, o = jax.lax.scan(
        walk, jnp.zeros((b, H, K, v.shape[-1]), acc_dtype(compute_dtype)),
        parts)
    return jnp.moveaxis(o, 0, 1).reshape(b, T + pad, H, -1)[:, :T]


def l2_normalised(x, eps=1e-6):
    """``x / sqrt(sum(x^2, last axis) + eps)``."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@implements("KimiDeltaAttentionLayer")
class KimiDeltaAttentionImpl(LayerImpl):
    """See the config class. Leaves (H heads of K channels, ``d = H K``):
    ``Wq``, ``Wk``, ``Wv`` [n_in, d], ``conv_q``, ``conv_k``, ``conv_v``
    [d, conv_size], the decay's ``W_fa`` [n_in, K], ``W_fb`` [K, d],
    ``dt_bias`` [d], ``A_log`` [H], ``W_b`` [n_in, H], the output gate's
    ``W_ga`` [n_in, K], ``W_gb`` [K, d], ``gn`` [K] (the output norm's one
    gain a head), ``Wo`` [d, n_out]."""

    #: the bounds the decays' steps are drawn between at init (log-uniform),
    #: and A's (uniform): ``Mamba2Impl``'s
    DT_RANGE, A_RANGE = (1e-3, 1e-1), (1.0, 16.0)
    MATRICES = ("Wq", "Wk", "Wv", "conv_q", "conv_k", "conv_v", "W_fa",
                "W_fb", "W_b", "W_ga", "W_gb", "Wo")

    def _sizes(self):
        c = self.conf
        H, K = int(c.num_heads), int(c.head_dim)
        return H, K, H * K

    def init(self, rng, lead=()):
        """``lead``: leading dimensions of every leaf (a stack of layers)."""
        c = self.conf
        H, K, d = self._sizes()
        n_in, conv = c.n_in, int(c.conv_size)
        shapes = {"Wq": (n_in, d), "Wk": (n_in, d), "Wv": (n_in, d),
                  "W_fa": (n_in, K), "W_fb": (K, d), "W_b": (n_in, H),
                  "W_ga": (n_in, K), "W_gb": (K, d), "Wo": (d, c.n_out)}
        keys = jax.random.split(rng, len(self.MATRICES) + 2)
        params = {}
        for name, key in zip(self.MATRICES, keys):
            # a convolution's taps [d, conv] with the fan of its window
            shape, fans = ((shapes[name],) * 2 if name in shapes
                           else ((d, conv), (conv, conv)))
            params[name] = self._init_w(key, lead + shape, *fans)
        dt = _uniform(keys[-2], lead + (d,), np.float32,
                      *map(math.log, self.DT_RANGE))
        a = _uniform(keys[-1], lead + (H,), np.float32, *self.A_RANGE)
        # on the host where the key is concrete (no program per shape)
        xp = jnp if isinstance(dt, jax.core.Tracer) else np
        dt, a = xp.exp(xp.asarray(dt)), xp.asarray(a)
        as_leaf = lambda t: jnp.asarray(t, self.dtype)
        # softplus(dt_bias) is the drawn step
        params["dt_bias"] = as_leaf(dt + xp.log(-xp.expm1(-dt)))
        params["A_log"] = as_leaf(xp.log(a))
        params["gn"] = host_full(lead + (K,), 1, self.dtype)
        return params, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        if mask is not None:
            raise ValueError("KimiDeltaAttentionLayer: a key mask is not "
                             "supported (a padded step would have to leave "
                             "the state and the convolutions' windows alone)")
        if ctx is not None and ctx.get("rnn_state_in") is not None:
            raise ValueError("KimiDeltaAttentionLayer has no streaming "
                             "state: rnn_time_step and truncated BPTT are "
                             "not supported")
        c = self.conf
        H, K, d = self._sizes()
        cd, sd = self.compute_dtype, acc_dtype(self.compute_dtype)
        x = self.maybe_dropout(x, train, rng)
        b, T, _ = x.shape
        proj = lambda t, w: jax.lax.dot_general(
            t.astype(cd), params[w].astype(cd),
            (((t.ndim - 1,), (0,)), ((), ())), preferred_element_type=sd)
        heads = lambda t: t.reshape(b, T, H, K)
        no_bias = jnp.zeros((d,), sd)
        conv = lambda w, taps, out: heads(split_conv_silu(
            proj(x, w), params[taps].astype(sd), no_bias, 0, out)[1])
        q = l2_normalised(conv("Wq", "conv_q", sd)) * K ** -0.5
        k = l2_normalised(conv("Wk", "conv_k", sd))
        v = conv("Wv", "conv_v", cd)
        g = -jnp.exp(params["A_log"].astype(sd))[:, None] * heads(
            jax.nn.softplus(proj(proj(x, "W_fa"), "W_fb")
                            + params["dt_bias"].astype(sd)))
        beta = jax.nn.sigmoid(proj(x, "W_b"))
        with jax.named_scope("kda_rule"):
            chunk = int(c.chunk_size)
            get_registry().gauge(
                "kda_chunks",
                "Chunks the delta rule of one layer cuts a sequence into "
                "(the carried state crosses one boundary fewer), set when "
                "the layer is traced",
                layer=str(getattr(self, "index", ""))).set(-(-T // chunk))
            get_registry().gauge(
                "kda_block_pairs",
                "Pairs of steps whose decayed weights the delta rule's "
                "forward forms for one diagonal block, head and key channel "
                "(the block's lower triangle and what its bands hold above "
                "it), set when the layer is traced",
                layer=str(getattr(self, "index", ""))).set(
                    block_pairs(math.gcd(chunk, SUB)))
            get_registry().gauge(
                "kda_kept_bytes",
                "Bytes of in-chunk matrices (A, B and the triangular "
                "inverse of every chunk and head) that one differentiated "
                "layer hands its backward by name, set when the layer is "
                "traced",
                layer=str(getattr(self, "index", ""))).set(
                    b * _padded_chunks(T, chunk)[0] * H * chunk * chunk
                    * (2 * jnp.dtype(sd).itemsize + jnp.dtype(cd).itemsize))
            o = delta_rule_chunked(q, k, v, g, beta, chunk, cd)
        gate = jax.nn.sigmoid(proj(proj(x, "W_ga"), "W_gb"))
        y = (rms_norm(o, params["gn"], c.eps, sd).reshape(b, T, d) * gate)
        # the out-projection's operand stands as an array of its own
        # (``Mamba2Impl.forward``'s reason)
        y = jax.lax.optimization_barrier(y.astype(cd))
        return self.activation(proj(y, "Wo")).astype(self.out_dtype), state

    def regularization(self, params):
        # the matrices only: gains, biases and the decays' vectors are free
        return super().regularization({k: params[k] for k in self.MATRICES})

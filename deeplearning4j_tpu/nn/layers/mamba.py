"""Mamba-2 state-space mixer (Dao, Gu 2024: "Transformers are SSMs").

Net-new vs the 0.9.x reference, whose only sequence layers are recurrent
cells. The selective recurrence ``S_t = a_t S_{t-1} + D_t x_t (x) B_t``,
``y_t = S_t C_t`` is computed in chunks (the paper's state-space duality):
within a chunk every output is a masked-decay product over the chunk's own
steps (einsums the MXU takes), and one state per chunk crosses the boundary,
carried by a ``lax.scan`` over the chunks. In plain XLA: no Pallas kernel.

Precision under a bfloat16 compute policy: the products' operands take the
compute dtype; the step sizes, the decays and their cumulative sums, the
state carried between chunks, the convolution and the gated norm's
statistics are float32.

One function carries a differentiation rule of its own (``jax.custom_vjp``):
:func:`split_conv_silu`, the in-projection's split with the convolution, its
SiLU and the cast to the compute dtype. It keeps the float32 projection, the
taps and the bias, and forms the pre-activation again backward; then one
``dpre``, the input's cotangent as K shifted multiply-adds of it, the taps'
and the bias's as reductions, and z's, xBC's and dt's cotangents leave as one
concatenation. Its bodies run under the scopes of the call (``ssm``), trace
no metric, and forward-mode differentiation of the layer is refused by jax.
The gated norm, ``rms_norm(y * silu(z), gn)``, is left to autodiff: a rule
that kept y and z and recomputed the rest compiled to the same three passes
(PERF.md section 6, PR 35); its output, cast to the compute dtype, passes a
``lax.optimization_barrier`` on its way to the out-projection, so that it is
written once and the product reads an array.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ...monitor import get_registry
from ..weights import _uniform, host_full
from .base import LayerImpl, implements, acc_dtype
from .normalization import rms_norm


def _shifted(x, k):
    """``x`` [b, T, C] moved ``k`` steps later in time (earlier for a
    negative ``k``); the steps that enter read as nought."""
    return jax.lax.pad(x, jnp.zeros((), x.dtype),
                       ((0, 0, 0), (k, -k, 0), (0, 0, 0)))


def _taps(x, K, lo, C):
    """What the K taps of a causal convolution over channels [lo, lo + C) of
    ``x`` [b, T, >= lo + C] read: tap k reads ``x_{t - K + 1 + k}``. Moved in
    time first and cut to the channels after, so that the compiler reads the
    channels where they lie and keeps no copy of them."""
    return [_shifted(x, K - 1 - k)[..., lo:lo + C] for k in range(K)]


def causal_conv1d(x, w, b, lo=0):
    """Depthwise causal convolution over time of channels [lo, lo + C) of
    ``x`` [b, T, >= lo + C], ``w`` [C, K], ``b`` [C] -> ``y_t = sum_k w[:, k]
    x_{t - K + 1 + k} + b`` [b, T, C] (steps before the first read as
    nought): K shifted multiply-adds, no gemm."""
    y = b
    for k, tap in enumerate(_taps(x, w.shape[-1], lo, w.shape[0])):
        y = y + tap * w[:, k]
    return y


def split_conv_silu(zxbcdt, w, b, d_inner, out_dtype):
    """The in-projection's split and the convolution as one function:
    ``zxbcdt`` [b, T, d_inner + C + H] (z | xBC | dt), ``w`` [C, K], ``b``
    [C] -> (z, ``silu(causal_conv1d(xBC, w, b))`` cast to ``out_dtype``, dt),
    the convolution and its SiLU in the dtype of ``zxbcdt``. Differentiated
    by a rule of its own (below): autodiff's transposition of the K shifted
    reads writes K arrays of xBC's size and adds them up again, and the split
    copies xBC out of the projection for it."""
    C = w.shape[0]
    return (zxbcdt[..., :d_inner],
            jax.nn.silu(causal_conv1d(zxbcdt, w, b, d_inner)).astype(out_dtype),
            zxbcdt[..., d_inner + C:])


def _split_conv_silu_fwd(zxbcdt, w, b, d_inner, out_dtype):
    # kept: the projection, the taps and the bias; the pre-activation is
    # formed again backward (the compiler may share it with a recomputed
    # forward that stands in the same program)
    return _split_conv_silu(zxbcdt, w, b, d_inner, out_dtype), (zxbcdt, w, b)


def _split_conv_silu_bwd(d_inner, out_dtype, kept, cotangents):
    zxbcdt, w, b = kept
    dz, dy, ddt = cotangents
    C, K = w.shape
    pre = causal_conv1d(zxbcdt, w, b, d_inner)
    gate = jax.nn.sigmoid(pre)
    dpre = dy.astype(pre.dtype) * (gate * (1 + pre * (1 - gate)))
    # tap k read x_{t - (K-1-k)}: it hands dpre_{t + (K-1-k)} back to x_t
    dx = sum(_shifted(dpre, k - (K - 1)) * w[:, k] for k in range(K))
    dw = jnp.stack([jnp.sum(dpre * tap, axis=(0, 1))
                    for tap in _taps(zxbcdt, K, d_inner, C)], axis=-1)
    return (jnp.concatenate([dz, dx, ddt], axis=-1), dw,
            jnp.sum(dpre, axis=(0, 1)))


_split_conv_silu = split_conv_silu
split_conv_silu = jax.custom_vjp(_split_conv_silu, nondiff_argnums=(3, 4))
split_conv_silu.defvjp(_split_conv_silu_fwd, _split_conv_silu_bwd)


def carried_states(local, decay, first):
    """The state entering each chunk and the one leaving the last: ``local``
    [b, c, H, P, N] is what a chunk's own steps add to the state at its end,
    ``decay`` [b, c, H] what the chunk leaves of the state it was handed,
    ``first`` [b, H, P, N] the state the first chunk is handed. One
    ``lax.scan`` over the chunks in ``local``'s dtype."""
    def step(s, chunk):
        add, keep = chunk
        return s * keep[..., None, None] + add, s
    last, entering = jax.lax.scan(
        step, first, (jnp.moveaxis(local, 1, 0), jnp.moveaxis(decay, 1, 0)))
    return jnp.moveaxis(entering, 0, 1), last


def ssd_segment(state, x, dt, a, B, C, compute_dtype):
    """One segment of the scan, all of its chunks at once: ``state``
    [b, H, P, N] enters; ``x`` [b, c, L, H, P], ``dt`` [b, c, L, H], ``B``,
    ``C`` [b, c, L, N] in chunks of L steps -> (the state that leaves,
    ``y`` [b, c, L, H, P]), both in the accumulator dtype."""
    cd, sd = compute_dtype, acc_dtype(compute_dtype)
    chunk = x.shape[2]
    dt = dt.astype(sd)
    B, C = B.astype(cd), C.astype(cd)
    einsum = lambda spec, l, r: jnp.einsum(spec, l, r,
                                           preferred_element_type=sd)
    # log of the decay from a chunk's start to the end of each of its steps
    cum = jnp.cumsum(dt * a.astype(sd), axis=2)              # [b, c, L, H]
    xs = x.astype(sd)
    # within a chunk: sum over s <= t of exp(cum_t - cum_s) (C_t.B_s) dt_s x_s
    by_head = jnp.moveaxis(cum, -1, 2)                       # [b, c, H, L]
    seg = by_head[..., :, None] - by_head[..., None, :]      # [b, c, H, t, s]
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    weights = (einsum("bcln,bcsn->bcls", C, B)[:, :, None]
               * jnp.exp(jnp.where(seen, seg, -jnp.inf)))
    y = einsum("bchls,bcshp->bclhp", weights.astype(cd),
               (xs * dt[..., None]).astype(cd))
    # across chunks: what each chunk adds to the state at its own end, the
    # state carried from chunk to chunk, and its read-out at every step
    to_end = jnp.exp(cum[:, :, -1:] - cum)                   # [b, c, L, H]
    local = einsum("bcshp,bcsn->bchpn",
                   (xs * (dt * to_end)[..., None]).astype(cd), B)
    entering, state = carried_states(local, jnp.exp(cum[:, :, -1]), state)
    y = y + einsum("bcln,bchpn->bclhp", C,
                   entering.astype(cd)) * jnp.exp(cum)[..., None]
    return state, y


#: chunks whose masked-decay products are alive at once: a longer sequence is
#: cut into segments of at most so many chunks, walked by a ``lax.scan`` that
#: carries the state and keeps, differentiated, only each segment's inputs
#: (at 64 heads and chunks of 256 the [chunks, H, 256, 256] float32 products
#: are 17 MB a chunk, several of them alive in the backward pass)
SEGMENT_CHUNKS = 8


def ssd_chunked(x, dt, a, B, C, chunk, compute_dtype):
    """``y_t = S_t C_t`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``
    from ``S = 0``, in chunks of ``chunk`` steps. ``x`` [b, T, H, P], ``dt``
    [b, T, H] (positive), ``a`` [H] (negative), ``B``, ``C`` [b, T, N] (one
    group: shared by the heads) -> [b, T, H, P] in the accumulator dtype. A
    ``T`` that does not fill its last chunk (or segment of chunks) is padded
    with steps of ``dt`` 0, which leave the state alone, and cut again."""
    b, T, H, P = x.shape
    chunks = -(-T // chunk)
    segments = -(-chunks // SEGMENT_CHUNKS)
    per = -(-chunks // segments)                 # chunks a segment
    pad = segments * per * chunk - T
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    # segment-major: [segments, b, chunks a segment, chunk, ...]
    x, dt, B, C = (jnp.moveaxis(
        t.reshape(b, segments, per, chunk, *t.shape[2:]), 1, 0)
        for t in (x, dt, B, C))
    segment = jax.checkpoint(
        lambda s, xs: ssd_segment(s, *xs[:2], a, *xs[2:], compute_dtype))
    _, y = jax.lax.scan(
        segment, jnp.zeros((b, H, P, B.shape[-1]), acc_dtype(compute_dtype)),
        (x, dt, B, C))
    return jnp.moveaxis(y, 0, 1).reshape(b, T + pad, H, P)[:, :T]


@implements("Mamba2Layer")
class Mamba2Impl(LayerImpl):
    """See the config class. Leaves: ``W_in`` [n_in, 2 d_inner + 2 N + H]
    (z | x B C | dt), ``conv_W`` [d_inner + 2 N, K], ``conv_bias``,
    ``dt_bias``, ``A_log``, ``D`` [H], ``gn`` [d_inner] (the gated norm's
    gain), ``W_out`` [d_inner, n_out]."""

    #: the bounds the step sizes are drawn between at init (log-uniform),
    #: and A's (uniform): the Mamba-2 paper's defaults
    DT_RANGE, A_RANGE = (1e-3, 1e-1), (1.0, 16.0)

    def _sizes(self):
        c = self.conf
        H, P, N = int(c.num_heads), int(c.head_dim), int(c.state_size)
        return H, P, N, H * P

    def init(self, rng, lead=()):
        """``lead``: leading dimensions of every leaf (a stack of layers)."""
        c = self.conf
        H, P, N, d_inner = self._sizes()
        K, conv = int(c.conv_size), d_inner + 2 * N
        k_in, k_conv, k_dt, k_a, k_out = jax.random.split(rng, 5)
        width = 2 * d_inner + 2 * N + H
        dt = _uniform(k_dt, lead + (H,), np.float32,
                      *map(math.log, self.DT_RANGE))
        a = _uniform(k_a, lead + (H,), np.float32, *self.A_RANGE)
        # on the host where the key is concrete (no program per shape)
        xp = jnp if isinstance(dt, jax.core.Tracer) else np
        dt, a = xp.exp(xp.asarray(dt)), xp.asarray(a)
        as_leaf = lambda v: jnp.asarray(v, self.dtype)
        return {
            "W_in": self._init_w(k_in, lead + (c.n_in, width), c.n_in, width),
            "conv_W": self._init_w(k_conv, lead + (conv, K), K, K),
            "conv_bias": host_full(lead + (conv,), 0, self.dtype),
            # softplus(dt_bias) is the drawn step size
            "dt_bias": as_leaf(dt + xp.log(-xp.expm1(-dt))),
            "A_log": as_leaf(xp.log(a)),
            "D": host_full(lead + (H,), 1, self.dtype),
            "gn": host_full(lead + (d_inner,), 1, self.dtype),
            "W_out": self._init_w(k_out, lead + (d_inner, c.n_out), d_inner,
                                  c.n_out),
        }, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        if mask is not None:
            raise ValueError("Mamba2Layer: a key mask is not supported (a "
                             "padded step would have to leave the state and "
                             "the convolution's window alone)")
        if ctx is not None and ctx.get("rnn_state_in") is not None:
            raise ValueError("Mamba2Layer has no streaming state: "
                             "rnn_time_step and truncated BPTT are not "
                             "supported")
        c = self.conf
        H, P, N, d_inner = self._sizes()
        cd, sd = self.compute_dtype, acc_dtype(self.compute_dtype)
        x = self.maybe_dropout(x, train, rng)
        b, T, _ = x.shape
        proj = lambda t, w: jax.lax.dot_general(
            t.astype(cd), w.astype(cd), (((t.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=sd)
        z, xbc, dt = split_conv_silu(
            proj(x, params["W_in"]), params["conv_W"].astype(sd),
            params["conv_bias"].astype(sd), d_inner, cd)
        xs, B, C = jnp.split(xbc, [d_inner, d_inner + N], axis=-1)
        xs = xs.reshape(b, T, H, P)
        with jax.named_scope("ssd"):
            chunk = int(c.chunk_size)
            get_registry().gauge(
                "ssm_chunks",
                "Chunks the state-space scan of one layer cuts a sequence "
                "into (the carried state crosses one boundary fewer), set "
                "when the layer is traced",
                layer=str(getattr(self, "index", ""))).set(-(-T // chunk))
            dt = jax.nn.softplus(dt + params["dt_bias"].astype(sd))
            y = ssd_chunked(xs, dt, -jnp.exp(params["A_log"].astype(sd)), B,
                            C, chunk, cd)
            y = y + params["D"].astype(sd)[:, None] * xs.astype(sd)
        y = rms_norm(y.reshape(b, T, d_inner) * jax.nn.silu(z), params["gn"],
                     c.eps, sd)
        # the out-projection's operand stands as an array of its own: fused
        # into the product as its operand's producer, the norm sets the
        # product's pace (on the v5e 1.75 ms a block for a 0.70 ms gemm)
        y = jax.lax.optimization_barrier(y.astype(cd))
        return self.activation(proj(y, params["W_out"])).astype(
            self.out_dtype), state

    def regularization(self, params):
        # the matrices only: gains, biases and the scan's vectors are free
        return super().regularization(
            {k: params[k] for k in ("W_in", "conv_W", "W_out")})

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


def causal_conv1d(x, w, b):
    """Depthwise causal convolution over time: ``x`` [b, T, C], ``w`` [C, K],
    ``b`` [C] -> ``y_t = sum_k w[:, k] x_{t - K + 1 + k} + b`` (steps before
    the first read as nought): K shifted multiply-adds, no gemm."""
    K, T = w.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = b
    for k in range(K):
        y = y + padded[:, k:k + T] * w[:, k]
    return y


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
        z, xbc, dt = jnp.split(proj(x, params["W_in"]),
                               [d_inner, 2 * d_inner + 2 * N], axis=-1)
        xbc = jax.nn.silu(causal_conv1d(xbc, params["conv_W"].astype(sd),
                                        params["conv_bias"].astype(sd)))
        xs, B, C = jnp.split(xbc.astype(cd), [d_inner, d_inner + N], axis=-1)
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
        return self.activation(proj(y, params["W_out"])).astype(
            self.out_dtype), state

    def regularization(self, params):
        # the matrices only: gains, biases and the scan's vectors are free
        return super().regularization(
            {k: params[k] for k in ("W_in", "conv_W", "W_out")})

"""Normalization implementations: BatchNormalization, LayerNormalization,
RMSNorm, LocalResponseNormalization.

TPU-native equivalents of reference ``nn/layers/normalization/{BatchNormalization,
LocalResponseNormalization}.java`` (cuDNN helper hooks at
``CudnnBatchNormalizationHelper``; here the XLA schedule plays that role).
Running mean/var live in the layer *state* pytree — the functional replacement
for the reference's mutable mean/var params — and are updated only when
``train=True``.

BN is pure HBM traffic, so the training path is written for the memory system
(see PERF.md):

 - batch statistics are a *single* fused pass over ``x``: two reductions
   (sum, sum-of-squares) with f32 accumulators via the reduce's ``dtype=`` —
   ``jnp.var``'s mean-then-deviations formulation costs an extra full
   traversal of every conv output.
 - the per-channel statistics are tagged ``checkpoint_name`` so the train
   step's remat policy (``GlobalConfig.remat``) stores them — tiny [C]
   vectors — while the normalized output itself is recomputed in the
   backward pass instead of being round-tripped through HBM
   (``save_output = False``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .base import LayerImpl, implements, acc_dtype
from ..weights import host_full


@implements("BatchNormalization")
class BatchNormImpl(LayerImpl):
    """Per-channel BN for [b, f] and NHWC [b, h, w, c] activations.
    Params gamma/beta (reference keys), state mean/var with ``decay`` EMA
    (reference ``BatchNormalization.java`` decay semantics:
    running = decay * running + (1-decay) * batch)."""

    save_output = False  # normalize is elementwise given stats: recompute

    def init(self, rng):
        c = self.conf
        n = c.n_out
        params = {}
        if not c.lock_gamma_beta:
            params["gamma"] = host_full((n,), c.gamma, self.dtype)
            params["beta"] = host_full((n,), c.beta, self.dtype)
        sd = acc_dtype(self.compute_dtype)  # stats precision
        state = {"mean": host_full((n,), 0, sd),
                 "var": host_full((n,), 1, sd)}
        return params, state

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        c = self.conf
        sd = acc_dtype(self.compute_dtype)
        axes = tuple(range(x.ndim - 1))  # all but channel/feature
        if train:
            if jnp.dtype(x.dtype).itemsize < 4:
                # one fused traversal of x: f32-accumulated sum and
                # sum-of-squares. E[x^2]-E[x]^2 cancels catastrophically when
                # |mean| >> std, but sub-32-bit x cannot *represent* such
                # data (bf16's 8-bit mantissa bounds mean/std ≈ 256, keeping
                # the f32 error below the input quantization) — so the fused
                # form is safe exactly where it is fast. Guard is on x's own
                # dtype: full-precision inputs take the exact path below even
                # under a bf16 compute policy.
                mean = jnp.mean(x, axis=axes, dtype=sd)
                meansq = jnp.mean(jnp.square(x.astype(sd)), axis=axes)
                var = jnp.maximum(meansq - mean * mean, 0.0)
            else:
                # full-precision compute: shifted two-pass (jnp.var) — exact
                # for large-mean data; f32/f64 runs are correctness-first
                mean = jnp.mean(x, axis=axes, dtype=sd)
                var = jnp.var(x.astype(sd), axis=axes)
            mean = checkpoint_name(mean, "dl4j_stat")
            var = checkpoint_name(var, "dl4j_stat")
            new_state = {
                "mean": c.decay * state["mean"] + (1 - c.decay) * mean,
                "var": c.decay * state["var"] + (1 - c.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = jax.lax.rsqrt((var + c.eps).astype(sd))
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
        if "gamma" in params:
            y = y * params["gamma"].astype(x.dtype) + params["beta"].astype(x.dtype)
        else:
            y = y * c.gamma + c.beta
        return y, new_state

    def regularization(self, params):
        return 0.0  # reference: no l1/l2 on BN params by default


@implements("LayerNormalization")
class LayerNormImpl(LayerImpl):
    """Per-position LayerNorm over the last (feature) dim, learned
    gain/bias (net-new: the reference predates transformers — see the
    config class). Stateless; normalizes [b, F] or [b, T, F] tokens
    independently, so a sharded time dim needs no collectives and the
    whole op fuses into one elementwise XLA kernel around two f32-
    accumulated moments."""

    save_output = False  # elementwise given the two moments: recompute

    def init(self, rng):
        n = self.conf.n_out
        return {"gain": host_full((n,), 1, self.dtype),
                "bias": host_full((n,), 0, self.dtype)}, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        sd = acc_dtype(self.compute_dtype)
        xs = x.astype(sd)
        mean = jnp.mean(xs, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xs - mean), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + self.conf.eps)
        y = (xs - mean) * inv
        y = (y * params["gain"].astype(sd) + params["bias"].astype(sd))
        return y.astype(x.dtype), state

    def regularization(self, params):
        return 0.0  # norm params free of l1/l2, like BN


def rms_norm(x, gain, eps, stat_dtype):
    """``x / sqrt(mean(x^2, last axis) + eps) * gain`` with the statistics
    and the product in ``stat_dtype``; returned in ``stat_dtype``."""
    xs = x.astype(stat_dtype)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xs), axis=-1, keepdims=True) + eps)
    return xs * inv * gain.astype(stat_dtype)


@implements("RMSNorm")
class RMSNormImpl(LayerImpl):
    """Per-position RMS normalization with a learned gain (see the config
    class); float32 statistics under a bfloat16 compute policy, one
    elementwise kernel around one f32-accumulated moment."""

    save_output = False  # elementwise given the moment: recompute

    def init(self, rng):
        return {"gain": host_full((self.conf.n_out,), 1, self.dtype)}, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        y = rms_norm(x, params["gain"], self.conf.eps,
                     acc_dtype(self.compute_dtype))
        return y.astype(x.dtype), state

    def regularization(self, params):
        return 0.0  # norm params free of l1/l2, like BN


@implements("LocalResponseNormalization")
class LRNImpl(LayerImpl):
    """Across-channel LRN on NHWC (reference ``LocalResponseNormalization.java``):
    y = x / (k + alpha * sum_{j in window} x_j^2)^beta."""

    save_output = False

    def init(self, rng):
        return {}, {}

    def regularization(self, params):
        return 0.0

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        c = self.conf
        half = int(c.n) // 2
        sq = x * x
        # sum over channel window via padded cumulative trick (static unroll of
        # the small window; XLA fuses this into one elementwise kernel)
        acc = jnp.zeros_like(sq)
        ch = x.shape[-1]
        for off in range(-half, half + 1):
            if off == 0:
                acc = acc + sq
            elif off < 0:
                acc = acc.at[..., :off].add(sq[..., -off:])
            else:
                acc = acc.at[..., off:].add(sq[..., :ch - off])
        denom = jnp.power(c.k + c.alpha * acc, c.beta)
        return x / denom, state

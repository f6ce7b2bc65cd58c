"""Output layer implementations: OutputLayer, RnnOutputLayer, LossLayer,
CenterLossOutputLayer, LoopLMOutputLayer.

TPU-native equivalents of reference ``nn/layers/OutputLayer.java`` /
``BaseOutputLayer.java`` (``computeScore``). An output layer is a dense projection
plus a loss; ``loss_on`` evaluates the loss on *preoutput* so numerically fused
softmax/sigmoid cross-entropy paths apply (see ``nn.losses``). The network's
jitted train step calls ``loss_on``; ``forward`` gives inference activations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import LayerImpl, NoParamLayerImpl, implements, acc_dtype
from ..weights import host_full
from .feedforward import _dot
from ..losses import get_loss, _reduce


class _OutputBase(LayerImpl):
    def preout(self, params, x):
        z = _dot(x, params["W"], self.compute_dtype)
        if "b" in params:
            z = z + params["b"].astype(z.dtype)
        return z

    def init(self, rng):
        c = self.conf
        params = {"W": self._init_w(rng, (c.n_in, c.n_out), c.n_in, c.n_out)}
        if getattr(c, "has_bias", True):
            params["b"] = self._init_b((c.n_out,))
        return params, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        x = self.maybe_dropout(x, train, rng)
        # terminal layer: user-facing predictions stay full precision — the
        # bf16 inter-layer policy (out_dtype) is an HBM-bandwidth measure and
        # the one output cast costs nothing
        return self.activation(self.preout(params, x)).astype(self.dtype), state

    def loss_on(self, params, state, x, labels, mask=None, train=True, rng=None):
        x = self.maybe_dropout(x, train, rng)
        z = self.preout(params, x)
        return get_loss(self.conf.loss)(labels, z, self.activation_name, mask)


@implements("OutputLayer")
class OutputLayerImpl(_OutputBase):
    pass


@implements("RnnOutputLayer")
class RnnOutputLayerImpl(_OutputBase):
    """Per-timestep output over [b, T, nIn] (reference ``RnnOutputLayer.java``);
    loss is mask-aware over [b, T]."""
    pass


def exit_distribution(gate_logits):
    """``(p, log p)`` over the leading (pass) axis from the exit gate's
    logits [R, ...]: ``p_t = lambda_t * prod_{j<t}(1 - lambda_j)`` with
    ``lambda = sigmoid(logits)``, the last pass taking the remainder
    ``prod_{j<R}(1 - lambda_j)`` whatever its own gate says. In log space
    (``log lambda = log_sigmoid(z)``, ``log(1 - lambda) = log_sigmoid(-z)``),
    so a saturated gate gives 0 * -inf nowhere."""
    stay = jax.nn.log_sigmoid(-gate_logits)
    before = jnp.cumsum(stay, axis=0) - stay          # sum over j < t
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits[:-1]) + before[:-1], before[-1:]])
    return jnp.exp(log_p), log_p


@implements("LoopLMOutputLayer")
class LoopLMOutputImpl(_OutputBase):
    """Head and exit gate over the [R, b, T, n_in] states of a looped stack
    (see the config class). The logits and the softmax statistics are float32
    whatever the compute dtype (the gemm's operands take the compute dtype,
    its accumulator is the output); one pass's logits are alive at a time:
    the per-pass head and cross-entropy run as the checkpointed body of a
    ``lax.scan`` over the passes."""

    def __init__(self, conf, gc, input_type=None):
        super().__init__(conf, gc, input_type)
        if (conf.loss, self.activation_name) != ("sparse_mcxent", "softmax"):
            raise ValueError(
                "LoopLMOutputLayer computes the exit-gate-weighted next-token "
                "cross-entropy over a softmax and no other loss: got loss="
                f"{conf.loss!r}, activation={self.activation_name!r}")

    def init(self, rng):
        c = self.conf
        k_head, k_gate = jax.random.split(rng)
        params, _ = super().init(k_head)
        params["gate_W"] = self._init_w(k_gate, (c.n_in,), c.n_in, 1)
        params["gate_b"] = self._init_b((1,), 0.0)
        return params, {}

    def _logits(self, params, h):
        cd = self.compute_dtype
        z = jax.lax.dot_general(
            h.astype(cd), params["W"].astype(cd),
            (((h.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype(cd))
        if "b" in params:
            z = z + params["b"].astype(z.dtype)
        return z

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        # inference reads the last pass
        z = self._logits(params, self.maybe_dropout(x[-1], train, rng))
        return self.activation(z).astype(self.dtype), state

    def loss_on(self, params, state, x, labels, mask=None, train=True, rng=None):
        x = self.maybe_dropout(x, train, rng)
        sd = acc_dtype(self.compute_dtype)
        labels = labels.astype(jnp.int32)

        @jax.checkpoint
        def pass_xent(h):
            z = self._logits(params, h)
            picked = jnp.take_along_axis(z, labels[..., None], axis=-1)
            return jax.nn.logsumexp(z, axis=-1) - picked[..., 0]

        # the scope is around the scan, so that the backward scan's own ops
        # (the head gradient's accumulation over the passes) carry it too
        with jax.named_scope("head"):
            _, xent = jax.lax.scan(lambda _, h: (None, pass_xent(h)), None, x)
        with jax.named_scope("exit_gate"):
            gate = (jnp.einsum("rbtd,d->rbt", x.astype(sd),
                               params["gate_W"].astype(sd))
                    + params["gate_b"].astype(sd))
            p, log_p = exit_distribution(gate)
            per_token = jnp.sum(p * xent, axis=0) \
                + self.conf.entropy_weight * jnp.sum(p * log_p, axis=0)
        return _reduce(per_token[..., None], mask)


@implements("LossLayer")
class LossLayerImpl(NoParamLayerImpl):
    """Loss without weights (reference ``nn/layers/LossLayer.java``)."""

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        return self.activation(x), state

    def loss_on(self, params, state, x, labels, mask=None, train=True, rng=None):
        return get_loss(self.conf.loss)(labels, x, self.activation_name, mask)


@implements("CenterLossOutputLayer")
class CenterLossOutputLayerImpl(_OutputBase):
    """Softmax loss + lambda * center loss (reference
    ``nn/layers/training/CenterLossOutputLayer.java``). Class centers are state,
    EMA-updated toward batch feature means with rate ``alpha``."""

    def init(self, rng):
        params, _ = super().init(rng)
        c = self.conf
        state = {"centers": host_full((c.n_out, c.n_in), 0,
                                      jnp.float32)}
        return params, state

    def loss_on(self, params, state, x, labels, mask=None, train=True, rng=None):
        c = self.conf
        z = self.preout(params, x)
        base = get_loss(c.loss)(labels, z, self.activation_name, mask)
        centers = state["centers"]
        cls = jnp.argmax(labels, axis=-1)
        diffs = x - centers[cls]
        center_loss = 0.5 * jnp.mean(jnp.sum(diffs * diffs, axis=-1))
        return base + c.lambda_ * center_loss

    def update_state(self, state, x, labels):
        """EMA center update (called outside AD by the train step)."""
        c = self.conf
        cls = jnp.argmax(labels, axis=-1)
        onehot = jax.nn.one_hot(cls, c.n_out, dtype=jnp.float32)
        counts = jnp.maximum(onehot.sum(axis=0), 1.0)[:, None]
        batch_means = (onehot.T @ x.astype(jnp.float32)) / counts
        present = (onehot.sum(axis=0) > 0)[:, None]
        centers = state["centers"]
        new_centers = jnp.where(present,
                                centers + c.alpha * (batch_means - centers),
                                centers)
        return {"centers": new_centers}

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
    loss is mask-aware over [b, T]. With ``tied_to`` the head is another
    vertex's ``W`` [n_out, n_in], which the graph hands over as ``tied_W``
    beside the layer's own leaves (a bias at most); with it or with
    ``logits_divisor`` the gemm's operands take the compute dtype and the
    logits are its float32 accumulator."""

    def init(self, rng):
        if self.conf.tied_to is None:
            return super().init(rng)
        c = self.conf          # no ``W`` is drawn: the head is the tied leaf
        return ({"b": self._init_b((c.n_out,))}
                if getattr(c, "has_bias", True) else {}), {}

    def preout(self, params, x):
        c = self.conf
        if c.tied_to is None and c.logits_divisor is None:
            return super().preout(params, x)
        cd = self.compute_dtype
        w, axis = ((params["W"], 0) if c.tied_to is None
                   else (params["tied_W"], 1))
        with jax.named_scope("head"):
            z = jax.lax.dot_general(
                x.astype(cd), w.astype(cd), (((x.ndim - 1,), (axis,)), ((), ())),
                preferred_element_type=acc_dtype(cd))
            if "b" in params:
                z = z + params["b"].astype(z.dtype)
            if c.logits_divisor is not None:
                z = z / jnp.asarray(c.logits_divisor, z.dtype)
            return z


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


def _xent(z, labels):
    """``(logsumexp, logsumexp - picked)`` of the logits ``z`` [..., V]."""
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return lse, lse - picked


def weighted_xent(impl, head, x, labels, w):
    """``sum(w * xent)``: the next-token cross-entropy of the states ``x``
    [R, b, T, d] under ``head`` (``W`` and, where the layer has one, ``b``;
    the logits as ``impl._logits`` forms them), each token's weighed by ``w``
    [R, b, T]. A ``lax.scan`` over the passes: one pass's logits are alive at
    a time.

    Differentiated by a rule of its own, not by AD. The cross-entropy's
    gradient with respect to the logits, ``w * (softmax - onehot)``, is known
    the moment the logits and their ``logsumexp`` are, and the total is linear
    in the one scalar that arrives from above. So the rule's forward sweep
    forms a pass's logits once and builds the head's and the states'
    gradients from them while they are alive (the two transposed products AD
    would run, on AD's operands; the head's summed over the passes in the
    scan's carry); its residuals are those gradients and ``xent``, and the
    backward sweep scales them: no product with a vocabulary axis is left
    there, and none is run twice."""
    with jax.named_scope("head"):
        _, xent = jax.lax.scan(
            lambda _, h: (None, _xent(impl._logits(head, h), labels)[1]),
            None, x)
        return jnp.sum(w * xent)


def _weighted_xent_fwd(impl, head, x, labels, w):
    def one_pass(dhead, h_w):
        h, w_r = h_w
        z, transposed = jax.vjp(impl._logits, head, h)
        lse, xent = _xent(z, labels)
        dz = w_r[..., None] * (jnp.exp(z - lse[..., None]) - jax.nn.one_hot(
            labels, z.shape[-1], dtype=z.dtype))
        dhead_r, dh = transposed(dz.astype(z.dtype))
        return jax.tree_util.tree_map(jnp.add, dhead, dhead_r), (xent, dh)

    # imported here, not at the top: every network's step carries this
    # file's line numbers (``_OutputBase``) in its op metadata, which the
    # compile cache's key holds, and a moved line compiles them all again
    from ...monitor import get_registry
    get_registry().gauge(
        "looped_head_logits_per_pass",
        "Times a pass's [tokens, vocabulary] logits are formed in one "
        "differentiated step of a looped output layer, set when its rule's "
        "forward sweep is traced", layer=str(getattr(impl, "index", ""))
    ).set(1)        # here, and not again in the backward sweep
    with jax.named_scope("head"):
        dhead, (xent, dx) = jax.lax.scan(
            one_pass, jax.tree_util.tree_map(jnp.zeros_like, head), (x, w))
        return jnp.sum(w * xent), (dhead, dx, xent)


def _weighted_xent_bwd(impl, residuals, g):
    dhead, dx, xent = residuals
    with jax.named_scope("head"):
        dhead, dx = jax.tree_util.tree_map(
            lambda t: (g * t).astype(t.dtype), (dhead, dx))
        return dhead, dx, None, g * xent      # labels are integers


weighted_xent = jax.custom_vjp(weighted_xent, nondiff_argnums=(0,))
weighted_xent.defvjp(_weighted_xent_fwd, _weighted_xent_bwd)


@implements("LoopLMOutputLayer")
class LoopLMOutputImpl(_OutputBase):
    """Head and exit gate over the [R, b, T, n_in] states of a looped stack
    (see the config class). The logits and the softmax statistics are float32
    whatever the compute dtype (the gemm's operands take the compute dtype,
    its accumulator is the output). One pass's logits are alive at a time, in
    the forward sweep only: the exit gate's weights are computed first, from
    the states, and :func:`weighted_xent` builds the head's gradients there,
    from the live logits, under its own differentiation rule."""

    def __init__(self, conf, gc, input_type=None):
        super().__init__(conf, gc, input_type)
        if (conf.loss, self.activation_name) != ("sparse_mcxent", "softmax"):
            raise ValueError(
                "LoopLMOutputLayer computes the exit-gate-weighted next-token "
                "cross-entropy over a softmax and no other loss: got loss="
                f"{conf.loss!r}, activation={self.activation_name!r}")
        if conf.tied_to is not None or conf.logits_divisor is not None:
            raise ValueError("LoopLMOutputLayer has a head of its own: "
                             "tied_to and logits_divisor are RnnOutputLayer's")

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
        with jax.named_scope("exit_gate"):
            gate = (jnp.einsum("rbtd,d->rbt", x.astype(sd),
                               params["gate_W"].astype(sd))
                    + params["gate_b"].astype(sd))
            p, log_p = exit_distribution(gate)
            # _reduce is linear in the per-token loss: transposed, it gives
            # the weight a token's loss carries into the mean
            share, = jax.linear_transpose(
                lambda per_token: _reduce(per_token[..., None], mask),
                jax.ShapeDtypeStruct(p.shape[1:], sd))(jnp.ones((), sd))
            w = p * share
        head = {k: params[k] for k in ("W", "b") if k in params}
        return weighted_xent(self, head, x, labels.astype(jnp.int32), w) \
            + self.conf.entropy_weight * jnp.sum(w * log_p)


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

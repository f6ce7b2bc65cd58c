"""Feed-forward layer implementations: Dense, GatedDense, Activation, Dropout,
Embedding, AutoEncoder.

TPU-native equivalents of reference ``nn/layers/feedforward/`` +
``nn/layers/BaseLayer.java`` (dense preOutput/activate) — gemms hit the MXU via a
single fused XLA dot with bfloat16 compute / f32 accumulation when the dtype
policy asks for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import LayerImpl, NoParamLayerImpl, implements, acc_dtype, pet_dtype


def _dot(x, w, compute_dtype):
    # low-precision compute accumulates in f32 on the MXU (see acc_dtype)
    return jax.lax.dot_general(x.astype(compute_dtype), w.astype(compute_dtype),
                               (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=pet_dtype(compute_dtype))


@implements("DenseLayer")
class DenseImpl(LayerImpl):
    """Reference ``nn/layers/feedforward/dense/DenseLayer.java`` (via BaseLayer
    preOutput: z = xW + b, ``nn/layers/BaseLayer.java``)."""

    def init(self, rng):
        c = self.conf
        w = self._init_w(rng, (c.n_in, c.n_out), c.n_in, c.n_out)
        params = {"W": w}
        if getattr(c, "has_bias", True):
            params["b"] = self._init_b((c.n_out,))
        return params, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        x = self.maybe_dropout(x, train, rng)
        z = _dot(x, params["W"], self.compute_dtype)
        if "b" in params:
            z = z + params["b"].astype(z.dtype)
        return self.activation(z).astype(self.out_dtype), state


@implements("GatedDenseLayer")
class GatedDenseImpl(LayerImpl):
    """``(act(x Wgate) * (x Wup)) Wdown`` (SwiGLU with ``silu``): three gemms
    in the compute dtype, the gate's activation and the product on the
    gemms' outputs."""

    def init(self, rng):
        c = self.conf
        h = int(c.n_hidden)
        kg, ku, kd = jax.random.split(rng, 3)
        return {"Wgate": self._init_w(kg, (c.n_in, h), c.n_in, h),
                "Wup": self._init_w(ku, (c.n_in, h), c.n_in, h),
                "Wdown": self._init_w(kd, (h, c.n_out), h, c.n_out)}, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        x = self.maybe_dropout(x, train, rng)
        cd = self.compute_dtype
        hidden = (self.activation(_dot(x, params["Wgate"], cd))
                  * _dot(x, params["Wup"], cd))
        return _dot(hidden, params["Wdown"], cd).astype(self.out_dtype), state


@implements("ActivationLayer")
class ActivationImpl(NoParamLayerImpl):
    save_output = False

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        return self.activation(x), state


@implements("DropoutLayer")
class DropoutImpl(NoParamLayerImpl):
    """Reference ``nn/layers/DropoutLayer.java``; dropout = retain probability."""

    save_output = False

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        return self.maybe_dropout(x, train, rng), state


@implements("EmbeddingLayer")
class EmbeddingImpl(LayerImpl):
    """Reference ``nn/layers/feedforward/embedding/EmbeddingLayer.java``: input is
    a column of integer indices [b] or one-hot [b, nIn]; output [b, nOut].
    Lookup is a gather (no one-hot matmul) — efficient on TPU HBM."""

    def init(self, rng):
        c = self.conf
        params = {"W": self._init_w(rng, (c.n_in, c.n_out), c.n_in, c.n_out)}
        if getattr(c, "has_bias", True):
            params["b"] = self._init_b((c.n_out,))
        return params, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        if x.ndim == 2 and x.shape[-1] == 1:
            x = x[..., 0]
        if x.ndim == 2:  # one-hot
            idx = jnp.argmax(x, axis=-1)
        else:
            idx = x.astype(jnp.int32)
        z = jnp.take(params["W"], idx, axis=0)
        if "b" in params:
            z = z + params["b"]
        return self.activation(z).astype(self.out_dtype), state


@implements("EmbeddingSequenceLayer")
class EmbeddingSequenceImpl(LayerImpl):
    """Index sequence [b, T] (or [b, T, 1]) → [b, T, nOut]."""

    def init(self, rng):
        c = self.conf
        params = {"W": self._init_w(rng, (c.n_in, c.n_out), c.n_in, c.n_out)}
        if getattr(c, "has_bias", False):
            params["b"] = self._init_b((c.n_out,))
        return params, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        if x.ndim == 3 and x.shape[-1] == 1:
            x = x[..., 0]
        idx = x.astype(jnp.int32)
        z = jnp.take(params["W"], idx, axis=0)
        if getattr(self.conf, "scale", None) is not None:
            z = z * jnp.asarray(self.conf.scale, z.dtype)
        if "b" in params:
            z = z + params["b"]
        return self.activation(z).astype(self.out_dtype), state


@implements("AutoEncoder")
class AutoEncoderImpl(LayerImpl):
    """Denoising autoencoder (reference ``nn/layers/feedforward/autoencoder/AutoEncoder.java``).
    Supervised forward = encoder only; ``pretrain_loss`` gives the reconstruction
    objective with input corruption."""

    def init(self, rng):
        c = self.conf
        k1, k2 = jax.random.split(rng)
        params = {
            "W": self._init_w(k1, (c.n_in, c.n_out), c.n_in, c.n_out),
            "b": self._init_b((c.n_out,)),
            "vb": self._init_b((c.n_in,)),  # visible bias (reference param key "vb")
        }
        return params, {}

    def encode(self, params, x):
        return self.activation(_dot(x, params["W"], self.compute_dtype)
                               + params["b"])

    def decode(self, params, h):
        return self.activation(_dot(h, params["W"].T, self.compute_dtype)
                               + params["vb"])

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        x = self.maybe_dropout(x, train, rng)
        return self.encode(params, x).astype(self.out_dtype), state

    def pretrain_loss(self, params, x, rng):
        from ..losses import get_loss
        c = self.conf
        if c.corruption_level and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - c.corruption_level, x.shape)
            xc = jnp.where(keep, x, jnp.zeros_like(x))
        else:
            xc = x
        recon = self.decode(params, self.encode(params, xc))
        return get_loss(c.loss)(x, recon, "identity", None)


@implements("RBM")
class RBMImpl(LayerImpl):
    """Restricted Boltzmann Machine (reference
    ``nn/layers/feedforward/rbm/RBM.java:1``: ``propUp`` :322, ``propDown``
    :388, ``contrastiveDivergence`` :103). Params follow the reference's
    pretrain-param layout: ``W`` [nIn, nOut], hidden bias ``b``, visible
    bias ``vb``.

    CD-k via the free-energy surrogate (see the config docstring): for
    binary hidden units F(v) = visible_term(v) - Σ softplus(vW+b), and
    differentiating ``mean(F(v0) - F(stop_grad(v_k)))`` reproduces the
    reference's ⟨v0 h0⟩ − ⟨vk hk⟩ update EXACTLY (checked against
    hand-computed outer products in tests). Gaussian hidden uses the
    quadratic free energy (also exact: mean activation = z). Rectified
    hidden has no closed-form free energy; the softplus form is the
    standard smooth surrogate — its implied hidden statistic is
    sigmoid(z), not relu(z), so updates approximate (rather than equal)
    the reference's noisy-ReLU CD statistics."""

    _HIDDEN = ("binary", "rectified", "gaussian", "identity")
    _VISIBLE = ("binary", "gaussian", "linear", "identity")

    def init(self, rng):
        c = self.conf
        if c.hidden_unit not in self._HIDDEN:
            raise ValueError(f"RBM hidden_unit '{c.hidden_unit}' not in "
                             f"{self._HIDDEN}")
        if c.visible_unit not in self._VISIBLE:
            raise ValueError(f"RBM visible_unit '{c.visible_unit}' not in "
                             f"{self._VISIBLE}")
        params = {
            "W": self._init_w(rng, (c.n_in, c.n_out), c.n_in, c.n_out),
            "b": self._init_b((c.n_out,)),
            "vb": self._init_b((c.n_in,)),
        }
        return params, {}

    # -- conditionals ------------------------------------------------------
    def _hidden_z(self, params, v):
        return _dot(v, params["W"], self.compute_dtype) + params["b"]

    def prop_up(self, params, v):
        """Mean hidden activation given visible (reference ``propUp``)."""
        z = self._hidden_z(params, v)
        hu = self.conf.hidden_unit
        if hu == "binary":
            return jax.nn.sigmoid(z)
        if hu == "rectified":
            return jax.nn.relu(z)
        return z  # gaussian / identity: mean = z

    def prop_down(self, params, h):
        """Mean visible activation given hidden (reference ``propDown``)."""
        z = _dot(h, params["W"].T, self.compute_dtype) + params["vb"]
        if self.conf.visible_unit == "binary":
            return jax.nn.sigmoid(z)
        return z  # gaussian / linear / identity

    def _sample_h(self, params, v, key):
        hu = self.conf.hidden_unit
        z = self._hidden_z(params, v)
        if hu == "binary":
            p = jax.nn.sigmoid(z)
            return jax.random.bernoulli(key, p).astype(z.dtype)
        if hu == "rectified":
            # reference: max(0, z + N(0, sigmoid(z))) noisy rectified units
            return jax.nn.relu(z + jnp.sqrt(jax.nn.sigmoid(z))
                               * jax.random.normal(key, z.shape, z.dtype))
        if hu == "gaussian":
            return z + jax.random.normal(key, z.shape, z.dtype)
        return z

    def _sample_v(self, params, h, key):
        vu = self.conf.visible_unit
        mean = self.prop_down(params, h)
        if vu == "binary":
            return jax.random.bernoulli(key, mean).astype(mean.dtype)
        if vu == "gaussian":
            return mean + jax.random.normal(key, mean.shape, mean.dtype)
        return mean  # linear / identity: mean-field

    def free_energy(self, params, v):
        """F(v); binary-visible term −v·vb, gaussian/linear ½‖v−vb‖²."""
        z = self._hidden_z(params, v)
        if self.conf.hidden_unit in ("gaussian", "identity"):
            # quadratic form: mean hidden activation is z for both, so the
            # surrogate gradient carries the same h = z statistics prop_up
            # reports (softplus would silently optimize a binary model)
            hidden = -0.5 * jnp.sum(z * z, axis=-1)
        else:
            hidden = -jnp.sum(jax.nn.softplus(z), axis=-1)
        if self.conf.visible_unit == "binary":
            vis = -v @ params["vb"]
        else:
            diff = v - params["vb"]
            vis = 0.5 * jnp.sum(diff * diff, axis=-1)
        return vis + hidden

    def gibbs_chain(self, params, v0, rng, k):
        """k alternating (h|v, v|h) sampling steps (reference
        ``contrastiveDivergence`` :103 'k steps of gibbs sampling')."""
        v = v0
        for i in range(k):
            kh, kv, rng = jax.random.split(rng, 3)
            h = self._sample_h(params, v, kh)
            v = self._sample_v(params, h, kv)
        return v

    def forward(self, params, state, x, train=False, rng=None, mask=None,
                ctx=None):
        """Supervised forward = propUp mean activation (reference
        ``activate`` :424-426)."""
        x = self.maybe_dropout(x, train, rng)
        return self.prop_up(params, x).astype(self.out_dtype), state

    def pretrain_loss(self, params, x, rng):
        c = self.conf
        rng = jax.random.PRNGKey(0) if rng is None else rng
        vk = jax.lax.stop_gradient(
            self.gibbs_chain(params, x, rng, max(1, int(c.k))))
        loss = jnp.mean(self.free_energy(params, x)
                        - self.free_energy(params, vk))
        if c.sparsity:
            # sparsity target on mean hidden activation (reference
            # applySparsity): penalize deviation from the target rate
            mean_h = jnp.mean(self.prop_up(params, x), axis=0)
            loss = loss + jnp.sum((mean_h - c.sparsity) ** 2)
        return loss

    def reconstruction_error(self, params, x):
        """Mean-squared reconstruction v → h_mean → v_mean (monitoring
        metric; CD's surrogate loss is not itself interpretable)."""
        recon = self.prop_down(params, self.prop_up(params, x))
        return jnp.mean((recon - x) ** 2)

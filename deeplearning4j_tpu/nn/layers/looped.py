"""Looped block stack: a stack of decoder blocks applied several times with
one set of weights.

Net-new vs the 0.9.x reference (which has neither attention nor weight
sharing across depth). The blocks are built from the layers the package has
(``RMSNorm``, ``SelfAttentionLayer`` with rotary positions and no bias,
``GatedDenseLayer``), but their weights live in ONE parameter dict of stacked
leaves ``[num_blocks, ...]``, one leaf per kind of weight, and the forward is
``lax.scan`` over those leaves inside ``lax.scan`` over the passes: one block
body in the compiled program whatever the depth and the number of passes, and
AD sums each weight's gradient over the passes because every pass reads the
same leaves.

The residual stream and every norm's statistics are float32 whatever the
compute dtype; the gemms and the attention kernel take the compute dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..conf.layers import GatedDenseLayer, SelfAttentionLayer
from ..weights import host_full
from .attention import SelfAttentionImpl
from .base import LayerImpl, implements, acc_dtype, block_checkpoint
from .feedforward import GatedDenseImpl
from .normalization import rms_norm

#: a block's stacked leaves: four norm gains, the attention's and the FFN's
#: matrices (``gf``, the final norm's gain, is the twelfth key and not stacked)
ATTN_KEYS = ("Wq", "Wk", "Wv", "Wo")
FFN_KEYS = ("Wgate", "Wup", "Wdown")
GAIN_KEYS = ("g1", "g2", "g3", "g4")


def stacked_matrices(impl, subs, names, rng, n):
    """The matrices ``names`` of ``n`` blocks made of the sub-layers
    ``subs``, stacked leaf by leaf ``[n, ...]``: one draw per stacked leaf,
    in ``names``' order, by ``impl``'s weight init; every matrix of a block
    is [fan_in, fan_out], read off the sub-layers' own ``init``."""
    block = jax.eval_shape(
        lambda k: {k_: v for sub in subs for k_, v in sub.init(k)[0].items()},
        rng)
    return {k: impl._init_w(key, (n,) + block[k].shape, *block[k].shape)
            for k, key in zip(names, jax.random.split(rng, len(names)))}


@implements("LoopedBlockStack")
class LoopedBlockStackImpl(LayerImpl):
    def __init__(self, conf, gc, input_type=None):
        super().__init__(conf, gc, input_type)
        c = conf
        self.attn = SelfAttentionImpl(SelfAttentionLayer(
            n_in=c.n_in, n_out=c.n_out, num_heads=c.num_heads,
            head_dim=c.head_dim, causal=True, rope_theta=c.rope_theta,
            has_bias=False, activation="identity",
            weight_init=c.weight_init, dist=c.dist), gc)
        self.ffn = GatedDenseImpl(GatedDenseLayer(
            n_in=c.n_out, n_out=c.n_out, n_hidden=c.n_hidden,
            weight_init=c.weight_init, dist=c.dist), gc)
        #: block applications per step (passes x blocks), for the
        #: ``looped_block_applications`` gauge
        self.block_applications = int(c.num_passes) * int(c.num_blocks)

    def init(self, rng):
        c = self.conf
        n = int(c.num_blocks)
        params = stacked_matrices(self, (self.attn, self.ffn),
                                  ATTN_KEYS + FFN_KEYS, rng, n)
        for k in GAIN_KEYS:
            params[k] = host_full((n, c.n_out), 1, self.dtype)
        params["gf"] = host_full((c.n_out,), 1, self.dtype)
        return params, {}

    def _norm(self, x, gain):
        return rms_norm(x, gain, self.conf.eps, acc_dtype(self.compute_dtype))

    def block(self, p, x, mask=None):
        """One block on the float32 stream ``x`` [b, T, d] with one block's
        leaves ``p``."""
        cd = self.compute_dtype
        with jax.named_scope("attn"):
            o, _ = self.attn.forward({k: p[k] for k in ATTN_KEYS}, {},
                                     self._norm(x, p["g1"]).astype(cd),
                                     mask=mask)
            a = x + self._norm(o, p["g2"])
        with jax.named_scope("ffn"):
            f, _ = self.ffn.forward({k: p[k] for k in FFN_KEYS}, {},
                                    self._norm(a, p["g3"]).astype(cd))
            return a + self._norm(f, p["g4"])

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        c = self.conf
        x = self.maybe_dropout(x, train, rng).astype(
            acc_dtype(self.compute_dtype))
        stacked = {k: params[k] for k in ATTN_KEYS + FFN_KEYS + GAIN_KEYS}
        block = lambda p, u: self.block(p, u, mask)
        if train:
            block = block_checkpoint(block)

        def one_pass(h, _):
            with jax.named_scope("blocks"):
                u, _ = jax.lax.scan(lambda u, p: (block(p, u), None), h,
                                    stacked)
            with jax.named_scope("final_norm"):
                h = self._norm(u, params["gf"])
            return h, h

        _, states = jax.lax.scan(one_pass, x, None, length=int(c.num_passes))
        return states, state

    def regularization(self, params):
        # the matrices only: norm gains are free of l1/l2, like BN's
        return super().regularization({k: params[k]
                                       for k in ATTN_KEYS + FFN_KEYS})

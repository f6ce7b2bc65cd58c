"""Looped block stack: a stack of decoder blocks applied several times with
one set of weights.

Net-new vs the 0.9.x reference (which has neither attention nor weight
sharing across depth). The blocks are built from the layers the package has
(``RMSNorm``, ``SelfAttentionLayer`` with rotary positions and no bias,
``GatedDenseLayer``), but their weights live in ONE parameter dict of stacked
leaves ``[num_blocks, ...]``, one leaf per kind of weight, and the forward is
ONE ``lax.scan`` over the ``num_passes x num_blocks`` block applications:
application ``n`` takes block ``n % num_blocks``'s leaves, and the last block
of a pass is followed by the final norm. One block body in the compiled
program whatever the depth and the number of passes, one stack of kept values
for the backward sweep (a pass's output is read off it: it is what the next
pass's first application took), and each weight's gradient summed over the
passes where it stands (``_take``). Training, an application keeps its input,
the flash kernels' residuals and the FFN's output, which the block's last
norm reads (``base.block_checkpoint``), and recomputes the rest backward.

The residual stream and every norm's statistics are float32 whatever the
compute dtype; the gemms and the attention kernel take the compute dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..conf.layers import GatedDenseLayer, SelfAttentionLayer
from ...monitor import get_registry
from ..weights import host_full
from .attention import SelfAttentionImpl
from .base import (LayerImpl, implements, acc_dtype, block_checkpoint,
                   NORM_IN)
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


def _row(leaf, i):
    return jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)


@jax.custom_vjp
def _take(leaves, grads, i):
    """Block ``i``'s row of every stacked leaf, and ``grads`` as it came.
    Backward, the rows' cotangent is added to row ``i`` of ``grads``'
    cotangent and nothing is handed to ``leaves``: carried through the scan
    over the applications, ``grads`` sums every leaf's gradient over the
    passes one row at a time, in place. AD's own transpose of the index
    writes each application's rows into a stack of zeros and adds whole
    stacks."""
    return jax.tree_util.tree_map(lambda v: _row(v, i), leaves), grads


def _take_fwd(leaves, grads, i):
    return _take(leaves, grads, i), i


def _take_bwd(i, cotangents):
    rows, grads = cotangents
    return None, jax.tree_util.tree_map(
        lambda g, r: jax.lax.dynamic_update_index_in_dim(
            g, _row(g, i) + r.astype(g.dtype), i, 0), grads, rows), None


_take.defvjp(_take_fwd, _take_bwd)


@jax.custom_vjp
def _stand_in(zeros, values):
    """``zeros`` where ``values`` stood, with ``values``' cotangent. Twice in
    the scan over the applications: for the stacked leaves, so that what
    ``_take`` has summed by the end of the backward sweep is their gradient;
    and in every application for the carried ``grads``, which is zeros too
    but not a constant of the scan: what a checkpointed application takes
    in it keeps for its backward sweep, a constant once, a carried value
    once an application."""
    return zeros


_stand_in.defvjp(lambda zeros, values: (zeros, None),
                 lambda _, cotangent: (None, cotangent))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _norm_if(norm, flag, u, gain):
    """``norm(u, gain)`` where ``flag`` holds, else ``u``. With a rule of its
    own because AD through a ``cond`` hands the backward sweep each branch's
    residuals, zeros from the branch that did not run: three arrays like
    ``u``, filled and added an application."""
    return jax.lax.cond(flag, norm, lambda u, gain: u, u, gain)


def _norm_if_fwd(norm, flag, u, gain):
    return _norm_if(norm, flag, u, gain), (flag, u, gain)


def _norm_if_bwd(norm, residuals, cotangent):
    flag, u, gain = residuals
    return (None,) + jax.lax.cond(
        flag, lambda c: jax.vjp(norm, u, gain)[1](c),
        lambda c: (c, jnp.zeros_like(gain)), cotangent)


_norm_if.defvjp(_norm_if_fwd, _norm_if_bwd)


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
        leaves ``p``. A sandwich block: each sub-layer's output passes a norm
        of its own before it joins the stream, and that norm's backward
        reads the output. The FFN's carries the checkpoint's name for such a
        value, so the down-projection is not run again backward for the norm
        alone; the attention's does not (kept too, the compiler lays the
        backward sweep's stream out anew and the head after it: PERF.md
        section 7)."""
        cd = self.compute_dtype
        with jax.named_scope("attn"):
            o, _ = self.attn.forward({k: p[k] for k in ATTN_KEYS}, {},
                                     self._norm(x, p["g1"]).astype(cd),
                                     mask=mask)
            a = x + self._norm(o, p["g2"])
        with jax.named_scope("ffn"):
            f, _ = self.ffn.forward({k: p[k] for k in FFN_KEYS}, {},
                                    self._norm(a, p["g3"]).astype(cd))
            return a + self._norm(checkpoint_name(f, NORM_IN), p["g4"])

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        c = self.conf
        blocks, passes = int(c.num_blocks), int(c.num_passes)
        get_registry().gauge(
            "looped_scan_steps",
            "Steps of the one scan a looped block stack runs as (passes x "
            "blocks: one block application each), set when the stack's "
            "forward is traced",
            layer=str(getattr(self, "index", ""))).set(passes * blocks)
        x = self.maybe_dropout(x, train, rng).astype(
            acc_dtype(self.compute_dtype))
        if train:
            # ``block`` names one value like the stream an application, in
            # the compute dtype: the FFN's output
            get_registry().gauge(
                "looped_kept_norm_input_bytes",
                "Bytes of post-norm inputs (sub-layer outputs a block named "
                "for its checkpoint) that one training step of a looped "
                "block stack keeps for its backward sweep, set when the "
                "stack's forward is traced",
                layer=str(getattr(self, "index", ""))).set(
                    passes * blocks * x.size * self.compute_dtype.itemsize)
        stacked = {k: params[k] for k in ATTN_KEYS + FFN_KEYS + GAIN_KEYS}
        # the leaves' values reach a block as constants of the loop; their
        # gradients travel as the cotangent of ``grads``, which no block reads
        leaves = jax.lax.stop_gradient(stacked)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, leaves)

        def apply(grads, i, ends_pass, u):
            with jax.named_scope("blocks"):
                p, grads = _take(leaves, grads, i)
                u = self.block(p, u, mask)
            with jax.named_scope("final_norm"):
                return _norm_if(self._norm, ends_pass, u, params["gf"]), grads

        if train:
            apply = block_checkpoint(apply)

        def application(carry, n):
            u, states, grads = carry
            i = n % blocks
            v, grads = apply(_stand_in(zeros, grads), i, i == blocks - 1, u)
            if not train:
                # nothing is kept: every application writes its pass's row,
                # the pass's last one, which ends in the final norm, last
                states = jax.lax.dynamic_update_index_in_dim(
                    states, v, n // blocks, 0)
            return (v, states, grads), (u if train else None)

        states = None if train else jnp.zeros((passes,) + x.shape, x.dtype)
        (u, states, _), taken = jax.lax.scan(
            application, (x, states, _stand_in(zeros, stacked)),
            jnp.arange(passes * blocks, dtype=jnp.int32))
        if train:
            # a pass hands its output to the next one's first block, and
            # what an application took is kept for the backward sweep as it
            # is: the kept stack holds every pass's output but the last
            firsts = taken.reshape((passes, blocks) + u.shape)[1:, 0]
            states = jnp.concatenate([firsts, u[None]])
        return states, state

    def regularization(self, params):
        # the matrices only: norm gains are free of l1/l2, like BN's
        return super().regularization({k: params[k]
                                       for k in ATTN_KEYS + FFN_KEYS})

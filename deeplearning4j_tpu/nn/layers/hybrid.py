"""Hybrid block stack: pre-normed decoder blocks of two kinds, state-space
(``Mamba2Layer``) and grouped-query attention without positions, each with a
gated MLP, in the order ``layer_types`` gives.

Net-new vs the 0.9.x reference, like :mod:`.looped`, and built the same way
from the layers the package has: every run of like blocks keeps its weights in
stacked leaves ``[n, ...]`` (keys ``r<run>.<leaf>``) and is one ``lax.scan``
over them, so the compiled program holds one body per run whatever the depth;
training checkpoints each block (``base.block_checkpoint``: keeps its input
and what an attention block's flash kernels read backward, recomputes the
rest). The residual stream and every norm's statistics are float32
whatever the compute dtype.
"""
from __future__ import annotations

import itertools

import jax

from ..conf.layers import GatedDenseLayer, Mamba2Layer, SelfAttentionLayer
from ..weights import host_full
from .attention import SelfAttentionImpl
from .base import LayerImpl, implements, acc_dtype, block_checkpoint
from .feedforward import GatedDenseImpl
from .looped import ATTN_KEYS, FFN_KEYS, stacked_matrices
from .mamba import Mamba2Impl
from .normalization import rms_norm

KINDS = ("mamba", "attention")


@implements("HybridBlockStack")
class HybridBlockStackImpl(LayerImpl):
    def __init__(self, conf, gc, input_type=None):
        super().__init__(conf, gc, input_type)
        c = conf
        unknown = sorted(set(c.layer_types or ()) - set(KINDS))
        if unknown or not c.layer_types:
            raise ValueError(f"HybridBlockStack: layer_types holds {KINDS}, "
                             f"one entry a block; got {unknown or 'none'}")
        init = dict(weight_init=c.weight_init, dist=c.dist)
        self.mixers = {
            "attention": SelfAttentionImpl(SelfAttentionLayer(
                n_in=c.n_in, n_out=c.n_out, num_heads=c.num_heads,
                num_kv_heads=c.num_kv_heads, head_dim=c.head_dim,
                attention_scale=c.attention_scale, causal=True,
                rope_theta=None, has_bias=False, activation="identity",
                **init), gc),
            "mamba": Mamba2Impl(Mamba2Layer(
                n_in=c.n_in, n_out=c.n_out, num_heads=c.mamba_heads,
                head_dim=c.mamba_head_dim, state_size=c.mamba_state_size,
                conv_size=c.mamba_conv_size, chunk_size=c.mamba_chunk_size,
                eps=c.eps, **init), gc)}
        self.ffn = GatedDenseImpl(GatedDenseLayer(
            n_in=c.n_out, n_out=c.n_out, n_hidden=c.n_hidden, **init), gc)
        #: (kind, blocks) of every run of like blocks, in order
        self.runs = [(kind, len(list(group)))
                     for kind, group in itertools.groupby(c.layer_types)]
        #: blocks of each kind, for the ``hybrid_blocks`` gauge
        self.block_kinds = {kind: list(c.layer_types).count(kind)
                            for kind in KINDS if kind in c.layer_types}

    def init(self, rng):
        c = self.conf
        params = {}
        for i, ((kind, n), key) in enumerate(zip(
                self.runs, jax.random.split(rng, len(self.runs)))):
            k_mixer, k_ffn = jax.random.split(key)
            run = (stacked_matrices(self, (self.mixers[kind],), ATTN_KEYS,
                                    k_mixer, n) if kind == "attention"
                   else self.mixers[kind].init(k_mixer, lead=(n,))[0])
            run.update(stacked_matrices(self, (self.ffn,), FFN_KEYS, k_ffn, n))
            for g in ("g1", "g2"):
                run[g] = host_full((n, c.n_out), 1, self.dtype)
            params.update({f"r{i}.{k}": v for k, v in run.items()})
        params["gf"] = host_full((c.n_out,), 1, self.dtype)
        return params, {}

    def _norm(self, x, gain):
        return rms_norm(x, gain, self.conf.eps, acc_dtype(self.compute_dtype))

    def block(self, kind, p, x, mask=None):
        """One block of ``kind`` on the float32 stream ``x`` [b, T, d] with
        one block's leaves ``p``."""
        cd, r = self.compute_dtype, self.conf.residual_multiplier
        # Every matrix is multiplied by a one that is read off the stream, so
        # that its cast to the compute dtype (the sub-layers' own) belongs
        # to this block's turn of the scan. A cast of a bare slice of a
        # stacked leaf is loop-invariant to the TPU compiler: it casts the
        # whole stacks once, before the scans, and keeps that second copy of
        # every matrix (2 bytes a parameter) alive through the step; with
        # the donated parameters that cost 2.5 GB of the chip at the
        # published widths (8.46 -> 5.99 GB of temporaries, compiled for a
        # described v5e). ``lax.optimization_barrier`` does not hold it: the
        # compiler drops the barrier first.
        one = jax.lax.stop_gradient(x[0, 0, 0]) * 0 + 1
        p = {k: v * one.astype(v.dtype) if v.ndim == 2 else v
             for k, v in p.items()}
        mixer = {k: v for k, v in p.items()
                 if k not in FFN_KEYS + ("g1", "g2")}
        with jax.named_scope("attn" if kind == "attention" else "ssm"):
            m, _ = self.mixers[kind].forward(
                mixer, {}, self._norm(x, p["g1"]).astype(cd), mask=mask)
            u = x + r * m.astype(x.dtype)
        with jax.named_scope("ffn"):
            f, _ = self.ffn.forward({k: p[k] for k in FFN_KEYS}, {},
                                    self._norm(u, p["g2"]).astype(cd))
            return u + r * f.astype(x.dtype)

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        if ctx is not None and ctx.get("rnn_state_in") is not None:
            raise ValueError("HybridBlockStack has no streaming state: "
                             "rnn_time_step and truncated BPTT are not "
                             "supported")
        x = self.maybe_dropout(x, train, rng).astype(
            acc_dtype(self.compute_dtype))
        self.mixers["mamba"].index = getattr(self, "index", "")
        with jax.named_scope("blocks"):
            for i, (kind, _) in enumerate(self.runs):
                stacked = {k.partition(".")[2]: v for k, v in params.items()
                           if k.startswith(f"r{i}.")}
                block = lambda p, u, kind=kind: self.block(kind, p, u, mask)
                if train:
                    block = block_checkpoint(block)
                x, _ = jax.lax.scan(
                    lambda u, p, block=block: (block(p, u), None), x, stacked)
        with jax.named_scope("final_norm"):
            return self._norm(x, params["gf"]), state

    def regularization(self, params):
        # the matrices only: gains, biases and the scan's vectors are free
        matrices = ATTN_KEYS + FFN_KEYS + ("W_in", "conv_W", "W_out")
        return super().regularization(
            {k: v for k, v in params.items()
             if k.partition(".")[2] in matrices})

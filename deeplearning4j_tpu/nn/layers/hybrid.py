"""Hybrid block stack: pre-normed decoder blocks whose mixer is one of five
kinds, state-space (``Mamba2Layer``), grouped-query attention (rotary or
without positions), the same in a sliding window, a gated delta rule
(``KimiDeltaAttentionLayer``) or latent attention (``SelfAttentionLayer`` in
its latent layout), each with a gated MLP or routed experts
(``RoutedExpertsLayer``), in the order ``layer_types`` and ``ffn_types``
give.

Net-new vs the 0.9.x reference, like :mod:`.looped`, and built the same way
from the layers the package has: every run of like blocks keeps its weights in
stacked leaves ``[n, ...]`` (keys ``r<run>.<leaf>``) and is one ``lax.scan``
over them (a run is a stretch of blocks alike in mixer and feed-forward), so
the compiled program holds one body per run whatever the depth;
training checkpoints each block (``base.block_checkpoint``: keeps its input
and what an attention block's flash kernels read backward, recomputes the
rest). The residual stream and every norm's statistics are float32
whatever the compute dtype.
"""
from __future__ import annotations

import itertools

import jax

from ..conf.layers import (GatedDenseLayer, KimiDeltaAttentionLayer,
                           Mamba2Layer, RoutedExpertsLayer, SelfAttentionLayer)
from ...monitor import get_registry
from ..weights import host_full
from .attention import SelfAttentionImpl
from .base import LayerImpl, implements, acc_dtype, block_checkpoint
from .feedforward import GatedDenseImpl
from .kda import KimiDeltaAttentionImpl
from .looped import ATTN_KEYS, FFN_KEYS, stacked_matrices
from .mamba import Mamba2Impl
from .moe import RoutedExpertsImpl
from .normalization import rms_norm

KINDS = ("mamba", "attention", "kda", "mla", "window")
FFN_KINDS = ("dense", "experts")
#: the latent layout's matrices (``gc``, its norm's gain, is no matrix)
MLA_KEYS = ("Wq", "Wkv_a", "Wkv_b", "Wo")
#: the scope a block's mixer half runs under, by kind
MIXER_SCOPE = {"mamba": "ssm", "attention": "attn", "kda": "kda", "mla": "mla",
               "window": "swa"}


@implements("HybridBlockStack")
class HybridBlockStackImpl(LayerImpl):
    def __init__(self, conf, gc, input_type=None):
        super().__init__(conf, gc, input_type)
        c = conf
        unknown = sorted(set(c.layer_types or ()) - set(KINDS))
        if unknown or not c.layer_types:
            raise ValueError(f"HybridBlockStack: layer_types holds {KINDS}, "
                             f"one entry a block; got {unknown or 'none'}")
        ffn_types = list(c.ffn_types or ["dense"] * len(c.layer_types))
        if (len(ffn_types) != len(c.layer_types)
                or set(ffn_types) - set(FFN_KINDS)):
            raise ValueError(f"HybridBlockStack: ffn_types holds {FFN_KINDS}, "
                             f"one entry a block; got {ffn_types}")
        init = dict(weight_init=c.weight_init, dist=c.dist)
        attention = dict(n_in=c.n_in, n_out=c.n_out, num_heads=c.num_heads,
                         attention_scale=c.attention_scale, causal=True,
                         rope_theta=None, has_bias=False,
                         activation="identity", **init)
        grouped = dict(attention, num_kv_heads=c.num_kv_heads,
                       head_dim=c.head_dim)
        self.mixers = {
            "attention": SelfAttentionImpl(SelfAttentionLayer(**dict(
                grouped, rope_theta=c.rope_theta,
                rope_scaling=c.rope_scaling)), gc),
            "mamba": Mamba2Impl(Mamba2Layer(
                n_in=c.n_in, n_out=c.n_out, num_heads=c.mamba_heads,
                head_dim=c.mamba_head_dim, state_size=c.mamba_state_size,
                conv_size=c.mamba_conv_size, chunk_size=c.mamba_chunk_size,
                eps=c.eps, **init), gc)}
        if "window" in c.layer_types:
            if not c.window:
                raise ValueError("HybridBlockStack: window blocks need a "
                                 "window")
            self.mixers["window"] = SelfAttentionImpl(SelfAttentionLayer(
                **dict(grouped, rope_theta=c.rope_theta,
                       window=c.window)), gc)
        if "kda" in c.layer_types:
            self.mixers["kda"] = KimiDeltaAttentionImpl(
                KimiDeltaAttentionLayer(
                    n_in=c.n_in, n_out=c.n_out, num_heads=c.kda_heads,
                    head_dim=c.kda_head_dim, conv_size=c.kda_conv_size,
                    chunk_size=c.kda_chunk_size, eps=c.eps, **init), gc)
        if "mla" in c.layer_types:
            self.mixers["mla"] = SelfAttentionImpl(SelfAttentionLayer(
                kv_latent_rank=c.kv_latent_rank,
                qk_nope_head_dim=c.qk_nope_head_dim,
                qk_rope_head_dim=c.qk_rope_head_dim,
                v_head_dim=c.v_head_dim, latent_norm_eps=c.eps,
                **attention), gc)
        self.ffn = GatedDenseImpl(GatedDenseLayer(
            n_in=c.n_out, n_out=c.n_out, n_hidden=c.n_hidden, **init), gc)
        if "experts" in ffn_types:
            self.experts = RoutedExpertsImpl(RoutedExpertsLayer(
                n_in=c.n_out, n_out=c.n_out, num_experts=c.num_experts,
                experts_held=c.experts_held, top_k=c.experts_per_token,
                n_hidden=c.expert_hidden, shared_hidden=c.shared_hidden,
                renormalize=c.renormalize,
                routed_scaling_factor=c.routed_scaling_factor,
                score=c.expert_score, **init), gc)
        groups = [(pair, len(list(group))) for pair, group in
                  itertools.groupby(zip(c.layer_types, ffn_types))]
        #: (mixer, blocks) of every run of like blocks, in order
        self.runs = [(mixer, n) for (mixer, _), n in groups]
        #: every run's feed-forward kind, beside ``runs``
        self.run_ffns = [ffn for (_, ffn), _ in groups]
        #: blocks of each kind, for the ``hybrid_blocks`` gauge
        self.block_kinds = {kind: list(c.layer_types).count(kind)
                            for kind in KINDS if kind in c.layer_types}

    def init(self, rng):
        c = self.conf
        params, state = {}, {}
        for i, ((kind, n), ffn, key) in enumerate(zip(
                self.runs, self.run_ffns,
                jax.random.split(rng, len(self.runs)))):
            k_mixer, k_ffn = jax.random.split(key)
            if kind in ("attention", "mla", "window"):
                run = stacked_matrices(
                    self, (self.mixers[kind],),
                    MLA_KEYS if kind == "mla" else ATTN_KEYS, k_mixer, n)
            else:
                run = self.mixers[kind].init(k_mixer, lead=(n,))[0]
            if kind == "mla":
                run["gc"] = host_full((n, c.kv_latent_rank), 1, self.dtype)
            if ffn == "dense":
                run.update(stacked_matrices(self, (self.ffn,), FFN_KEYS,
                                            k_ffn, n))
            else:
                held, kept = self.experts.init(k_ffn, lead=(n,))
                run.update(held)
                state.update({f"r{i}.{k}": v for k, v in kept.items()})
            for g in ("g1", "g2"):
                run[g] = host_full((n, c.n_out), 1, self.dtype)
            params.update({f"r{i}.{k}": v for k, v in run.items()})
        params["gf"] = host_full((c.n_out,), 1, self.dtype)
        return params, state

    def _norm(self, x, gain):
        return rms_norm(x, gain, self.conf.eps, acc_dtype(self.compute_dtype))

    def block(self, kind, p, x, mask=None, ffn="dense", kept=None):
        """One block of mixer ``kind`` and feed-forward ``ffn`` on the
        float32 stream ``x`` [b, T, d] with one block's leaves ``p`` (and
        ``kept``, its leaves of the stack's state: the experts' bias)."""
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
        p = {k: v * one.astype(v.dtype) if v.ndim >= 2 else v
             for k, v in p.items()}
        ffn_keys = FFN_KEYS if ffn == "dense" else (
            ("Wr",) + self.experts.EXPERT_KEYS + self.experts.SHARED_KEYS)
        mixer = {k: v for k, v in p.items()
                 if k not in ffn_keys + ("g1", "g2")}
        with jax.named_scope(MIXER_SCOPE[kind]):
            m, _ = self.mixers[kind].forward(
                mixer, {}, self._norm(x, p["g1"]).astype(cd), mask=mask)
            u = x + r * m.astype(x.dtype)
        if ffn == "experts":
            # the router reads the normed stream as it is, float32: a choice
            # of experts is a comparison of scores
            with jax.named_scope("moe"):
                f, _ = self.experts.forward(
                    {k: p[k] for k in ffn_keys if k in p}, kept,
                    self._norm(u, p["g2"]))
                return u + r * f.astype(x.dtype)
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
        for sub in (*self.mixers.values(), getattr(self, "experts", None)):
            if sub is not None:
                sub.index = getattr(self, "index", "")
        if "window" in self.mixers:
            get_registry().gauge(
                "attention_window",
                "Keys a query of a hybrid stack's sliding-window blocks sees "
                "(itself among them), set when the stack is traced",
                layer=str(getattr(self, "index", ""))).set(self.conf.window)
        of_run = lambda leaves, i: {
            k.partition(".")[2]: v for k, v in leaves.items()
            if k.startswith(f"r{i}.")}
        with jax.named_scope("blocks"):
            for i, ((kind, _), ffn) in enumerate(zip(self.runs,
                                                     self.run_ffns)):
                block = lambda pk, u, kind=kind, ffn=ffn: self.block(
                    kind, pk[0], u, mask, ffn, pk[1])
                stacked = (of_run(params, i), of_run(state, i))
                if train:
                    block = block_checkpoint(block)
                x, _ = jax.lax.scan(
                    lambda u, p, block=block: (block(p, u), None), x, stacked)
        with jax.named_scope("final_norm"):
            return self._norm(x, params["gf"]), state

    def regularization(self, params):
        # the matrices only: gains, biases and the scan's vectors are free
        matrices = (ATTN_KEYS + FFN_KEYS + ("W_in", "conv_W", "W_out")
                    + MLA_KEYS + KimiDeltaAttentionImpl.MATRICES + ("Wr",)
                    + RoutedExpertsImpl.EXPERT_KEYS
                    + RoutedExpertsImpl.SHARED_KEYS)
        return super().regularization(
            {k: v for k, v in params.items()
             if k.partition(".")[2] in matrices})

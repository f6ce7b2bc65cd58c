"""Operations and bytes one training step of ``kimi_linear_48b_a3b`` needs,
from its shapes (``builder_kwargs``: the vocabulary slice V, hidden d, the
dense MLP's width F, the KDA layers' H heads of K channels with a convolution
of width C, latent attention's h heads with a latent of rank r, key parts N
and R and value heads of Dv, the experts' width f with e held of E published
and k chosen a token, and which of the published layers 1 .. ``layers`` are
of which kind) and the cell's batch b and length T; tokens = b·T.

Whole step, per token: every layer's matrices once (a KDA mixer's
2·(4·d·H·K + 2·(d·K + K·H·K) + d·H), latent attention's
2·(d·h·(N + R) + d·(r + R) + r·h·(N + Dv) + h·Dv·d), the dense MLP's
2·3·d·F, an expert layer's router 2·d·E, its shared expert 2·3·d·f and the
routed experts at their expectation under uniform routing, k·e/E experts a
token, 2·3·d·f each), causal attention at half of the full
2·T·h·(N + R) + 2·T·h·Dv, for the delta rule what the recurrence itself
needs, 7·H·K·K (the decay of the state, a multiply; ``S^T k``, the update
``k (x) u`` and the read-out ``S^T q``, a multiply and an add each per state
element), not the extra products the chunked form spends, and the untied
head over the slice once (2·d·V); forward once and backward twice, so three
times that. The embedding is a gather; the norms, the convolutions (2·C a
channel), the gates, the decays, the routing and the loss are left out: a
floor. Nothing recomputed counts.

Bytes, a floor no program can undercut: what the step is handed and hands
back. Every parameter and its two Adam moments (float32) are read once and
written once, 24 bytes a parameter and step; the int32 ids and labels are
read once. Activations are not counted.

The flash kernels (``ops/flash_attention.py``) do attention's products only:
per head and latent-attention layer a causal [T, T, N + R] and a causal
[T, T, Dv] product forward (half of 2·T·T·D each) and, backward, the scores
and dq and dk at N + R and dp and dv at Dv, the scores and dp in both
backward kernels: 2 + 5 products at equal sizes, here (N + R) + Dv forward
and 4·(N + R) + 3·Dv backward. ``kda_work`` is the delta rule's needed part
alone, the yardstick of ``kda_rule_roofline``.
"""
from __future__ import annotations


def _sizes(config, traffic=None):
    kw = config["builder_kwargs"]
    order = range(1, int(kw["layers"]) + 1)
    kda = sum(i in set(kw["kda_layers"]) for i in order)
    dense = sum(i <= int(kw["first_k_dense"]) for i in order)
    traffic = traffic or {"batch": 0, "seq_len": 0}
    return dict(
        v=int(kw["vocab"]), d=int(kw["hidden"]), F=int(kw["intermediate"]),
        h=int(kw["heads"]), r=int(kw["kv_lora_rank"]),
        N=int(kw["qk_nope_head_dim"]), R=int(kw["qk_rope_head_dim"]),
        Dv=int(kw["v_head_dim"]), H=int(kw["kda_heads"]),
        K=int(kw["kda_head_dim"]), C=int(kw["kda_conv"]),
        e=int(kw["experts"]), E=int(kw["experts_published"]),
        k=int(kw["experts_per_token"]), f=int(kw["moe_intermediate"]),
        fs=int(kw["shared_experts"]) * int(kw["moe_intermediate"]),
        kda=kda, mla=len(order) - kda, dense=dense,
        experts=len(order) - dense,
        b=int(traffic["batch"]), t=int(traffic["seq_len"]))


def kda_matrices(d, H, K, **_):
    """A KDA mixer's projections: q, k, v, out; the decay's and the output
    gate's two low-rank pairs; beta's."""
    return 4 * d * H * K + 2 * (d * K + K * H * K) + d * H


def mla_matrices(d, h, r, N, R, Dv, **_):
    return d * h * (N + R) + d * (r + R) + r * h * (N + Dv) + h * Dv * d


def params(config):
    s = _sizes(config)
    kda = (kda_matrices(**s) + 3 * s["H"] * s["K"] * s["C"] + s["H"]
           + s["H"] * s["K"] + s["K"])
    mla = mla_matrices(**s) + s["r"]
    dense = 3 * s["d"] * s["F"]
    experts = (s["d"] * s["E"] + 3 * s["d"] * s["fs"]
               + s["e"] * 3 * s["d"] * s["f"])
    layers = s["kda"] + s["mla"]
    return (s["kda"] * kda + s["mla"] * mla + s["dense"] * dense
            + s["experts"] * experts + layers * 2 * s["d"]
            + 2 * s["v"] * s["d"] + s["d"])


def rule_flops_per_token(H, K, **_):
    """What the delta rule needs of one layer for one token, forward."""
    return 7 * H * K * K


def expert_products_per_token(d, f, fs, k, e, E, **_):
    """The shared expert's and the routed experts' products of one layer for
    one token, forward: the routed at ``k e / E`` experts a token."""
    return 2 * 3 * d * fs + (k * e / E) * 2 * 3 * d * f


def step_work(config, traffic):
    s = _sizes(config, traffic)
    per_token = (
        s["kda"] * (2 * kda_matrices(**s) + rule_flops_per_token(**s))
        + s["mla"] * (2 * mla_matrices(**s)
                      + s["t"] * s["h"] * (s["N"] + s["R"] + s["Dv"]))
        + s["dense"] * 2 * 3 * s["d"] * s["F"]
        + s["experts"] * (2 * s["d"] * s["E"]
                          + expert_products_per_token(**s))
        + 2 * s["d"] * s["v"])
    return {"flops": int(3 * s["b"] * s["t"] * per_token),
            "bytes": 24 * params(config) + 2 * s["b"] * s["t"] * 4}


def kernel_work(config, traffic):
    """What the flash-attention kernels of one step need."""
    s = _sizes(config, traffic)
    qk, dv = s["N"] + s["R"], s["Dv"]
    return {"flops": ((qk + dv) + (4 * qk + 3 * dv)) * s["t"] * s["t"]
            * s["b"] * s["h"] * s["mla"],
            "bytes": 0,                 # none has to cross HBM: see above
            "calls": 3 * s["mla"]}      # flash_fwd, flash_dq, flash_dkv


def kda_work(config, traffic):
    """What the delta rules of one step need: forward once and backward
    twice, in every KDA layer."""
    s = _sizes(config, traffic)
    return {"flops": 3 * s["b"] * s["t"] * s["kda"]
            * rule_flops_per_token(**s),
            "bytes": 0}                 # its operands are values of the step

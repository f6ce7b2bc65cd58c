"""Operations and bytes one training step of ``granite_4_0_h_micro`` needs,
from its shapes (``builder_kwargs``: the vocabulary slice V, hidden d, MLP
width F, attention's h query heads over g key-value heads of D, the
state-space layers' H heads of P with state N and a convolution of width K,
and the first ``layers`` entries of ``layer_types``) and the cell's batch b
and length T; tokens = b·T.

Whole step, per token: every layer's matrices once (a state-space layer's two
projections 2·(d·(2·H·P + 2·N + H) + H·P·d), an attention layer's four
2·(2·d·h·D + 2·d·g·D), the gated MLP's three 2·3·d·F), causal attention at
half of the full 4·T·h·D (the score and the value product over the visible
half), for the scan what the recurrence itself needs, 4·H·P·N (the state's
update ``a S + (dt x) (x) B`` and its read-out ``S C``, a multiply and an add
each per state element), not the extra products the chunked form spends, and
the tied head over the slice once (2·d·V); forward once and backward twice,
so three times that. The embedding is a gather; the norms, the convolution
(2·K a channel), the gates, the decays and the loss are left out: a floor.
Nothing recomputed counts: the stack's block checkpoint adds about a forward
pass of the blocks' work that no algorithm needs.

Bytes, a floor no program can undercut: what the step is handed and hands
back. Every parameter and its two Adam moments (float32) are read once and
written once, 24 bytes a parameter and step, the tied leaf once; the int32
ids and labels are read once. Activations are not counted: a step's could in
principle stay on the chip.

The flash kernels (``ops/flash_attention.py``) do attention's products only,
at h query heads (the grouped keys and values reach them repeated): per
query head and attention layer two causal [T, T, D] products forward and
five backward, as ``opcount/ouro_2_6b.py`` counts them, and no byte of
theirs has to cross HBM. ``ssd_work`` is the scan's needed part alone, the
yardstick of ``ssd_roofline``.
"""
from __future__ import annotations


def _sizes(config, traffic=None):
    kw = config["builder_kwargs"]
    types = list(kw["layer_types"][:int(kw["layers"])])
    traffic = traffic or {"batch": 0, "seq_len": 0}
    return dict(
        v=int(kw["vocab"]), d=int(kw["hidden"]), f=int(kw["intermediate"]),
        h=int(kw["heads"]), g=int(kw["kv_heads"]), hd=int(kw["head_dim"]),
        H=int(kw["mamba_heads"]), P=int(kw["mamba_head_dim"]),
        N=int(kw["mamba_state"]), K=int(kw["mamba_conv"]),
        mamba=types.count("mamba"), attention=types.count("attention"),
        b=int(traffic["batch"]), t=int(traffic["seq_len"]))


def mamba_matrices(d, H, P, N, **_):
    """A state-space mixer's two projections."""
    return d * (2 * H * P + 2 * N + H) + H * P * d


def attention_matrices(d, h, g, hd, **_):
    return 2 * d * h * hd + 2 * d * g * hd


def params(config):
    s = _sizes(config)
    mlp_and_gains = 3 * s["d"] * s["f"] + 2 * s["d"]
    conv = (s["H"] * s["P"] + 2 * s["N"]) * (s["K"] + 1)
    mamba = (mamba_matrices(**s) + conv + 3 * s["H"] + s["H"] * s["P"]
             + mlp_and_gains)
    attention = attention_matrices(**s) + mlp_and_gains
    return (s["mamba"] * mamba + s["attention"] * attention
            + s["v"] * s["d"] + s["d"])


def scan_flops_per_token(H, P, N, **_):
    """What the recurrence needs of one layer for one token, forward."""
    return 4 * H * P * N


def step_work(config, traffic):
    s = _sizes(config, traffic)
    mlp = 2 * 3 * s["d"] * s["f"]
    per_token = (s["mamba"] * (2 * mamba_matrices(**s)
                               + scan_flops_per_token(**s) + mlp)
                 + s["attention"] * (2 * attention_matrices(**s)
                                     + 2 * s["t"] * s["h"] * s["hd"] + mlp)
                 + 2 * s["d"] * s["v"])
    return {"flops": 3 * s["b"] * s["t"] * per_token,
            "bytes": 24 * params(config) + 2 * s["b"] * s["t"] * 4}


def kernel_work(config, traffic):
    """What the flash-attention kernels of one step need."""
    s = _sizes(config, traffic)
    return {"flops": (2 + 5) * s["t"] * s["t"] * s["hd"] * s["b"] * s["h"]
            * s["attention"],
            "bytes": 0,                 # none has to cross HBM: see above
            "calls": 3 * s["attention"]}    # flash_fwd, flash_dq, flash_dkv


def ssd_work(config, traffic):
    """What the state-space recurrences of one step need: forward once and
    backward twice, in every state-space layer."""
    s = _sizes(config, traffic)
    return {"flops": 3 * s["b"] * s["t"] * s["mamba"]
            * scan_flops_per_token(**s),
            "bytes": 0}                 # its operands are values of the step

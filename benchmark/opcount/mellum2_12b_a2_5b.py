"""Operations and bytes one training step of ``mellum2_12b_a2_5b`` needs,
from its shapes (``builder_kwargs``: the vocabulary slice V, hidden d, H
query heads over G key-value heads of D channels, the window w of the
``sliding_attention`` layers among the published layers 0 .. ``layers`` - 1,
the experts' width f with e held of E published and k chosen a token) and the
cell's batch b and length T; tokens = b·T.

Attention needs, per query head and layer, the pairs (query, key) it sees:
``cells(T, w)`` of them, the causal triangle T (T + 1) / 2 in a full layer
and the band, w (w + 1) / 2 + (T - w) w, in a sliding one (960.06 a query at
T 8192 and w 1024, where the triangle has 4096.5): the keys outside the band
are not needed, whatever a program computes of them.

Whole step, per token: every layer's matrices once (2·(d·H·D + 2·d·G·D +
H·D·d) for the projections, the router 2·d·E, the routed experts at their
expectation under uniform routing, k·e/E experts a token, 2·3·d·f each),
attention's two products at 2·D a visible pair and head, and the untied head
over the slice once (2·d·V); forward once and backward twice, so three times
that. The embedding is a gather; the norms, the rotation, the softmaxes, the
routing and the loss are left out: a floor. Nothing recomputed counts.

Bytes, a floor no program can undercut: what the step is handed and hands
back. Every parameter and its two Adam moments (float32) are read once and
written once, 24 bytes a parameter and step; the int32 ids and labels are
read once. Activations are not counted.

The flash kernels (``ops/flash_attention.py``) do attention's products only:
per visible pair and head 2 products forward (the scores, the values) and 7
backward (the scores and dp in both backward kernels, dq, dk, dv), each of
2·D operations. ``kernel_work`` counts them over every layer, the yardstick of
``flash_kernels_roofline``; ``window_kernel_work`` over the sliding layers
alone, of ``window_flash_roofline``.
"""
from __future__ import annotations

#: products of a visible pair and head, forward and backward (above)
PRODUCTS = 2 + 7


def _sizes(config, traffic=None):
    kw = config["builder_kwargs"]
    kinds = list(kw["layer_types"][:int(kw["layers"])])
    traffic = traffic or {"batch": 0, "seq_len": 0}
    return dict(
        v=int(kw["vocab"]), d=int(kw["hidden"]), H=int(kw["heads"]),
        G=int(kw["kv_heads"]), D=int(kw["head_dim"]), w=int(kw["window"]),
        e=int(kw["experts"]), E=int(kw["experts_published"]),
        k=int(kw["experts_per_token"]), f=int(kw["moe_intermediate"]),
        sliding=kinds.count("sliding_attention"),
        full=kinds.count("full_attention"), layers=len(kinds),
        b=int(traffic["batch"]), t=int(traffic["seq_len"]))


def cells(T, window=None):
    """(query, key) pairs one head of a causal layer sees over ``T``
    tokens, within ``window`` keys where it is given."""
    if window is None or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def attention_matrices(d, H, G, D, **_):
    return d * H * D + 2 * d * G * D + H * D * d


def layer_params(d, E, e, f, **s):
    """A layer's attention, router, held experts and two gains."""
    return attention_matrices(d=d, **s) + d * E + e * 3 * d * f + 2 * d


def params(config):
    s = _sizes(config)
    return s["layers"] * layer_params(**s) + 2 * s["v"] * s["d"] + s["d"]


def _pairs(s):
    """Visible pairs of one head over the step's sequences: (sliding layer,
    full layer)."""
    return (s["b"] * cells(s["t"], s["w"]), s["b"] * cells(s["t"]))


def step_work(config, traffic):
    s = _sizes(config, traffic)
    tokens = s["b"] * s["t"]
    sliding, full = _pairs(s)
    per_token = s["layers"] * (
        2 * attention_matrices(**s) + 2 * s["d"] * s["E"]
        + (s["k"] * s["e"] / s["E"]) * 2 * 3 * s["d"] * s["f"]) + (
        2 * s["d"] * s["v"])
    attention = 2 * 2 * s["D"] * s["H"] * (s["sliding"] * sliding
                                           + s["full"] * full)
    return {"flops": int(3 * (tokens * per_token + attention)),
            "bytes": 24 * params(config) + 2 * tokens * 4}


def kernel_work(config, traffic):
    """What the flash-attention kernels of one step need, every layer."""
    s = _sizes(config, traffic)
    sliding, full = _pairs(s)
    return {"flops": PRODUCTS * 2 * s["D"] * s["H"]
            * (s["sliding"] * sliding + s["full"] * full),
            "bytes": 0,                 # none has to cross HBM: see above
            "calls": 3 * s["layers"]}   # flash_fwd, flash_dq, flash_dkv


def window_kernel_work(config, traffic):
    """What the sliding layers' flash kernels of one step need: the band."""
    s = _sizes(config, traffic)
    return {"flops": PRODUCTS * 2 * s["D"] * s["H"] * s["sliding"]
            * _pairs(s)[0],
            "bytes": 0,
            "calls": 3 * s["sliding"]}

"""Operations and bytes one training step of ``ouro_2_6b`` needs, from its
shapes (``builder_kwargs``: vocabulary V, hidden d, heads H of D, FFN width F,
L blocks applied R times) and the cell's batch b and length T; N = b·T
tokens.

Whole step, per token: a block's seven matrices once per application
(2·(4·d·H·D + 3·d·F)), causal attention at half of the full 4·T·H·D (the
score and the value product over the visible half), the head once per pass
(2·d·V); forward once and backward twice, so three times that. The
embedding is a gather, the norms, the rotation, the gate (2·d a token and
pass) and the losses are left out: a floor. Nothing recomputed counts: the
stack's block checkpoint adds about a forward pass of the blocks' work that
no algorithm needs (the head recomputes nothing since PR 31).

Bytes, a floor no program can undercut: what the step is handed and hands
back. Every parameter and its two Adam moments (float32) are read once and
written once, 24 bytes a parameter and step, the weights of the looped blocks
once whatever R; the int32 ids and labels are read once. Activations are not
counted: a step's could in principle stay on the chip.

The flash kernels (``ops/flash_attention.py``) do attention's products only.
Per head and block application they need two causal [T, T, D] products
forward (scores, values) and five backward (the scores once more, dP, dV,
dQ, dK; that the two backward kernels each form scores and dP is their
choice and counted once), each T·T·D operations at half the square; the
forward calls of the recomputed blocks are not needed and not counted. Their
operands are
values inside the program, so no byte of theirs has to cross HBM and their
floor is the MXU's.
"""
from __future__ import annotations


def _sizes(config, traffic):
    kw = config["builder_kwargs"]
    return (int(kw["vocab"]), int(kw["hidden"]), int(kw["heads"]),
            int(kw["head_dim"]), int(kw["intermediate"]), int(kw["blocks"]),
            int(kw["passes"]), int(traffic["batch"]), int(traffic["seq_len"]))


def block_params(d, h, hd, f):
    """A block's matrices (the four gains apart)."""
    return 4 * d * h * hd + 3 * d * f


def params(config):
    v, d, h, hd, f, blocks, _, _, _ = _sizes(config, {"batch": 0,
                                                      "seq_len": 0})
    return (2 * v * d + blocks * (block_params(d, h, hd, f) + 4 * d)
            + d + d + 1)


def step_work(config, traffic):
    v, d, h, hd, f, blocks, passes, b, t = _sizes(config, traffic)
    per_token = (passes * blocks * (2 * block_params(d, h, hd, f)
                                    + 2 * t * h * hd)
                 + passes * 2 * d * v)
    return {"flops": 3 * b * t * per_token,
            "bytes": 24 * params(config) + 2 * b * t * 4}


def kernel_work(config, traffic):
    """What the flash-attention kernels of one step need."""
    v, d, h, hd, f, blocks, passes, b, t = _sizes(config, traffic)
    return {"flops": (2 + 5) * t * t * hd * b * h * passes * blocks,
            "bytes": 0,                 # none has to cross HBM: see above
            "calls": 3 * passes * blocks}   # flash_fwd, flash_dq, flash_dkv

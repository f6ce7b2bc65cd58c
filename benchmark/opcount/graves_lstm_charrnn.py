"""Operations and bytes one training step of ``graves_lstm_charrnn`` needs,
from its shapes (``builder_kwargs``: vocabulary V, width H, layers) and the
cell's batch b and length T, truncated at L = ``tbptt``.

Whole step, per character: each layer's input projection (2·in·4H) and
recurrent product (2·H·4H) and the classifier (2·H·V), forward once and
backward twice; the first layer needs no gradient of its one-hot input.

Bytes, a floor that no program can undercut: what the step's program is
handed in HBM and hands back there. The one-hot features and labels
([b, T, V] float32 each) are read once; every parameter and its two Adam
moments (float32) are read once and written once, 24 bytes a parameter and
step. Nothing else is counted, because nothing else has to cross HBM: a
truncated segment's streams (projected input, gates, cell states, outputs)
and the gradients are values inside the scanned program, which the compiler
may keep in on-chip memory from the forward pass to the backward one, and
does (operands in ``S(1)``; PERF.md §5). The count this file held until
PR 26, 14·H float32 stream values per character and layer each way and 32
bytes per parameter and *segment*, put 47 GB a step through HBM and read
110.8 % of the roofline (ledger, PR 24): bytes that never go to memory.

The Pallas kernels (``ops/lstm_cell.py``) do the recurrent part only. Per
character and layer they need 2·H·4H operations forward and 2·H·4H backward
(dh = dz·RWᵀ; the weight gradient hᵀ·dz is an XLA product outside them).
Their operands and results are values inside the scanned program, which the
compiler may keep on the chip, so no byte of theirs has to cross HBM and
their floor is the MXU's alone: counted through HBM by their shapes they
read 173.7 % of its peak (21·H float32 values per character and layer;
ledger, PR 24), and would still read about 132 % with ``xw``, ``ys`` and
``dz`` counted in the bfloat16 they have had since PR 24.
"""
from __future__ import annotations


def _sizes(config, traffic):
    kw = config["builder_kwargs"]
    return (int(kw["vocab"]), int(kw["width"]), int(kw["layers"]),
            int(kw["tbptt"]), int(traffic["batch"]), int(traffic["seq_len"]))


def step_work(config, traffic):
    v, h, layers, tbptt, b, t = _sizes(config, traffic)
    per_char = 2 * h * v
    n_in, params = v, h * v + v
    for _ in range(layers):
        per_char += 2 * n_in * 4 * h + 2 * h * 4 * h
        params += n_in * 4 * h + h * 4 * h + 4 * h + 3 * h
        n_in = h
    flops = b * t * (3 * per_char - 2 * v * 4 * h)
    return {"flops": flops,
            "bytes": 2 * b * t * v * 4 + 24 * params}


def kernel_work(config, traffic):
    """What the Pallas LSTM kernels of one step need."""
    v, h, layers, tbptt, b, t = _sizes(config, traffic)
    return {"flops": b * t * layers * 2 * (2 * h * 4 * h),
            "bytes": 0,                 # none has to cross HBM: see above
            "calls": 2 * layers * -(-t // tbptt)}   # forward and backward

"""Operations and bytes one training step of ``graves_lstm_charrnn`` needs,
from its shapes (``builder_kwargs``: vocabulary V, width H, layers) and the
cell's batch b and length T, truncated at L = ``tbptt``.

Whole step, per character: each layer's input projection (2·in·4H) and
recurrent product (2·H·4H) and the classifier (2·H·V), forward once and
backward twice; the first layer needs no gradient of its one-hot input.
Bytes, a floor: per character and layer the projected input, the gates, the
cell states and the outputs (14·H float32 values) cross HBM once forward and
once backward; per segment every parameter costs 32 bytes (weight, gradient,
Adam's two moments, float32, read and written once).

The Pallas kernels (``ops/lstm_cell.py``) do the recurrent part only. Per
character and layer they need 2·H·4H operations forward and 2·H·4H backward
(dh = dz·RWᵀ; the weight gradient hᵀ·dz is an XLA product outside them), and
move, by the shapes of their operands and results, 10·H float32 values
forward (projected input 4H in; outputs H, gates 4H, cell states H out) and
11·H backward (output gradient H, gates 4H, cell states 2·H in; dz 4H out),
besides the H·4H recurrent weights in the compute dtype once per call.
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
    segments = -(-t // tbptt)
    return {"flops": flops,
            "bytes": b * t * layers * 2 * 14 * h * 4 + segments * 32 * params}


def kernel_work(config, traffic):
    """What the Pallas LSTM kernels of one step need."""
    v, h, layers, tbptt, b, t = _sizes(config, traffic)
    weight_bytes = 2 if config["global_conf"].get(
        "compute_dtype") == "bfloat16" else 4
    calls = 2 * layers * -(-t // tbptt)          # forward and backward
    return {"flops": b * t * layers * 2 * (2 * h * 4 * h),
            "bytes": b * t * layers * 21 * h * 4
            + calls * h * 4 * h * weight_bytes,
            "calls": calls}

"""Operations and bytes one training step of ``resnet50_imagenet`` needs,
from the shapes in the configuration's ``published`` block (He et al. 2015,
Table 1), not from the compiled program: XLA's ``cost_analysis()`` counts
padding and recomputation too (5.82 TFLOP and 81.5 GB per b256 step on the
v5e, jitwatch's capture, my chip run, PR 22) and may be printed beside this.

Operations: two per multiply-add of every convolution and of the classifier,
forward once and backward twice (gradient of the weights, gradient of the
input; the stem needs no gradient of the image). Batch normalisation, ReLU,
pooling and the residual sums are left out: they are not MXU work, and the
roofline counts their bytes.

Bytes, a floor under perfect fusion: every convolution's output crosses HBM
twice forward (written, read by its consumer) and three times backward (read
again, its gradient written and read), in the compute dtype; the residual
sums likewise; each parameter costs 32 bytes (weight, gradient and Adam's
two moments in float32, each read and written once).
"""
from __future__ import annotations


def conv_layers(published):
    """``(kernel, c_in, c_out, out_side)`` of every convolution, in order."""
    stem = published["stem"]
    side = published["image_size"] // stem["stride"]
    layers = [(stem["kernel"], 3, stem["channels"], side)]
    side //= stem["pool_stride"]
    c_in = stem["channels"]
    exp = published["bottleneck_expansion"]
    for si, (blocks, width) in enumerate(published["stages"]):
        for bi in range(blocks):
            if bi == 0 and si > 0:
                side //= 2           # stride 2 on the block's first 1x1
            layers.append((1, c_in, width, side))
            layers.append((3, width, width, side))
            layers.append((1, width, exp * width, side))
            if bi == 0:              # projection shortcut
                layers.append((1, c_in, exp * width, side))
            c_in = exp * width
    return layers, c_in


def _count(config):
    published = config["published"]
    layers, features = conv_layers(published)
    classes = published["num_classes"]
    macs = [k * k * ci * co * s * s for k, ci, co, s in layers]
    fwd = 2 * (sum(macs) + features * classes)
    params = (sum(k * k * ci * co + 2 * co for k, ci, co, _ in layers)
              + features * classes + classes)
    acts = sum(co * s * s for _, _, co, s in layers)
    # one residual sum per block, the size of the block's output
    exp = published["bottleneck_expansion"]
    side = published["image_size"] // published["stem"]["stride"] \
        // published["stem"]["pool_stride"]
    for si, (blocks, width) in enumerate(published["stages"]):
        side = side // 2 if si > 0 else side
        acts += blocks * exp * width * side * side
    return {"fwd_flops": fwd, "stem_flops": 2 * macs[0], "params": params,
            "act_elements": acts}


def step_work(config, traffic):
    """``{"flops", "bytes"}`` one training step needs."""
    c = _count(config)
    batch = int(traffic["batch"])
    act_bytes = 2 if config["global_conf"].get(
        "compute_dtype") == "bfloat16" else 4
    return {"flops": batch * (3 * c["fwd_flops"] - c["stem_flops"]),
            "bytes": batch * 5 * c["act_elements"] * act_bytes
            + 32 * c["params"]}

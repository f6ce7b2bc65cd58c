"""Plain reference of ``resnet50_imagenet``: ResNet-50 as He et al. 2015
(arXiv:1512.03385) Table 1 has it, in straightforward float32 ``jax.numpy``
with train-mode batch normalisation, softmax cross-entropy and nothing of
the program's (no layer classes, no precision policy, no remat).

Written from the paper, with the three choices the DL4J zoo makes where the
paper leaves room, each of which the program's model makes too: the stride
of a down-sampling block sits on its first 1x1 convolution; convolutions and
the stem's max-pool pad as XLA's ``SAME`` does; convolutions carry no bias
(batch normalisation follows each). It is handed the network's own
parameters, so it knows their names (``stem-conv``, ``s<stage>b<block>-a-conv``
… ``output``) and layout (``W``: HWIO), and nothing else about the network.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: Table 1, 50-layer column: (blocks, bottleneck width) of conv2_x … conv5_x;
#: a block's output is 4 x its width
STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))
BN_EPS = 1e-5

#: by the configuration's compute dtype. ``loss``: relative error of the
#: loss. ``grads``: ||g - g_ref|| / ||g_ref|| over all parameters together,
#: or None where gradients cannot be held to a reference.
#:
#: float32 is the CPU test's bar (the same arithmetic, another order of sums).
#:
#: bfloat16 is the chip's. Loss: the program rounds every convolution's
#: operands and every activation between layers to 8 bits of mantissa;
#: measured on the v5e at 8 x 224 x 224 against this reference 0.05 %,
#: 0.06 %, 0.18 % and 0.94 % over two seeds and two ways of compiling the
#: program (my chip runs, PR 22): batch norm over 8 images divides by small
#: variances. 4 % holds that and fails arithmetic 32 times coarser (3 bits
#: of mantissa). Gradients: None. A ReLU network's
#: gradient is a discontinuous function of its rounding (a ReLU or max-pool
#: that flips takes its whole term along), so the error grows as the square
#: root of the perturbation, not with it: at 8 x 224 x 224 on the seeded
#: weights float32 against float32 differ by 1.8 % (a 1e-6 rescaling of the
#: input, which batch norm undoes exactly, moves the program's own float32
#: gradients 2.5 %), and bfloat16 against float32 by 133 % on the v5e and
#: on the CPU alike, only the classifier's staying within 11 % (PR 22).
#: No tolerance separates bfloat16 from anything coarser there, so the
#: chip's check holds the loss; the gradients' code is held at float32 on
#: the CPU (tests/benchmark), and on four chips by the loss trajectory.
TOLERANCE = {"float32": {"loss": 1e-4, "grads": 1e-4},
             "bfloat16": {"loss": 0.04, "grads": None}}


def _conv(x, w, stride):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["gamma"] + p["beta"]


def _conv_bn(params, name, x, stride, relu=True):
    y = _bn(_conv(x, params[f"{name}-conv"]["W"], stride), params[f"{name}-bn"])
    return jax.nn.relu(y) if relu else y


def logits(params, images, stages=STAGES):
    """``images``: [b, 3, h, w] as the user hands them over."""
    x = jnp.transpose(images.astype(jnp.float32), (0, 2, 3, 1))
    x = _conv_bn(params, "stem", x, 2)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for si, (blocks, _) in enumerate(stages):
        for bi in range(blocks):
            name = f"s{si}b{bi}"
            stride = 2 if bi == 0 and si > 0 else 1
            y = _conv_bn(params, f"{name}-a", x, stride)
            y = _conv_bn(params, f"{name}-b", y, 1)
            y = _conv_bn(params, f"{name}-c", y, 1, relu=False)
            if bi == 0:              # projection shortcut (option B)
                x = _conv_bn(params, f"{name}-sc", x, stride, relu=False)
            x = jax.nn.relu(y + x)
    x = jnp.mean(x, axis=(1, 2))
    out = params["output"]
    return jnp.dot(x, out["W"], precision=lax.Precision.HIGHEST) + out["b"]


def loss(params, images, labels, stages=STAGES):
    """Softmax cross-entropy against one-hot ``labels``, summed over the
    classes and averaged over the batch."""
    logp = jax.nn.log_softmax(logits(params, images, stages), axis=-1)
    return -jnp.sum(labels * logp) / images.shape[0]

"""Plain reference of ``graves_lstm_charrnn``: stacked LSTM layers with
peephole connections (Graves, "Generating Sequences With Recurrent Neural
Networks", 2013, eqs. 7-11, as DL4J's GravesLSTM has them: the input and
forget gates see the previous cell state, the output gate the new one) and a
softmax layer over the characters at every step, written from the equations
in float32 ``jax.numpy``: one matrix product per gate block and step, no
hoisted projection, no kernel, full back-propagation through the sample's
whole length.

It is handed the network's own parameters and knows their names and layout:
layer ``"i"`` holds ``W`` [in, 4H], ``RW`` [H, 4H], ``b`` [4H] with the gate
blocks in the order input, forget, output, cell candidate, and the peephole
vectors ``pi``, ``pf``, ``po``; the last entry is the output layer's ``W``,
``b``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: by the configuration's compute dtype; ``loss`` relative, ``grads``
#: ||g - g_ref|| / ||g_ref|| over all parameters together. float32 is the
#: CPU test's bar. bfloat16 is the chip's: the program rounds the operands
#: of every product to 8 bits of mantissa and its kernels carry 50 steps of
#: that; measured on the v5e at 8 x T 50 against this reference: loss
#: 8e-7 and 1.8e-4, gradients 0.467 % and 0.468 % (my chip runs, PR 22; PR 21
#: saw 1.5 % between the kernel and scan paths). The bars are about four
#: times that, and arithmetic 32 times coarser (3 bits of mantissa) cannot
#: stay inside. (Sigmoid and tanh are smooth, so unlike ResNet50's these
#: gradients can be held to a reference.)
TOLERANCE = {"float32": {"loss": 1e-4, "grads": 1e-4},
             "bfloat16": {"loss": 2e-3, "grads": 0.02}}

_HI = lax.Precision.HIGHEST


def _layer(p, xs):
    """``xs``: [T, b, in] -> [T, b, H]."""
    hidden = p["RW"].shape[0]

    def step(carry, x_t):
        h, c = carry
        z = (jnp.dot(x_t, p["W"], precision=_HI)
             + jnp.dot(h, p["RW"], precision=_HI) + p["b"])
        zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
        i = jax.nn.sigmoid(zi + c * p["pi"])
        f = jax.nn.sigmoid(zf + c * p["pf"])
        c_new = f * c + i * jnp.tanh(zg)
        o = jax.nn.sigmoid(zo + c_new * p["po"])
        h_new = o * jnp.tanh(c_new)
        return (h_new, c_new), h_new

    zeros = jnp.zeros((xs.shape[1], hidden), jnp.float32)
    _, ys = lax.scan(step, (zeros, zeros), xs)
    return ys


def loss(params, chars, labels):
    """``chars``, ``labels``: one-hot [b, T, vocab]. Cross-entropy summed
    over steps and characters, averaged over the batch."""
    n = len(params)
    xs = jnp.swapaxes(chars.astype(jnp.float32), 0, 1)
    for i in range(n - 1):
        xs = _layer(params[str(i)], xs)
    out = params[str(n - 1)]
    logp = jax.nn.log_softmax(
        jnp.dot(xs, out["W"], precision=_HI) + out["b"], axis=-1)
    return -jnp.sum(jnp.swapaxes(labels, 0, 1) * logp) / chars.shape[0]

"""Long-context training with container-level sequence parallelism.

A transformer-style net trains with the TIME dimension sharded across the
mesh — ring(-flash) attention mixes context across shards, so per-device
activation memory is O(T/n) while the math stays exactly the full-attention
step. Runs anywhere; to try it on the virtual CPU mesh:

    DL4J_TPU_EXAMPLE_CPU=8 python examples/long_context_sequence_parallel.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _common import maybe_force_cpu  # noqa: E402

maybe_force_cpu()

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu import NeuralNetConfiguration, Adam
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer, DenseLayer,
                                               RnnOutputLayer)
from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                         SEQUENCE_AXIS)

VOCAB, WIDTH, HEADS = 32, 64, 4

conf = (NeuralNetConfiguration.builder().seed(7)
        .updater(Adam(learning_rate=3e-4)).activation("identity")
        .list()
        .layer(SelfAttentionLayer(n_in=VOCAB, n_out=WIDTH, num_heads=HEADS,
                                  causal=True))
        .layer(DenseLayer(n_in=WIDTH, n_out=WIDTH, activation="relu"))
        .layer(SelfAttentionLayer(n_in=WIDTH, n_out=WIDTH, num_heads=HEADS,
                                  causal=True))
        .layer(RnnOutputLayer(n_in=WIDTH, n_out=VOCAB, activation="softmax",
                              loss="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()

n = len(jax.devices())
mesh = make_mesh(jax.devices(), axes=(SEQUENCE_AXIS,))
step, place = sequence_parallel_step(net, mesh)
place(net)

T = 128 * n                       # local shard = 128 → flash-in-ring on TPU
rng = np.random.default_rng(0)
ids = rng.integers(0, VOCAB, size=(2, T))
f = np.eye(VOCAB, dtype=np.float32)[ids]
l = np.eye(VOCAB, dtype=np.float32)[np.roll(ids, -1, axis=1)]
print(f"devices={n}  T={T}  local shard={T // n}")

it = 0
for s in range(10):
    (net.params, net.states, net.updater_state, loss) = step(
        net.params, net.states, net.updater_state,
        jnp.asarray(it, jnp.int32), jax.random.PRNGKey(s),
        jnp.asarray(f), jnp.asarray(l))
    it += 1
    if s % 3 == 0:
        print(f"step {s:2d} loss {float(loss):.3f}")

# after sp training the same net serves with the normal dense path
out = net.output(f[:, :64])
print("dense-path inference after sp training:", np.asarray(out).shape)

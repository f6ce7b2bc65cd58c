"""The names the program puts on the device (docs/OBSERVABILITY.md "Span
Tracer"): a stable ``name=`` on every ``pallas_call``, and
``jax.named_scope`` per layer / vertex, ``loss`` and ``updater`` in the
``op_name`` of both containers' train steps. Metadata only: nothing here
runs a kernel."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import (Adam, InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer, DenseLayer,
                                               OutputLayer, SubsamplingLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import lstm_cell as lk
from deeplearning4j_tpu.ops import lstm_fused as lf


@pytest.fixture
def interpret(monkeypatch):
    # off the chip a kernel call is an error unless a test asks for
    # interpret mode; tracing to a jaxpr then runs nothing
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)


def _grad_jaxpr(fn, *avals):
    loss = lambda *a: jnp.sum(jax.tree_util.tree_leaves(fn(*a))[0]
                              .astype(jnp.float32) ** 2)
    return str(jax.make_jaxpr(jax.grad(loss, argnums=tuple(
        range(len(avals)))))(*avals))


SDS = jax.ShapeDtypeStruct
B, T, H = 8, 8, 128
XP, W = SDS((B, T, 4 * H), jnp.float32), SDS((H, 4 * H), jnp.float32)
ST, PEEP = SDS((B, H), jnp.float32), (SDS((H,), jnp.float32),) * 3
QKV = [SDS((1, 256, 2, 64), jnp.float32)] * 3


@pytest.mark.parametrize("entry,avals,kernels", [
    (lk.lstm_scan, (SDS((T, B, 4 * H), jnp.float32),
                    SDS((4 * H,), jnp.float32), W, PEEP, ST, ST),
     ("lstm_cell_fwd", "lstm_cell_bwd")),
    (lf.lstm_scan2, (XP, W, PEEP, W, SDS((4 * H,), jnp.float32), W, PEEP,
                     ST, ST, ST, ST),
     ("lstm_fused_fwd", "lstm_fused_bwd")),
    (lambda q, k, v: fa.flash_attention(q, k, v, causal=True), QKV,
     ("flash_fwd", "flash_dq", "flash_dkv")),
], ids=["lstm_cell", "lstm_fused", "flash_attention"])
def test_every_pallas_kernel_has_its_name_in_the_jaxpr(entry, avals, kernels,
                                                       interpret):
    jaxpr = _grad_jaxpr(entry, *avals)
    assert jaxpr.count("pallas_call") >= len(kernels)
    for name in kernels:
        assert name in jaxpr, name


def _lenet_layers():
    return [ConvolutionLayer(kernel_size=(5, 5), n_out=4, activation="relu"),
            SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)),
            DenseLayer(n_out=16, activation="relu"),
            OutputLayer(n_out=10, activation="softmax", loss="mcxent")]


def _builder():
    return NeuralNetConfiguration.builder().seed(1).updater(
        Adam(learning_rate=1e-3))


def _lowered(net, features, labels):
    step = net._raw_step(False)
    return jax.jit(step).lower(
        net.params, net.states, net.updater_state, jnp.int32(0),
        net._next_rng(), features, labels, None, None).as_text(
            debug_info=True)


def test_multilayer_step_carries_layer_loss_and_updater_scopes():
    b = _builder().list()
    for layer in _lenet_layers():
        b = b.layer(layer)
    net = MultiLayerNetwork(
        b.set_input_type(InputType.convolutional(28, 28, 1)).build()).init()
    text = _lowered(net, jnp.zeros((8, 1, 28, 28)), jnp.zeros((8, 10)))
    for scope in ("0", "1", "2", "loss"):
        assert f"jvp({scope})/" in text, scope           # its forward pass
        assert f"transpose(jvp({scope}))/" in text, scope    # its backward
    assert "jit(step)/updater/" in text


def test_graph_step_carries_vertex_loss_and_updater_scopes():
    names = ["conv", "pool", "dense", "out"]
    g = _builder().graph_builder().add_inputs("in")
    for name, layer, below in zip(names, _lenet_layers(), ["in"] + names):
        g = g.add_layer(name, layer, below)
    net = ComputationGraph(
        g.set_outputs("out")
        .set_input_types(InputType.convolutional(28, 28, 1)).build()).init()
    text = _lowered(net, (jnp.zeros((8, 1, 28, 28)),),
                    (jnp.zeros((8, 10)),))
    for scope in ("conv", "pool", "dense", "loss"):
        assert f"jvp({scope})/" in text, scope
        assert f"transpose(jvp({scope}))/" in text, scope
    assert "jit(step)/updater/" in text


_CHILD = """
import contextlib, json, os, sys
import jax, jax.numpy as jnp
from jax import monitoring
from deeplearning4j_tpu.compilecache import enable
enable(sys.argv[1])
seen = {"hits": 0, "misses": 0}
monitoring.register_event_listener(lambda event, **kw: seen.__setitem__(
    event.rsplit("_", 1)[-1], seen.get(event.rsplit("_", 1)[-1], 0) + 1))
scope = jax.named_scope("layer7") if sys.argv[2] == "scoped" \\
    else contextlib.nullcontext()
def f(x):
    with scope: return jnp.tanh(x @ x).sum()
text = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
print(json.dumps({"named": "layer7" in text, "hits": seen["hits"],
                  "misses": seen["misses"]}))
"""


def test_the_compile_cache_never_serves_another_commits_names(tmp_path):
    """The scopes are op metadata, which jax leaves out of the persistent
    cache's key unless told otherwise: an entry compiled before the scope
    existed would serve a program text (and trace names) without it.
    ``compilecache.enable`` puts the metadata in the key."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # the cache is this test's

    def child(mode):
        p = subprocess.run(
            [sys.executable, "-c", _CHILD, str(tmp_path / "cache"), mode],
            capture_output=True, text=True, timeout=300, env=env)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    assert child("plain") == {"named": False, "hits": 0, "misses": 3}
    scoped = child("scoped")
    # the same arithmetic under a scope compiles for itself …
    assert scoped["named"] is True and scoped["misses"] >= 1
    # … and is then served from the cache with its own names
    assert child("scoped") == {"named": True, "hits": 3, "misses": 0}

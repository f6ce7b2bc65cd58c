"""What the hybrid state-space / attention language model brought to the
program: the chunked Mamba-2 scan against the step-by-step recurrence, the
causal convolution, grouped-query heads and the attention scale against a
dense oracle (and through the flash kernels in interpret mode at head size
64), the embedding's multiplier and the head's divisor, the tied leaf, the
hybrid stack's runs, scopes and gauges."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeplearning4j_tpu.ops.flash_attention as fa
from benchmark.reference import granite_4_0_h_micro as reference
from deeplearning4j_tpu import Adam, DataSet
from deeplearning4j_tpu.monitor import get_registry
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    EmbeddingSequenceLayer, HybridBlockStack, LoopedBlockStack,
    LoopLMOutputLayer, Mamba2Layer, RnnOutputLayer, SelfAttentionLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import hybrid, looped, mamba
from deeplearning4j_tpu.nn.layers.attention import mha
from deeplearning4j_tpu.nn.layers.base import FLASH_RES, NORM_IN
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.utils.model_serializer import ModelSerializer

V, D = 48, 32
TYPES = ["mamba", "mamba", "attention", "mamba"]


def _builder():
    return (NeuralNetConfiguration.builder().seed(11)
            .updater(Adam(learning_rate=1e-3)).activation("identity"))


def _stack(**over):
    kw = dict(n_in=D, n_out=D, layer_types=TYPES, n_hidden=40, eps=1e-5,
              residual_multiplier=0.22, num_heads=8, num_kv_heads=2,
              head_dim=4, attention_scale=0.2, mamba_heads=4,
              mamba_head_dim=16, mamba_state_size=8, mamba_conv_size=4,
              mamba_chunk_size=8)
    return HybridBlockStack(**{**kw, **over})


def _lm(tied=True, l2=None, scale=12.0, divisor=8.0, **over):
    b = _builder()
    if l2:
        b = b.l2(l2)
    return ComputationGraph(
        b.graph_builder().add_inputs("ids")
        .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=D,
                                                   scale=scale), "ids")
        .add_layer("stack", _stack(**over), "embed")
        .add_layer("out", RnnOutputLayer(
            n_in=D, n_out=V, loss="sparse_mcxent", activation="softmax",
            has_bias=False, tied_to="embed" if tied else None,
            logits_divisor=divisor), "stack")
        .set_outputs("out").build()).init()


def _batch(T=20, seed=0, b=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (b, T + 1)).astype(np.int32)
    return DataSet(np.ascontiguousarray(ids[:, :-1]),
                   np.ascontiguousarray(ids[:, 1:]))


# ----------------------------------------------------------- the scan
def _scan_inputs(T, seed=0, b=2, H=3, P=4, N=5):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.6, size=(b, T, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 8.0, size=(H,)), jnp.float32)
    B, C = (jnp.asarray(rng.normal(size=(b, T, N)), jnp.float32)
            for _ in range(2))
    return x, dt, a, B, C


def _recurrence(x, dt, a, B, C):
    """The benchmark reference's step-by-step scan, on the program's
    arguments."""
    return reference.selective_scan(x, dt, jnp.exp(dt * a), B, C)


@pytest.mark.parametrize("T, chunk", [
    (8, 8),        # one chunk
    (24, 4),       # several chunks, one segment
    (100, 4),      # 25 chunks: four segments of seven, padded to 28
    (21, 8),       # no multiple of the chunk: padded with dt = 0, cut again
])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(T, chunk):
    args = _scan_inputs(T)
    assert -(-T // chunk) <= mamba.SEGMENT_CHUNKS or T == 100
    got = mamba.ssd_chunked(*args, chunk, jnp.float32)
    want = _recurrence(*args)
    assert got.shape == want.shape == args[0].shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    w = jnp.asarray(np.random.default_rng(1).normal(size=want.shape),
                    jnp.float32)
    g_got = jax.grad(lambda *a: jnp.sum(w * mamba.ssd_chunked(
        *a, chunk, jnp.float32)), argnums=(0, 1, 2, 3, 4))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(w * _recurrence(*a)),
                      argnums=(0, 1, 2, 3, 4))(*args)
    for got_leaf, want_leaf in zip(g_got, g_want):
        scale = float(jnp.max(jnp.abs(want_leaf)))
        np.testing.assert_allclose(got_leaf, want_leaf, rtol=1e-4,
                                   atol=1e-5 * scale)


def test_the_state_crosses_chunk_and_segment_boundaries():
    """An impulse at step 0 is still read at the last step, three segments
    on; with the carried state cut it is not."""
    T, chunk = 4 * mamba.SEGMENT_CHUNKS * 3, 4
    x, dt, a, B, C = _scan_inputs(T, H=1, P=1, N=1, b=1)
    x = jnp.zeros_like(x).at[0, 0].set(1.0)
    dt, a = jnp.full_like(dt, 0.01), jnp.full_like(a, -0.5)
    B, C = jnp.ones_like(B), jnp.ones_like(C)
    y = mamba.ssd_chunked(x, dt, a, B, C, chunk, jnp.float32)
    want = 0.01 * np.exp(-0.5 * 0.01 * (T - 1))
    np.testing.assert_allclose(float(y[0, -1, 0, 0]), want, rtol=1e-5)


def test_the_convolution_is_causal_and_reads_its_own_channel():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    y = mamba.causal_conv1d(x, w, bias)
    later = x.at[:, 7:].add(3.0)         # nothing at or before 6 moves
    np.testing.assert_array_equal(
        np.asarray(mamba.causal_conv1d(later, w, bias))[:, :7],
        np.asarray(y)[:, :7])
    other = x.at[:, :, 1].add(3.0)       # nor any channel but 1
    moved = np.asarray(mamba.causal_conv1d(other, w, bias)) != np.asarray(y)
    assert moved[:, :, 1].all() and not moved[:, :, [0, 2, 3, 4, 5]].any()
    np.testing.assert_allclose(
        y, reference.causal_depthwise_conv(x, w, bias), rtol=1e-5, atol=1e-5)
    # y_t = sum_k w[:, k] x_{t - 3 + k} + b, written out at t = 5
    np.testing.assert_allclose(
        y[:, 5], sum(w[:, k] * x[:, 2 + k] for k in range(4)) + bias,
        rtol=1e-5, atol=1e-5)


# -------------------------------------------- the differentiation rule
def _plain_split_conv_silu(zxbcdt, w, b, d_inner, out_dtype):
    """What the rule replaces: split, convolution, SiLU, cast, autodiff
    throughout."""
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + w.shape[0]], axis=-1)
    return z, jax.nn.silu(mamba.causal_conv1d(xbc, w, b)).astype(out_dtype), dt


@pytest.mark.parametrize("out_dtype, tol", [
    (jnp.float32, 1e-6),
    # the policy's rounding: xBC leaves, and its cotangent comes, in bfloat16
    (jnp.bfloat16, 2.0 ** -8),
], ids=["float32", "bfloat16"])
def test_the_rule_is_the_gradient_of_what_it_replaces(out_dtype, tol):
    """Batch 2, a T that is no multiple of any chunk."""
    rng = np.random.default_rng(3)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    d_inner, C, H = 6, 11, 3
    args = draw(2, 21, d_inner + C + H), draw(C, 4), draw(C)
    got = mamba.split_conv_silu(*args, d_inner, out_dtype)
    want = _plain_split_conv_silu(*args, d_inner, out_dtype)
    assert [o.dtype for o in got] == [jnp.float32, out_dtype, jnp.float32]
    for g, wnt in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(wnt, np.float32))
    weights = [jnp.asarray(rng.normal(size=o.shape), o.dtype) for o in want]
    scalar = lambda f: lambda *a: sum(
        jnp.sum((o * wt).astype(jnp.float32))
        for o, wt in zip(f(*a, d_inner, out_dtype), weights))
    got = jax.grad(scalar(mamba.split_conv_silu), argnums=(0, 1, 2))(*args)
    want = jax.grad(scalar(_plain_split_conv_silu), argnums=(0, 1, 2))(*args)
    for g, wnt in zip(got, want):
        assert g.shape == wnt.shape and g.dtype == wnt.dtype
        assert (float(jnp.linalg.norm(g - wnt))
                <= tol * float(jnp.linalg.norm(wnt)))


def _mamba_impl():
    from deeplearning4j_tpu.nn.layers.base import impl_for
    conf = (_builder().list().layer(Mamba2Layer(
        n_in=D, n_out=D, num_heads=4, head_dim=16, state_size=8,
        chunk_size=8)).build())
    return impl_for(conf.layers[0], conf.global_conf, None)


def test_a_mamba2_layer_is_the_recurrence_in_output_and_every_leaf():
    """The layer with its rule against the benchmark reference's mixer (the
    recurrence step by step, autodiff throughout), batch 2, a T that does not
    fill its last chunk."""
    impl = _mamba_impl()
    params, _ = impl.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    params = {k: v + 0.1 * jnp.asarray(rng.normal(size=v.shape), v.dtype)
              for k, v in params.items()}      # no leaf at its drawn 0 or 1
    x = jnp.asarray(rng.normal(size=(2, 21, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 21, D)), jnp.float32)
    ours = lambda p, x: impl.forward(p, {}, x, train=True)[0]
    theirs = lambda p, x: reference.mamba_mixer(p, x, 1e-5)
    np.testing.assert_allclose(ours(params, x), theirs(params, x),
                               rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda p, x: jnp.sum(w * ours(p, x)), (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(w * theirs(p, x)), (0, 1))(params, x)
    assert set(got[0]) == {"W_in", "conv_W", "conv_bias", "dt_bias", "A_log",
                           "D", "gn", "W_out"}
    for name, g, wnt in [("x", got[1], want[1])] + [
            (k, got[0][k], want[0][k]) for k in sorted(want[0])]:
        assert (float(jnp.linalg.norm(g - wnt))
                <= 1e-4 * float(jnp.linalg.norm(wnt))), name


def _padded_products(jaxpr, shape):
    """The distinct products of ``shape`` that a ``pad`` reads, through every
    sub-jaxpr: autodiff's transposition of K shifted reads pads K products
    of the cotangent, one a tap."""
    found = set()

    def walk(j):
        made = {}
        for eqn in j.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            if eqn.primitive.name == "mul":
                made[eqn.outvars[0]] = eqn
            if (eqn.primitive.name == "pad" and eqn.invars[0] in made
                    and eqn.invars[0].aval.shape == shape):
                found.add(eqn.invars[0])
    walk(jaxpr.jaxpr)
    return len(found)


def test_the_layers_backward_pads_no_product_a_tap():
    """No four [b, T, d_inner + 2N] products of the cotangent with a tap,
    each padded and added up: one ``dpre``, shifted."""
    impl = _mamba_impl()
    params, _ = impl.init(jax.random.PRNGKey(3))
    x = jnp.ones((2, 21, D), jnp.float32)
    shape = (2, 21, 64 + 2 * 8)
    layer = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        impl.forward(p, {}, x, train=True)[0])))(params)
    assert _padded_products(layer, shape) == 1
    cw, cb = params["conv_W"], params["conv_bias"]
    plain = jax.make_jaxpr(jax.grad(lambda u: sum(map(
        jnp.sum, _plain_split_conv_silu(u, cw, cb, 64, jnp.float32)))))(
            jnp.ones((2, 21, 148), jnp.float32))
    assert _padded_products(plain, shape) == 4     # what the rule replaced


def test_a_mamba2_layer_trains_in_a_multilayer_network():
    net = MultiLayerNetwork(
        _builder().list()
        .layer(EmbeddingSequenceLayer(n_in=V, n_out=D))
        .layer(Mamba2Layer(n_in=D, n_out=D, num_heads=4, head_dim=16,
                           state_size=8, chunk_size=8))
        .layer(RnnOutputLayer(n_in=D, n_out=V, loss="sparse_mcxent",
                              activation="softmax")).build()).init()
    assert set(net.params["1"]) == {"W_in", "conv_W", "conv_bias", "dt_bias",
                                    "A_log", "D", "gn", "W_out"}
    assert net.params["1"]["W_in"].shape == (D, 2 * 64 + 2 * 8 + 4)
    dt = jax.nn.softplus(net.params["1"]["dt_bias"])
    lo, hi = mamba.Mamba2Impl.DT_RANGE
    assert float(dt.min()) >= lo * 0.999 and float(dt.max()) <= hi * 1.001
    ds = _batch()
    losses = []
    for _ in range(8):
        net.fit(ds)
        losses.append(float(net.score_))
    assert losses[-1] < losses[0]
    with pytest.raises(ValueError, match="key mask"):
        net.score(DataSet(ds.features, ds.labels,
                          features_mask=np.ones((2, 20), np.float32)))


# ------------------------------------------------ grouped heads, the scale
def _dense_oracle(q, k, v, scale):
    """Causal attention with query head i on key-value head i // group."""
    group = q.shape[2] // k.shape[2]
    T = q.shape[1]
    out = []
    for i in range(q.shape[2]):
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, i], k[:, :, i // group]) * scale
        p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                                     -jnp.inf), axis=-1)
        out.append(jnp.einsum("bqk,bkd->bqd", p, v[:, :, i // group]))
    return jnp.stack(out, axis=2)


def _grouped(T, h, kv, d, seed=0, b=2):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, T, h, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, T, kv, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, T, kv, d)), jnp.float32))


def test_grouped_heads_and_the_scale_on_the_dense_path():
    q, k, v = _grouped(24, 8, 2, 4)
    got = mha(q, k, v, True, jnp.float32, scale=0.015625)
    np.testing.assert_allclose(got, _dense_oracle(q, k, v, 0.015625),
                               rtol=1e-5, atol=1e-6)
    plain = mha(q, k, v, True, jnp.float32)          # None: 1 / sqrt(d)
    np.testing.assert_allclose(plain, _dense_oracle(q, k, v, 0.5), rtol=1e-5,
                               atol=1e-6)


def test_grouped_heads_reach_the_flash_kernels_at_head_size_64(monkeypatch):
    """Interpret mode, 4 query heads a group, d 64, several blocks: forward
    and all three gradients against the dense oracle; dk and dv are sums
    over a group's query heads."""
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    q, k, v = _grouped(256, 8, 2, 64, seed=3, b=1)
    called = []
    sound = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: (
        called.append(a[1].shape), sound(*a, **kw))[1])
    scale = 0.015625
    np.testing.assert_allclose(mha(q, k, v, True, jnp.float32, scale=scale),
                               _dense_oracle(q, k, v, scale), rtol=2e-4,
                               atol=2e-5)
    assert called == [(1, 256, 8, 64)]       # repeated to the query heads
    w = jnp.asarray(np.random.default_rng(4).normal(size=q.shape),
                    jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(w * mha(*a, True, jnp.float32,
                                              scale=scale)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * _dense_oracle(*a, scale)),
                    argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=2e-3, atol=2e-4)


def test_a_long_causal_call_on_the_tpu_raises_rather_than_go_dense(
        monkeypatch):
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    T = fa.MIN_SEQ + 4                 # no multiple of the kernels' block
    q = jax.ShapeDtypeStruct((1, T, 4, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, T, 1, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"does not fit the flash kernels"):
        jax.eval_shape(lambda q, k, v: mha(q, k, v, True, jnp.bfloat16),
                       q, kv, kv)
    # not causal, or short: the dense path as before
    short = jax.ShapeDtypeStruct((1, 100, 4, 64), jnp.bfloat16)
    kv_short = jax.ShapeDtypeStruct((1, 100, 1, 64), jnp.bfloat16)
    assert jax.eval_shape(lambda q, k, v: mha(q, k, v, True, jnp.bfloat16),
                          short, kv_short, kv_short).shape == short.shape


def test_the_attention_layer_with_grouped_heads_streams_what_it_computes():
    conf = SelfAttentionLayer(n_in=D, n_out=D, num_heads=8, num_kv_heads=2,
                              head_dim=4, attention_scale=0.3, has_bias=False,
                              activation="identity", stream_max_length=32)
    net = MultiLayerNetwork(
        _builder().list().layer(conf)
        .layer(RnnOutputLayer(n_in=D, n_out=5, loss="mcxent",
                              activation="softmax")).build()).init()
    assert net.params["0"]["Wk"].shape == (D, 2 * 4)
    assert net.params["0"]["Wq"].shape == (D, 8 * 4)
    x = np.random.default_rng(5).normal(size=(2, 12, D)).astype(np.float32)
    whole = np.asarray(net.output(x))
    net.rnn_clear_previous_state()
    parts = [np.asarray(net.rnn_time_step(x[:, s:s + 4]))
             for s in (0, 4, 8)]
    np.testing.assert_allclose(np.concatenate(parts, axis=1), whole,
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="does not divide"):
        MultiLayerNetwork(_builder().list().layer(SelfAttentionLayer(
            n_in=D, n_out=D, num_heads=8, num_kv_heads=3)).layer(
                RnnOutputLayer(n_in=D, n_out=5)).build()).init()


# -------------------------------------------------------------- the tie
def test_the_tied_head_is_one_leaf_counted_once():
    net = _lm()
    assert net.params["out"] == {} and "W" in net.params["embed"]
    untied = _lm(tied=False)
    assert untied.params["out"]["W"].shape == (D, V)
    assert net.num_params() == untied.num_params() - D * V
    assert net.num_params() == sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree_util.tree_leaves(net.params))
    assert "embed" in net.updater_state and not net.updater_state["out"]
    assert f"{0:>10}" in net.summary().splitlines()[3]      # out: 0 params


def test_the_tied_leafs_gradient_is_the_gathers_plus_the_heads():
    net, ds = _lm(), _batch()
    grads, _ = net.compute_gradient_and_score(ds)
    sound = net._params_of

    def cut(which):
        """The loss's gradient with one of the leaf's two uses detached."""
        def params_of(params, name):
            out = sound(params, name)
            if name == "out" and which == "head":
                out = {**out, "tied_W": jax.lax.stop_gradient(out["tied_W"])}
            if name == "embed" and which == "gather":
                out = jax.lax.stop_gradient(out)
            return out
        net._params_of = params_of
        try:
            return net.compute_gradient_and_score(ds)[0]["embed"]["W"]
        finally:
            net._params_of = sound

    gather_only, head_only = cut("head"), cut("gather")
    assert float(jnp.abs(gather_only).max()) > 0
    assert float(jnp.abs(head_only).max()) > 0
    np.testing.assert_allclose(grads["embed"]["W"], gather_only + head_only,
                               rtol=1e-5, atol=1e-6)


def test_the_tied_leaf_is_regularised_and_updated_once():
    ds = _batch()
    plain, reg = _lm(), _lm(l2=0.1)
    matrices = [v for k, v in reg.params["stack"].items()
                if k.partition(".")[2] in ("W_in", "conv_W", "W_out", "Wq",
                                           "Wk", "Wv", "Wo", "Wgate", "Wup",
                                           "Wdown")]
    want = 0.05 * (float(jnp.sum(reg.params["embed"]["W"] ** 2))
                   + sum(float(jnp.sum(m ** 2)) for m in matrices))
    assert reg.score(ds) - plain.score(ds) == pytest.approx(want, rel=1e-4)
    before = np.asarray(plain.params["embed"]["W"])
    plain.fit(ds)
    step = np.abs(np.asarray(plain.params["embed"]["W"]) - before)
    # Adam's first step moves every touched entry by the learning rate, once
    assert step.max() == pytest.approx(1e-3, rel=1e-2)


def test_the_tied_network_round_trips_through_the_serializer(tmp_path):
    net, ds = _lm(), _batch()
    net.fit(ds)
    path = str(tmp_path / "tied.zip")
    ModelSerializer.write_model(net, path, save_updater=True)
    back = ModelSerializer.restore_computation_graph(path)
    assert back.params["out"] == {}
    assert back.conf.vertices["out"].tied_to == "embed"
    assert back.conf.vertices["out"].logits_divisor == 8.0
    assert back.conf.vertices["embed"].scale == 12.0
    assert back.conf.vertices["stack"].layer_types == TYPES
    jax.tree_util.tree_map(np.testing.assert_array_equal, net.params,
                           back.params)
    np.testing.assert_allclose(back.output(ds.features),
                               net.output(ds.features), rtol=1e-6)
    assert back.score(ds) == pytest.approx(net.score(ds), rel=1e-6)


def test_a_tie_needs_a_graph_and_an_embedding_of_the_right_shape():
    layers = (_builder().list()
              .layer(EmbeddingSequenceLayer(n_in=V, n_out=D))
              .layer(RnnOutputLayer(n_in=D, n_out=V, loss="sparse_mcxent",
                                    activation="softmax", tied_to="0")))
    with pytest.raises(ValueError, match="only a ComputationGraph"):
        MultiLayerNetwork(layers.build()).init()
    for source, n_out in (("stack", V), ("embed", V + 1), ("nowhere", V)):
        with pytest.raises(ValueError, match="is tied to"):
            ComputationGraph(
                _builder().graph_builder().add_inputs("ids")
                .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=D),
                           "ids")
                .add_layer("stack", _stack(), "embed")
                .add_layer("out", RnnOutputLayer(
                    n_in=D, n_out=n_out, loss="sparse_mcxent",
                    activation="softmax", tied_to=source), "stack")
                .set_outputs("out").build()).init()


def test_the_embeddings_multiplier_and_the_heads_divisor():
    ids = _batch().features
    one, twelve = _lm(scale=None), _lm(scale=12.0)
    acts = [net.feed_forward(ids)["embed"] for net in (one, twelve)]
    np.testing.assert_allclose(acts[1], 12.0 * acts[0], rtol=1e-6)
    # logits / 8: the softmax of the divided logits
    net = _lm(divisor=8.0)
    h = net.feed_forward(ids)["stack"]
    logits = jnp.einsum("btd,vd->btv", h, net.params["embed"]["W"]) / 8.0
    np.testing.assert_allclose(net.output(ids), jax.nn.softmax(logits, -1),
                               rtol=1e-5, atol=1e-7)


# ------------------------------------------------------------ the stack
def test_the_stack_scans_runs_of_like_blocks_over_stacked_leaves():
    net = _lm()
    stack = net.impls["stack"]
    assert stack.runs == [("mamba", 2), ("attention", 1), ("mamba", 1)]
    assert stack.block_kinds == {"mamba": 3, "attention": 1}
    p = net.params["stack"]
    assert p["r0.W_in"].shape[0] == 2 and p["r2.W_in"].shape[0] == 1
    assert p["r1.Wq"].shape == (1, D, 32) and p["r1.Wk"].shape == (1, D, 8)
    assert "r1.W_in" not in p and "r0.Wq" not in p and p["gf"].shape == (D,)
    with pytest.raises(ValueError, match="layer_types"):
        ComputationGraph(
            _builder().graph_builder().add_inputs("ids")
            .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=D),
                       "ids")
            .add_layer("stack", _stack(layer_types=["mamba", "conv"]),
                       "embed")
            .add_layer("out", RnnOutputLayer(n_in=D, n_out=V), "stack")
            .set_outputs("out").build()).init()
    with pytest.raises(ValueError, match="no streaming state"):
        net.rnn_time_step(_batch().features)


def test_the_step_carries_the_scopes_and_sets_the_gauges(monkeypatch):
    import deeplearning4j_tpu.monitor.registry as registry
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())
    net, ds = _lm(), _batch(T=20)
    net.fit(ds)
    gauges = get_registry().snapshot()
    blocks = {row["labels"]["kind"]: (row["value"], row["labels"]["network"])
              for row in gauges["hybrid_blocks"]}
    assert blocks == {"mamba": (3, "cg"), "attention": (1, "cg")}
    chunks, = gauges["ssm_chunks"]
    assert chunks["labels"] == {"layer": "stack"} and chunks["value"] == 3
    f, l, fm, lm = net._batch_streams(ds)
    text = jax.jit(net._raw_step(False)).lower(
        net.params, net.states, net.updater_state, jnp.int32(0),
        net._next_rng(), f, l, fm, lm).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))

    def named(*parts):      # an op whose op_name holds the parts in order
        pattern = re.compile(".*".join(re.escape(p) for p in parts))
        return any(pattern.search(n) for n in names)

    for sub in ("ssm", "ssm/ssd", "attn", "ffn"):
        assert named("jvp(stack)/", "blocks/", f"/{sub}/"), sub
        assert named("transpose(jvp(stack))/", "blocks/", f"/{sub}/"), sub
        # the recomputed forward, inside the backward pass
        assert named("transpose(jvp(stack))/", "blocks/",
                     "/rematted_computation/", f"{sub}/"), sub
    assert named("jvp(stack)/", "final_norm")
    assert named("jvp(loss)/", "head/", "dot_general")
    assert named("transpose(jvp(loss))/", "head/", "dot_general")


# ------------------------------------------------- the block checkpoint
def _looped_lm():
    return ComputationGraph(
        _builder().graph_builder().add_inputs("ids")
        .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=D), "ids")
        .add_layer("stack", LoopedBlockStack(
            n_in=D, n_out=D, num_blocks=2, num_passes=2, num_heads=4,
            head_dim=8, n_hidden=40, eps=1e-6, rope_theta=1e6), "embed")
        .add_layer("out", LoopLMOutputLayer(n_in=D, n_out=V,
                                            entropy_weight=0.05), "stack")
        .set_outputs("out").build()).init()


#: stack -> (its network, the heads of a flash call, their size)
STACKS = {"looped": (_looped_lm, 4, 8), "hybrid": (_lm, 8, 4)}


def _trained(make, T):
    """(network after one ``fit``, its loss as a function of the
    parameters on a fresh batch)."""
    net = make()
    net.fit(_batch(T=T, seed=1, b=1))
    ds = _batch(T=T, seed=2, b=1)
    return net, lambda p: net._loss_fn(
        p, net.states, [jnp.asarray(ds.features)], [jnp.asarray(ds.labels)],
        None, None, True, None)[0]


def _kernels_by_scan(jaxpr, found):
    """Appends to ``found`` the sorted names of the Pallas kernels that each
    ``scan`` of ``jaxpr`` holds outside any scan of its own (none: no
    entry); returns those that ``jaxpr`` holds outside every scan."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inner = _kernels_by_scan(sub, found)
            if eqn.primitive.name != "scan":
                names += inner
            elif inner:
                found.append(sorted(inner))
    return names


def _plain_checkpoint(monkeypatch):
    """Both stacks' blocks under ``jax.checkpoint`` with no policy."""
    for module in (looped, hybrid):
        monkeypatch.setattr(module, "block_checkpoint",
                            lambda block: jax.checkpoint(block))


def _checkpoint_keeping(monkeypatch, *names):
    """Both stacks' blocks under a checkpoint whose policy keeps ``names``."""
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    for module in (looped, hybrid):
        monkeypatch.setattr(
            module, "block_checkpoint",
            lambda block: jax.checkpoint(block, policy=policy))


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_a_blocks_backward_holds_no_forward_kernel(stack, monkeypatch):
    """The block checkpoint keeps the flash kernels' residuals: the forward
    scan over the blocks holds ``flash_fwd``, the backward scan the two
    backward kernels and no ``flash_fwd``; the gradients are those of a
    plain ``jax.checkpoint(block)``, whose backward scan runs the forward
    kernel again; the gauge reads the residuals' bytes."""
    import deeplearning4j_tpu.monitor.registry as registry
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    T = 2 * fa.MIN_BLOCK
    make, heads, d = STACKS[stack]
    net, loss = _trained(make, T)
    fwd, dq, dkv = (f"flash_{k}_q{T}_k{T}" for k in ("fwd", "dq", "dkv"))

    scans = []
    assert not _kernels_by_scan(
        jax.make_jaxpr(jax.grad(loss))(net.params).jaxpr, scans)
    assert scans == [[fwd], [dkv, dq]]
    got = jax.grad(loss)(net.params)

    row, = get_registry().snapshot()["flash_residual_bytes"]
    assert row["labels"] == {"kernel": fwd}
    assert row["value"] == heads * T * (4 * d + 1) * 4   # q k v o, a lane

    _plain_checkpoint(monkeypatch)
    scans = []
    _kernels_by_scan(jax.make_jaxpr(jax.grad(loss))(net.params).jaxpr, scans)
    assert scans == [[fwd], [dkv, dq, fwd]]
    want = jax.grad(loss)(net.params)
    # kept and recomputed values are the same bits; the CPU compiler fuses
    # the two backward bodies apart, which rounds (6e-7 of a leaf's norm)
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(x - y)) <= 2e-6 * float(
            jnp.linalg.norm(y))


@pytest.mark.parametrize("stack", ["looped_dense", "hybrid_dense",
                                   "state_space_only"])
def test_a_stack_with_no_flash_call_is_the_program_it_was(stack, monkeypatch):
    """Nothing tagged, nothing kept: on the dense path (off the TPU) and in
    a stack of state-space blocks the policy changes no line. The looped
    block names its last norm's input whatever the path (PR 38): there the
    flash kernels' name changes no line."""
    make = {"looped_dense": _looped_lm, "hybrid_dense": _lm,
            "state_space_only": lambda: _lm(layer_types=["mamba"] * 3)}[stack]
    net, loss = _trained(make, 24)
    text = jax.jit(jax.grad(loss)).lower(net.params).as_text()
    assert ("dot_general" in text) and "flash" not in text
    if stack == "looped_dense":
        _checkpoint_keeping(monkeypatch, NORM_IN)
    else:
        _plain_checkpoint(monkeypatch)
    assert jax.jit(jax.grad(loss)).lower(net.params).as_text() == text


def test_the_hybrid_block_names_no_norm_input(monkeypatch):
    """The hybrid block has no norm after a sub-layer: it names nothing for
    the checkpoint, and its step under the one policy, which also keeps what
    a looped block names (PR 38), is its step under the flash kernels' name
    alone, kernels and all."""
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
    net, loss = _trained(_lm, 2 * fa.MIN_BLOCK)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(net.params))
    assert FLASH_RES in jaxpr and NORM_IN not in jaxpr
    text = jax.jit(jax.grad(loss)).lower(net.params).as_text()
    _checkpoint_keeping(monkeypatch, FLASH_RES)
    assert jax.jit(jax.grad(loss)).lower(net.params).as_text() == text

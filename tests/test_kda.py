"""The Kimi Delta Attention mixer (``nn/layers/kda.py``): the chunked delta
rule against the recurrence token by token (loss and every gradient, several
chunk lengths, lengths that fill no chunk, strong decays, several segments),
and the layer against its equations written out."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers import kda


def recurrence(q, k, v, g, beta):
    """``S_t = Diag(exp(g_t)) S_{t-1}``, ``S_t += beta_t k_t (v_t - S_t^T
    k_t)^T``, ``o_t = S_t^T q_t``, one step a token."""
    b, T, H, K = k.shape

    def step(S, at_t):
        q_t, k_t, v_t, g_t, b_t = at_t
        S = jnp.exp(g_t)[..., None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, v_t - seen)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((b, H, K, v.shape[-1]), k.dtype),
                        tuple(jnp.moveaxis(t, 1, 0)
                              for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _inputs(T, decay, seed=0, b=2, H=2, K=8):
    rng = np.random.default_rng(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    q, k, v, w = (rng.normal(size=(b, T, H, K)) for _ in range(4))
    g = -decay * rng.uniform(0.0, 1.0, size=(b, T, H, K))
    beta = rng.uniform(0.0, 1.0, size=(b, T, H))
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        unit(q) * K ** -0.5, unit(k), v, g, beta, w))


@pytest.mark.parametrize("chunk,T,decay", [
    (8, 40, 1.0),        # one block a chunk: the differences alone
    (16, 50, 0.05),      # a length that fills no chunk: padded
    (32, 96, 1.0),       # two blocks a chunk: one through its start
    (64, 150, 1.0),      # the family's chunk, four blocks
    (64, 128, 6.0),      # decays whose inverse overflows float32 in a chunk
    (48, 100, 1.0),      # a chunk that 16 does not divide (blocks of 16 = gcd)
])
def test_the_chunked_rule_is_the_recurrence(chunk, T, decay):
    *args, w = _inputs(T, decay)
    chunked = lambda *a: jnp.sum(
        kda.delta_rule_chunked(*a, chunk, jnp.float32) * w)
    stepwise = lambda *a: jnp.sum(recurrence(*a) * w)
    got, grads = jax.value_and_grad(chunked, (0, 1, 2, 3, 4))(*args)
    want, ref = jax.value_and_grad(stepwise, (0, 1, 2, 3, 4))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for a, r in zip(grads, ref):
        assert float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r)) < 1e-4


def test_the_state_crosses_segments_as_it_crosses_chunks(monkeypatch):
    """Five chunks in segments of two (one padded with a whole chunk): the
    checkpointed walk over segments hands the state on."""
    monkeypatch.setattr(kda, "SEGMENT_CHUNKS", 2)
    *args, w = _inputs(70, 1.0, seed=2)
    chunked = lambda *a: jnp.sum(
        kda.delta_rule_chunked(*a, 16, jnp.float32) * w)
    stepwise = lambda *a: jnp.sum(recurrence(*a) * w)
    got, grads = jax.value_and_grad(chunked, (0, 1, 2, 3, 4))(*args)
    want, ref = jax.value_and_grad(stepwise, (0, 1, 2, 3, 4))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for a, r in zip(grads, ref):
        assert float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r)) < 1e-4


def test_a_state_that_is_not_carried_shows(monkeypatch):
    """The fault the benchmark plants: every chunk starting from nought is
    another function."""
    *args, w = _inputs(64, 0.05, seed=3)
    sound = kda.delta_rule_chunked(*args, 16, jnp.float32)
    carried = kda.carried_states

    def forgetful(S, *chunks):
        out = [carried(jnp.zeros_like(S), *(c[i:i + 1] for c in chunks[:-1]),
                       chunks[-1]) for i in range(chunks[0].shape[0])]
        return out[-1][0], tuple(jnp.concatenate([o[1][j] for o in out])
                                 for j in range(2))

    monkeypatch.setattr(kda, "carried_states", forgetful)
    cut = kda.delta_rule_chunked(*args, 16, jnp.float32)
    assert float(jnp.linalg.norm(cut - sound) / jnp.linalg.norm(sound)) > 0.1
    assert np.allclose(np.asarray(cut[:, :16]), np.asarray(sound[:, :16]),
                       atol=1e-5)


def _layer(n_in=24, H=2, K=8, chunk=16):
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import KimiDeltaAttentionLayer
    from deeplearning4j_tpu.nn.layers.base import impl_for
    conf = NeuralNetConfiguration.builder().seed(3).list().build()
    return impl_for(KimiDeltaAttentionLayer(
        n_in=n_in, n_out=n_in, num_heads=H, head_dim=K, chunk_size=chunk),
        conf.global_conf)


def test_the_layer_is_its_equations():
    """``KimiDeltaAttentionLayer`` against the benchmark's plain reference of
    the mixer (``benchmark/reference/kimi_linear_48b_a3b.py: kda_mixer``,
    the recurrence step by step): output and every gradient leaf."""
    from benchmark.reference import kimi_linear_48b_a3b as reference
    layer = _layer()
    params, state = layer.init(jax.random.PRNGKey(1))
    assert state == {}
    assert {k: v.shape for k, v in params.items()} == {
        "Wq": (24, 16), "Wk": (24, 16), "Wv": (24, 16), "conv_q": (16, 4),
        "conv_k": (16, 4), "conv_v": (16, 4), "W_fa": (24, 8),
        "W_fb": (8, 16), "W_b": (24, 2), "W_ga": (24, 8), "W_gb": (8, 16),
        "Wo": (16, 24), "dt_bias": (16,), "A_log": (2,), "gn": (8,)}
    # softplus(dt_bias) is a step drawn in [1e-3, 1e-1], A in [1, 16]
    step = np.log1p(np.exp(np.asarray(params["dt_bias"])))
    assert 1e-3 <= step.min() and step.max() <= 1e-1
    assert 0.0 <= np.asarray(params["A_log"]).min() \
        and np.asarray(params["A_log"]).max() <= np.log(16.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 37, 24)),
                    jnp.float32)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    got, grads = jax.value_and_grad(loss(
        lambda p, x: layer.forward(p, {}, x)[0]), (0, 1))(params, x)
    with jax.default_matmul_precision("highest"):
        want, ref = jax.value_and_grad(loss(
            lambda p, x: reference.kda_mixer(p, x, 1e-5)), (0, 1))(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, a), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref)):
        assert float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r)) < 1e-4, path


def test_the_layer_stacks_its_leaves_and_refuses_what_it_cannot_do():
    layer = _layer()
    params, _ = layer.init(jax.random.PRNGKey(1), lead=(3,))
    assert params["Wq"].shape == (3, 24, 16)
    assert params["A_log"].shape == (3, 2) and params["gn"].shape == (3, 8)
    one, _ = layer.init(jax.random.PRNGKey(1))
    x = jnp.zeros((1, 8, 24), jnp.float32)
    with pytest.raises(ValueError, match="key mask"):
        layer.forward(one, {}, x, mask=jnp.ones((1, 8)))
    with pytest.raises(ValueError, match="streaming"):
        layer.forward(one, {}, x, ctx={"rnn_state_in": {}})
    # the chunks' gauge is set where the layer is traced
    from deeplearning4j_tpu.monitor import get_registry
    layer.index = "probe"
    layer.forward(one, {}, jnp.zeros((1, 40, 24), jnp.float32))
    rows = {row["labels"]["layer"]: row["value"]
            for row in get_registry().snapshot()["kda_chunks"]}
    assert rows["probe"] == 3                                  # 40 / 16

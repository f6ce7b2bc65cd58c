"""Sliding-window attention and YaRN positions: the banded walk of the flash
kernels (interpret mode) against the dense oracle with the band mask, its
pair tables, the dense path's window, YaRN's frequencies at the published
numbers, and the hybrid stack's ``window`` blocks."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeplearning4j_tpu.ops.flash_attention as fa
from deeplearning4j_tpu.nn.layers.attention import mha, rope, yarn


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = fa._FORCE_INTERPRET
    fa._FORCE_INTERPRET = True
    yield
    fa._FORCE_INTERPRET = old


def _bh_inputs(bh, T, d, seed):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(bh, T, d)), jnp.float32)
                 for _ in range(3))


def _dense_band(q, k, v, window, km=None, keep=None, rate=0.0):
    """Dense attention on [bh, T, d]: query i sees keys i - window < j <= i
    (and ``km``'s), ``keep`` drops normalized probabilities, a row with no
    visible key outputs 0."""
    T = q.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    vis = jnp.broadcast_to(jnp.asarray((j <= i) & (j > i - window)), s.shape)
    if km is not None:
        vis &= km[:, None, :] > 0
    p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1)
    p = jnp.where(jnp.any(vis, axis=-1, keepdims=True), p, 0.0)
    if keep is not None:
        p = p * keep / (1.0 - rate)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _kernels(q, k, v, km8, seed, rate, block_q, block_k, window):
    """(o, dq, dk, dv) of loss = sum(o²) through the three kernels."""
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    kw = dict(block_q=block_q, block_k=block_k, window=window)
    o, lse = fa._fwd(q, k, v, km8, seed, True, scale, rate, **kw)
    do = 2.0 * o
    delta = fa.rowwise_delta(do, o)
    dq = fa.dq_block(q, k, v, km8, do, delta, lse, True, scale, seed, rate,
                     **kw)
    dk, dv = fa.dkv_block(q, k, v, km8, do, delta, lse, True, scale, seed,
                          rate, **kw)
    return o, dq, dk, dv


@pytest.mark.parametrize("case,window,block_q,block_k,variant", [
    ("window under a block", 64, 128, 128, "plain"),
    ("window a block", 128, 128, 128, "plain"),
    ("window no multiple of the block", 200, 128, 128, "plain"),
    ("window no multiple, unequal edges", 200, 256, 128, "plain"),
    ("a row's first block outside its window", 64, 256, 128, "plain"),
    ("key mask", 200, 128, 128, "key_mask"),
    ("dropout", 200, 128, 256, "dropout"),
])
def test_the_banded_walk_matches_the_dense_band(case, window, block_q,
                                                block_k, variant):
    """Forward and the three gradients at T 512 on multi-block grids. With
    block_q 256 over block_k 128 and a window of 64, the queries 320 .. 511
    first visit the k block 128 .. 255, which lies wholly outside their
    window: they come out exact."""
    bh, T, d, rate = 2, 512, 32, 0.5
    q, k, v = _bh_inputs(bh, T, d, seed=51)
    km = km8 = seed = keep = None
    if variant == "key_mask":
        km = np.ones((bh, T), np.float32)
        km[0, 100:300] = 0.0       # rows 100 .. 299 of the first lose keys
        km[1, 7::5] = 0.0
        km = jnp.asarray(km)
        km8 = jnp.broadcast_to(km[..., None], (bh, T, 8))
    if variant == "dropout":
        seed = fa.seed3(77)
        keep = fa.dropout_keep_mask(bh, T, T, 77, rate)
    use_rate = rate if variant == "dropout" else 0.0
    got = _kernels(q, k, v, km8, seed, use_rate, block_q, block_k, window)
    loss = lambda q, k, v: jnp.sum(
        _dense_band(q, k, v, window, km, keep, rate) ** 2)
    want = (_dense_band(q, k, v, window, km, keep, rate),
            *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
    for name, a, w in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-3,
                                   atol=2e-4, err_msg=f"{case}: {name}")
    # against the causal walk the band changes what it should
    if variant == "plain":
        causal = fa._fwd(q, k, v, None, None, True, 1 / np.sqrt(d), 0.0,
                         block_q=block_q, block_k=block_k)[0]
        assert not np.allclose(np.asarray(causal)[:, window:],
                               np.asarray(got[0])[:, window:])
        np.testing.assert_allclose(np.asarray(causal)[:, :window],
                                   np.asarray(got[0])[:, :window],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("window", [256, 1000])
def test_a_window_of_the_whole_length_is_the_causal_call(window):
    """A window of T or more is the causal call bit for bit, forward and
    backward, under the causal kernels' names."""
    q, k, v = (jnp.transpose(x.reshape(1, 2, 256, 16), (0, 2, 1, 3))
               for x in _bh_inputs(2, 256, 16, seed=5))
    loss = lambda w: lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, window=w) ** 2)
    for a, b in zip(jax.value_and_grad(loss(window), (0, 1, 2))(q, k, v),
                    jax.value_and_grad(loss(None), (0, 1, 2))(q, k, v)):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    jaxpr = str(jax.make_jaxpr(loss(window))(q, k, v))
    assert "_w" not in jaxpr.split("name=flash_fwd")[1].split()[0]


def test_a_window_needs_a_causal_call():
    q, k, v = _bh_inputs(1, 256, 16, seed=1)
    with pytest.raises(ValueError, match="causal"):
        fa._fwd(q, k, v, None, None, False, 0.25, 0.0, window=64)
    x = q.reshape(1, 256, 1, 16)
    with pytest.raises(ValueError, match="causal"):
        mha(x, x, x, False, jnp.float32, window=64)


# --------------------------------------------------------------- pair tables
def _band_pairs(nq, nk, bq, bk, window, k_major):
    """The pairs of blocks that hold a cell of the band, by brute force."""
    out = []
    for i in range(nq):
        for j in range(nk):
            qs, ks = np.arange(i * bq, (i + 1) * bq), np.arange(j * bk,
                                                               (j + 1) * bk)
            vis = ((ks[None] <= qs[:, None])
                   & (ks[None] > qs[:, None] - window))
            if vis.any():
                out.append((j, i) if k_major else (i, j))
    return sorted(out)


@pytest.mark.parametrize("T,bq,bk,window", [
    (8192, 1024, 1024, 1024), (8192, 512, 512, 1024),
    (8192, 1024, 512, 1024), (2048, 256, 128, 300), (1024, 128, 256, 100)])
@pytest.mark.parametrize("k_major", [False, True])
def test_the_band_tables_hold_exactly_the_band(T, bq, bk, window, k_major):
    """No pair outside the band is ever a grid step, every pair in it is
    one, rows in order with their first and last marked."""
    nq, nk = T // bq, T // bk
    outer, inner, first, last = fa.causal_pairs(nq, nk, bq, bk, k_major,
                                                window)
    pairs = list(zip(outer.tolist(), inner.tolist()))
    assert pairs == _band_pairs(nq, nk, bq, bk, window, k_major)
    for t, (o, _) in enumerate(pairs):
        assert first[t] == (t == 0 or pairs[t - 1][0] != o)
        assert last[t] == (t == len(pairs) - 1 or pairs[t + 1][0] != o)
    # the cell's shape (ISSUE 41): 15 pairs against the triangle's 36 at
    # 1024 x 1024; 45 at 512 x 512 where the triangle takes 136
    causal = len(fa.causal_pairs(nq, nk, bq, bk, k_major)[0])
    if (T, bq, bk) == (8192, 1024, 1024):
        assert (len(pairs), causal) == (15, 36)
    if (T, bq, bk) == (8192, 512, 512):
        assert (len(pairs), causal) == (45, 136)


@pytest.mark.parametrize("nq,nk,bq,bk", [(4, 4, 128, 128), (4, 2, 128, 256),
                                         (2, 4, 128, 128)])
@pytest.mark.parametrize("k_major", [False, True])
def test_no_window_keeps_the_causal_tables(nq, nk, bq, bk, k_major):
    """``window=None`` returns the tables of the causal walk, array for
    array (their values as written before windows)."""
    vis = np.arange(nk)[None, :] * bk <= (np.arange(nq)[:, None] + 1) * bq - 1
    if k_major:
        vis = vis.T.copy()
        vis[~vis.any(axis=1), -1] = True
    outer, inner = np.nonzero(vis)
    edge = outer[1:] != outer[:-1]
    want = (outer, inner, np.r_[True, edge], np.r_[edge, True])
    for a, b in zip(fa.causal_pairs(nq, nk, bq, bk, k_major), want):
        assert a.dtype == np.int32
        np.testing.assert_array_equal(a, b.astype(np.int32))


def test_the_banded_kernels_are_named_and_walk_the_band():
    """The names carry the window, and ``flash_grid_steps`` counts the
    band's pairs times bh against the triangle's."""
    from deeplearning4j_tpu.monitor import get_registry
    sds = jax.ShapeDtypeStruct((2, 1024, 32), jnp.float32)
    st = jax.ShapeDtypeStruct((2, 1024, 8), jnp.float32)

    def three(q, k, v, do, delta, lse):
        kw = dict(block_q=256, block_k=256, window=300)
        return (fa._fwd(q, k, v, None, None, True, 0.25, 0.0, **kw),
                fa.dq_block(q, k, v, None, do, delta, lse, True, 0.25, **kw),
                fa.dkv_block(q, k, v, None, do, delta, lse, True, 0.25,
                             **kw))

    jaxpr = str(jax.make_jaxpr(three)(sds, sds, sds, sds, st, st))
    steps = {r["labels"]["kernel"]: r["value"]
             for r in get_registry().snapshot()["flash_grid_steps"]}
    band = len(fa.causal_pairs(4, 4, 256, 256, window=300)[0])
    assert band == 4 + 3 + 2 < len(fa.causal_pairs(4, 4, 256, 256)[0]) == 10
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"{kernel}_q256_k256_w300" in jaxpr
        assert steps[f"{kernel}_q256_k256_w300"] == 2 * band


def test_the_chooser_takes_the_window():
    """The window is one of what the chooser sees (the swept edges of the
    Mellum2 cell's windowed layers: the backward kernels at half the
    window), the edges it gives fit the scoped VMEM, and a call without a
    window keeps its edges."""
    swept = {"flash_fwd": (1024, 1024), "flash_dq": (512, 512),
             "flash_dkv": (512, 512)}
    for kernel, edges in swept.items():
        bq, bk = fa.pick_blocks(kernel, 8192, 8192, 128, jnp.bfloat16,
                                window=1024)
        assert (bq, bk) == edges
        assert fa.vmem_bytes(kernel, bq, bk, 128, jnp.bfloat16) \
            <= fa.VMEM_LIMIT
        assert fa.pick_blocks(kernel, 8192, 8192, 128, jnp.bfloat16) == (
            1024, 1024)
        assert fa.pick_blocks(kernel, 8192, 8192, 128, jnp.bfloat16,
                              window=100) == (128, 128)


# ------------------------------------------------------------- dense window
def test_the_dense_path_honours_the_window():
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 100, 4, 16)), jnp.float32)
               for _ in range(3))
    k2, v2 = k[:, :, ::2], v[:, :, ::2]                   # two kv heads
    got = mha(q, k2, v2, True, jnp.float32, window=30)
    bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(8, 100, 16)
    rep = lambda x: jnp.repeat(x, 2, axis=2)
    want = _dense_band(bh(q), bh(rep(k2)), bh(rep(v2)), 30)
    np.testing.assert_allclose(
        np.asarray(jnp.transpose(got, (0, 2, 1, 3)).reshape(8, 100, 16)),
        np.asarray(want), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------- YaRN
MELLUM2_YARN = {"rope_type": "yarn", "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782}


def test_yarn_at_the_published_numbers():
    """transformers' ``_compute_yarn_parameters`` written out: channels
    0 .. 18 keep their frequency, 35 .. 63 are divided by 16, the ones
    between are blended on a linear ramp; the factor is the published one."""
    theta, d = 500000.0, 128
    inv_freq, factor = yarn(theta, d // 2, **MELLUM2_YARN)
    ext = theta ** (-np.arange(0, d, 2) / d)
    low = math.floor(d * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    ramp = np.clip((np.arange(64) - low) / (high - low), 0, 1)
    want = ext / 16 * ramp + ext * (1 - ramp)
    np.testing.assert_allclose(inv_freq, want, rtol=1e-12)
    np.testing.assert_array_equal(inv_freq[:19], ext[:19])
    np.testing.assert_allclose(inv_freq[35:], ext[35:] / 16, rtol=1e-12)
    assert np.all(inv_freq[19:35] < ext[19:35])
    assert np.all(inv_freq[19:35] > ext[19:35] / 16)
    assert factor == 1.2772588722239782
    # the default factor where the block gives none
    assert yarn(theta, 64, **dict(MELLUM2_YARN, attention_factor=None))[1] \
        == pytest.approx(0.1 * math.log(16) + 1)


def test_rope_with_yarn_rotates_by_the_scaled_frequencies():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 12, 2, 128)), jnp.float32)
    got = rope(x, 500000.0, 3, MELLUM2_YARN)
    inv_freq, m = yarn(500000.0, 64, **MELLUM2_YARN)
    angle = (3 + np.arange(12))[:, None] * inv_freq
    cos, sin = m * np.cos(angle)[:, None], m * np.sin(angle)[:, None]
    x1, x2 = np.asarray(x[..., :64]), np.asarray(x[..., 64:])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # the scores of a rotated pair grow by the factor's square
    q = rope(x, 500000.0, 0, MELLUM2_YARN)
    plain = rope(x, 500000.0, 0, dict(MELLUM2_YARN, attention_factor=1.0))
    np.testing.assert_allclose(np.asarray(jnp.sum(q * q)),
                               m * m * np.asarray(jnp.sum(plain * plain)),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="yarn"):
        rope(x, 500000.0, 0, {"rope_type": "dynamic", "factor": 2.0})


# ------------------------------------------------- the hybrid window blocks
def _stack(layer_types, **kw):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import HybridBlockStack
    from deeplearning4j_tpu.nn.layers.base import impl_for
    conf = (NeuralNetConfiguration.builder().seed(1).activation("identity")
            .graph_builder().add_inputs("x").add_layer(
                "stack", HybridBlockStack(
                    n_in=32, n_out=32, layer_types=layer_types,
                    ffn_types=["experts"] * len(layer_types), eps=1e-6,
                    num_heads=4, num_kv_heads=2, head_dim=8, num_experts=8,
                    experts_held=[0, 1, 2, 3], experts_per_token=2,
                    expert_hidden=16, expert_score="softmax", **kw), "x")
            .set_outputs("stack").build())
    conf.global_conf.compute_dtype = "float32"
    return impl_for(conf.vertices["stack"], conf.global_conf, None)


def test_window_blocks_run_in_runs_of_their_own_under_their_scope():
    from deeplearning4j_tpu.monitor import get_registry
    stack = _stack(["window", "window", "attention"], window=5,
                   rope_theta=5e5, rope_scaling=MELLUM2_YARN)
    assert stack.runs == [("window", 2), ("attention", 1)]
    assert stack.block_kinds == {"attention": 1, "window": 2}
    window, full = stack.mixers["window"].conf, stack.mixers["attention"].conf
    assert (window.window, window.rope_theta, window.rope_scaling) == (
        5, 5e5, None)
    assert (full.window, full.rope_theta, full.rope_scaling) == (
        None, 5e5, MELLUM2_YARN)
    params, state = stack.init(jax.random.PRNGKey(0))
    assert state == {}                     # softmax scores: no bias state
    assert params["r0.Wk"].shape == (2, 32, 16)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 12, 32)),
                    jnp.float32)
    text = jax.jit(lambda p: stack.forward(p, state, x, train=True)[0]
                   ).lower(params).as_text(debug_info=True)
    assert "/swa/" in text and "/attn/" in text
    gauges = get_registry().snapshot()["attention_window"]
    assert {row["value"] for row in gauges} == {5}
    with pytest.raises(ValueError, match="window"):
        _stack(["window"])

"""Pallas flash attention vs the dense oracle (the cuDNN-helper
cross-validation pattern, SURVEY.md §4.4: custom-kernel path == builtin path
on identical inputs — here for forward AND backward)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeplearning4j_tpu.ops.flash_attention as fa
from deeplearning4j_tpu.nn.layers.attention import mha


@pytest.fixture(autouse=True)
def _interpret_mode():
    # CPU test backend: run the Pallas kernels in interpreter mode
    old = fa._FORCE_INTERPRET
    fa._FORCE_INTERPRET = True
    yield
    fa._FORCE_INTERPRET = old


def _qkv(b=2, T=256, h=2, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, T, h, d)), jnp.float32)
                 for _ in range(3))


def _dense_ref(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    if causal:
        T = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_dense(causal):
    q, k, v = _qkv()
    out = fa.flash_attention(q, k, v, causal=causal)
    want = _dense_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_dense(causal):
    q, k, v = _qkv(b=1, T=256, h=1, d=16, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_ref(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_mha_routes_to_flash_and_matches():
    """mha() with a block-divisible sequence uses the flash path; the result
    must match the dense oracle computation."""
    q, k, v = _qkv(b=2, T=256, h=2, d=32, seed=5)
    got = mha(q, k, v, True, jnp.float32)
    want = _dense_ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_mha_fallback_paths_still_dense():
    # odd T → dense path; with key_mask → dense path. Both still correct.
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 100, 2, 16)), jnp.float32)
               for _ in range(3))
    got = mha(q, k, v, True, jnp.float32)
    want = _dense_ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    km = jnp.ones((2, 256))
    q2, k2, v2 = _qkv(seed=8)
    got2 = mha(q2, k2, v2, False, jnp.float32, key_mask=km)
    want2 = _dense_ref(q2, k2, v2, False)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=2e-4, atol=2e-5)


def test_self_attention_layer_uses_flash_for_long_seq():
    from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                    DataSet, Adam)
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer)

    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Adam(learning_rate=1e-3)).activation("identity")
            .list()
            .layer(SelfAttentionLayer(n_in=16, n_out=16, num_heads=2))
            .layer(RnnOutputLayer(n_in=16, n_out=4, activation="softmax",
                                  loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(2)
    f = rng.normal(size=(2, 256, 16)).astype(np.float32)
    l = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (2, 256))].astype(
        np.float32)
    ds = DataSet(f, l)
    s0 = float(net.score(ds))
    for _ in range(5):
        net.fit(ds)
    assert np.isfinite(float(net.score_))
    assert float(net.score(ds)) < s0


def test_supported_routing_contract():
    """Routing rules: no flash off-TPU (unless tests force interpret), no
    flash below MIN_SEQ on hardware / odd lengths; dropout and [b, T] key
    masks run IN-kernel (no dense fallback)."""
    # inside this module's autouse fixture _FORCE_INTERPRET is True:
    assert fa.supported(256, 64, 0.0, None)
    assert not fa.supported(250, 64, 0.0, None)     # not block-divisible
    assert not fa.supported(256, 512, 0.0, None)    # head dim too large
    assert fa.supported(256, 64, 0.1, None)         # in-kernel dropout
    assert not fa.supported(256, 64, 1.0, None)     # degenerate rate
    assert not fa.supported(256, 64, 0.0, object())  # non-[b,T] mask object
    # without forced interpret on the CPU test backend: never supported
    fa._FORCE_INTERPRET = False
    try:
        assert not fa.supported(8192, 64, 0.0, None)
    finally:
        fa._FORCE_INTERPRET = True


@pytest.mark.parametrize("window", [None, 4])
def test_self_attention_rnn_time_step_kv_cache_matches_full(window):
    """Streaming rnn_time_step with the KV cache must reproduce the full-
    sequence causal forward, token by token (the attention analogue of the
    reference's rnnTimeStep-vs-full consistency checks); with a window of 4
    keys, both sides see the same 4."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer)

    conf = (NeuralNetConfiguration.builder().seed(4)
            .updater(Adam(learning_rate=1e-3)).activation("identity")
            .list()
            .layer(SelfAttentionLayer(n_in=12, n_out=12, num_heads=3,
                                      stream_max_length=32, window=window))
            .layer(RnnOutputLayer(n_in=12, n_out=5, activation="softmax",
                                  loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(6)
    T = 9
    x = rng.normal(size=(2, T, 12)).astype(np.float32)
    full = np.asarray(net.output(x))           # causal full-sequence forward

    net.rnn_clear_previous_state()
    stepped = []
    for t in range(T):
        y = np.asarray(net.rnn_time_step(x[:, t:t + 1, :]))
        stepped.append(y[:, 0])
    stepped = np.stack(stepped, axis=1)
    np.testing.assert_allclose(stepped, full, rtol=2e-4, atol=2e-5)


def test_self_attention_kv_cache_sliding_window_rollover():
    """Past capacity, the cache must keep the MOST RECENT window (evict the
    oldest), matching windowed full attention over the last L tokens."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer)

    L = 4
    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(Adam(learning_rate=1e-3)).activation("identity")
            .list()
            .layer(SelfAttentionLayer(n_in=8, n_out=8, num_heads=2,
                                      stream_max_length=L))
            .layer(RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                                  loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(7)
    T = 9
    x = rng.normal(size=(1, T, 8)).astype(np.float32)

    net.rnn_clear_previous_state()
    stepped = []
    for t in range(T):
        stepped.append(np.asarray(net.rnn_time_step(x[:, t:t + 1, :]))[:, 0])
    stepped = np.stack(stepped, axis=1)

    # oracle: token t attends over the last min(t+1, L) tokens only
    for t in range(T):
        lo = max(0, t - L + 1)
        window = x[:, lo:t + 1, :]
        want = np.asarray(net.output(window))[:, -1]
        np.testing.assert_allclose(stepped[:, t], want, rtol=2e-4, atol=2e-5,
                                   err_msg=f"token {t}")


def test_self_attention_kv_cache_chunked_rollover():
    """Multi-token chunks that roll the cache past capacity must still give
    every query its exact (p - L, p] window — the chunk's writes may not
    evict keys its own earlier queries should see (round-3 advisor finding:
    write-after-attend)."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer)

    L, C, T = 4, 3, 9          # capacity 4, chunk width 3, stream length 9
    conf = (NeuralNetConfiguration.builder().seed(9)
            .updater(Adam(learning_rate=1e-3)).activation("identity")
            .list()
            .layer(SelfAttentionLayer(n_in=8, n_out=8, num_heads=2,
                                      stream_max_length=L))
            .layer(RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                                  loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(11)
    x = rng.normal(size=(2, T, 8)).astype(np.float32)

    net.rnn_clear_previous_state()
    chunks = []
    for s in range(0, T, C):
        chunks.append(np.asarray(net.rnn_time_step(x[:, s:s + C, :])))
    got = np.concatenate(chunks, axis=1)

    # oracle: token t attends over the last min(t+1, L) tokens only
    for t in range(T):
        lo = max(0, t - L + 1)
        want = np.asarray(net.output(x[:, lo:t + 1, :]))[:, -1]
        np.testing.assert_allclose(got[:, t], want, rtol=2e-4, atol=2e-5,
                                   err_msg=f"token {t}")


def test_self_attention_kv_cache_per_example_key_masks():
    """Streaming with key masks that DIFFER across the batch: each example's
    padded tokens must be invisible to that example only (round-3 advisor
    finding: no min-collapse across the batch)."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer)

    conf = (NeuralNetConfiguration.builder().seed(13)
            .updater(Adam(learning_rate=1e-3)).activation("identity")
            .list()
            .layer(SelfAttentionLayer(n_in=8, n_out=8, num_heads=2,
                                      stream_max_length=16))
            .layer(RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                                  loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    impl = net.impls[0]
    rng = np.random.default_rng(17)
    b, T = 3, 6
    x = jnp.asarray(rng.normal(size=(b, T, 8)), jnp.float32)
    # example 0: all real; example 1: last two padded; example 2: middle padded
    mask = jnp.asarray([[1, 1, 1, 1, 1, 1],
                        [1, 1, 1, 1, 0, 0],
                        [1, 1, 0, 0, 1, 1]], jnp.float32)
    params, state = net.params["0"], net.states["0"]

    def run(xs, m):
        ctx = {"rnn_state_in": {0: impl.init_stream_state(xs.shape[0])}}
        y, _ = impl.forward(params, state, xs, train=False, rng=None,
                            mask=m, ctx=ctx)
        return np.asarray(y)

    got = run(x, mask)
    for i in range(b):
        want = run(x[i:i + 1], mask[i:i + 1])
        np.testing.assert_allclose(got[i:i + 1], want, rtol=2e-4, atol=2e-5,
                                   err_msg=f"example {i}")


@pytest.mark.parametrize("causal", [True, False])
def test_flash_key_mask_matches_dense(causal):
    """Key-padding masks stream through the flash kernels (round-3 VERDICT
    item 5): forward must equal the dense masked oracle."""
    q, k, v = _qkv(b=2, T=256, h=2, d=32, seed=21)
    rng = np.random.default_rng(22)
    km = jnp.asarray((rng.random((2, 256)) > 0.3).astype(np.float32))
    got = fa.flash_attention(q, k, v, causal=causal, key_mask=km)

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(32.0)
    vis = km[:, None, None, :] > 0
    if causal:
        vis = vis & jnp.tril(jnp.ones((256, 256), bool))[None, None]
    s = jnp.where(vis, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_flash_key_mask_grads_match_dense():
    q, k, v = _qkv(b=1, T=256, h=1, d=16, seed=23)
    rng = np.random.default_rng(24)
    km = jnp.asarray((rng.random((1, 256)) > 0.25).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          key_mask=km) ** 2)

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(16.0)
        vis = (km[:, None, None, :] > 0) & \
            jnp.tril(jnp.ones((256, 256), bool))[None, None]
        p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_pick_block_contract():
    """pick_blocks: per kernel the largest tile of 128-multiples that divide
    Tq and Tk, no larger than the swept edges, whose step fits VMEM_LIMIT by
    the module's own reckoning; the floor is 128 × 128. (The edges are no
    knob: they follow from lengths, head size and operand dtype.)"""
    bf16, f32 = jnp.bfloat16, jnp.float32
    kernels = ("flash_fwd", "flash_dq", "flash_dkv")
    pick = lambda *a: [fa.pick_blocks(k, *a) for k in kernels]
    assert pick(8192, 8192, 64, bf16) == [(1024, 1024)] * 3
    assert pick(4224, 4224, 64, bf16) == [(384, 384)] * 3   # 33*128: 384|
    assert pick(4352, 4352, 64, bf16) == [(256, 256)] * 3   # 34*128: 256|
    # VMEM: f32 operands at d 256 leave the forward its tile and cut the
    # backward kernels' (four [bq, bk] f32 intermediates, wider tiles)
    assert pick(8192, 8192, 256, f32) == [(1024, 1024), (1024, 512),
                                          (1024, 512)]
    assert pick(8192, 8192, 256, bf16) == [(1024, 1024)] * 3
    assert pick(256, 256, 64, bf16) == [(256, 256)] * 3     # T under the edge
    assert pick(128, 128, 64, bf16) == [(128, 128)] * 3
    # the reckoning is what bounds, not the edge: every pick fits the limit,
    # and a smaller limit cuts the backward kernels first
    for k, (bq, bk) in zip(kernels, pick(8192, 8192, 256, f32)):
        assert fa.vmem_bytes(k, bq, bk, 256, f32) <= fa.VMEM_LIMIT
    assert fa.vmem_bytes("flash_dq", 1024, 1024, 256, f32) > fa.VMEM_LIMIT
    old = fa.VMEM_LIMIT
    try:
        fa.VMEM_LIMIT = 16 * 2 ** 20
        assert pick(4096, 4096, 128, bf16) == [(1024, 1024), (1024, 512),
                                               (1024, 512)]
        fa.VMEM_LIMIT = 2 ** 20
        assert pick(4096, 4096, 128, bf16) == [(128, 128)] * 3  # the floor
    finally:
        fa.VMEM_LIMIT = old
    assert pick(768 * 4, 768 * 4, 64, bf16) == [(1024, 1024)] * 3
    # q and k are tiled apart (the ring's shard against a longer k/v block)
    assert pick(256, 4096, 128, bf16) == [(256, 1024)] * 3
    assert pick(4096, 512, 128, bf16) == [(1024, 512)] * 3
    # the looped-LM cell's shape gives the swept edges
    assert pick(4096, 4096, 128, bf16) == [(1024, 1024)] * 3


# ------------------------------------------------- tilings and the causal walk
def _dense_bh(q, k, v, causal, km=None, keep=None, rate=0.0):
    """Dense attention on the kernels' own [bh, T, d] layout, with their
    conventions: causal on local positions, ``km`` [bh, Tk] hides keys,
    ``keep`` [bh, Tq, Tk] drops normalized probabilities, a row with no
    visible key outputs 0."""
    s = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    vis = jnp.ones(s.shape, bool)
    if causal:
        vis &= jnp.tril(jnp.ones(s.shape[1:], bool))[None]
    if km is not None:
        vis &= km[:, None, :] > 0
    p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1)
    p = jnp.where(jnp.any(vis, axis=-1, keepdims=True), p, 0.0)
    if keep is not None:
        p = p * keep / (1.0 - rate)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _kernels_out_and_grads(q, k, v, km8, seed, causal, rate, block_q,
                           block_k):
    """(o, dq, dk, dv) of loss = sum(o²) through the three kernels at the
    given edges."""
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    kw = dict(block_q=block_q, block_k=block_k)
    o, lse = fa._fwd(q, k, v, km8, seed, causal, scale, rate, **kw)
    do = 2.0 * o
    delta = fa.rowwise_delta(do, o)
    dq = fa.dq_block(q, k, v, km8, do, delta, lse, causal, scale, seed, rate,
                     **kw)
    dk, dv = fa.dkv_block(q, k, v, km8, do, delta, lse, causal, scale, seed,
                          rate, **kw)
    return o, dq, dk, dv


def _bh_inputs(bh, Tq, Tk, d, seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(bh, Tq, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(bh, Tk, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(bh, Tk, d)), jnp.float32))


@pytest.mark.parametrize("variant", ["plain", "key_mask", "dropout"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256),
                                             (256, 128), (256, 256)])
def test_every_tiling_matches_the_dense_oracle(block_q, block_k, causal,
                                               variant):
    """Forward and the three gradients on multi-block grids at T 512,
    q and k edges apart: whatever the edges, the walk (visible pairs only,
    the mask on diagonal blocks only) computes dense attention. Under
    dropout the decisions hash global positions: on inputs whose arithmetic
    is exact (q = 0, integer v, rate 1/2) the output is bit-equal to
    another tiling's."""
    bh, T, d, rate = 2, 512, 32, 0.5
    q, k, v = _bh_inputs(bh, T, T, d, seed=41)
    km = km8 = seed = keep = None
    if variant == "key_mask":
        km = np.ones((bh, T), np.float32)
        km[0, :200] = 0.0          # a whole block and a half: rows left blind
        km[1, 300::3] = 0.0
        km = jnp.asarray(km)
        km8 = jnp.broadcast_to(km[..., None], (bh, T, 8))
    if variant == "dropout":
        seed = fa.seed3(1234)
        keep = fa.dropout_keep_mask(bh, T, T, 1234, rate)
    use_rate = rate if variant == "dropout" else 0.0
    got = _kernels_out_and_grads(q, k, v, km8, seed, causal, use_rate,
                                 block_q, block_k)
    loss = lambda q, k, v: jnp.sum(
        _dense_bh(q, k, v, causal, km, keep, rate) ** 2)
    want = (_dense_bh(q, k, v, causal, km, keep, rate),
            *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
    for name, a, w in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-3,
                                   atol=2e-4, err_msg=name)
    if variant == "dropout":
        exact_v = jnp.round(v * 4.0)
        run = lambda bq, bk: np.asarray(fa._fwd(
            jnp.zeros_like(q), k, exact_v, None, seed, causal, 1.0, rate,
            block_q=bq, block_k=bk)[0])
        np.testing.assert_array_equal(run(block_q, block_k), run(512, 512))


@pytest.mark.parametrize("Tq,Tk,block_q,block_k,causal", [
    (256, 512, 128, 256, False), (512, 256, 256, 128, False),
    (256, 512, 128, 256, True), (512, 256, 256, 128, True),
    (256, 512, None, None, True)])
def test_backward_blocks_at_unequal_lengths_and_edges(Tq, Tk, block_q,
                                                      block_k, causal):
    """dq_block / dkv_block on a q shard and a k/v block of different
    lengths, each tiled by its own edge (the ring's use; no gcd of the two
    lengths). Causal with Tk > Tq the k blocks past the last query see no
    query and their dk, dv are zeros, written all the same."""
    q, k, v = _bh_inputs(2, Tq, Tk, 32, seed=43)
    got = _kernels_out_and_grads(q, k, v, None, None, causal, 0.0, block_q,
                                 block_k)
    loss = lambda q, k, v: jnp.sum(_dense_bh(q, k, v, causal) ** 2)
    want = (_dense_bh(q, k, v, causal),
            *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
    for name, a, w in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-3,
                                   atol=2e-4, err_msg=name)
    if causal and Tk > Tq:
        np.testing.assert_array_equal(np.asarray(got[2][:, Tq:]), 0.0)
        np.testing.assert_array_equal(np.asarray(got[3][:, Tq:]), 0.0)


@pytest.mark.parametrize("nq,nk,block_q,block_k", [
    (4, 4, 128, 128), (8, 2, 128, 512), (2, 8, 512, 128), (3, 6, 256, 128),
    (4, 1, 128, 512)])
@pytest.mark.parametrize("k_major", [False, True])
def test_causal_pair_table_holds_exactly_the_visible_pairs(nq, nk, block_q,
                                                           block_k, k_major):
    """One grid step per visible (q block, k block) pair and no other, rows
    in order, each row's first and last step marked."""
    outer, inner, first, last = fa.causal_pairs(nq, nk, block_q, block_k,
                                                k_major=k_major)
    visible = {(qi, kj) for qi in range(nq) for kj in range(nk)
               if kj * block_k <= (qi + 1) * block_q - 1}
    pairs = list(zip(outer.tolist(), inner.tolist()))
    as_qk = [(i, o) for o, i in pairs] if k_major else pairs
    assert len(set(as_qk)) == len(as_qk)
    assert set(as_qk) == visible          # Tq == Tk: every row sees a pair
    assert pairs == sorted(pairs)
    for t, (o, _) in enumerate(pairs):
        assert first[t] == (t == 0 or pairs[t - 1][0] != o)
        assert last[t] == (t == len(pairs) - 1 or pairs[t + 1][0] != o)
    assert sorted(set(outer.tolist())) == list(range(nk if k_major else nq))
    assert all(a.dtype == np.int32 for a in (outer, inner, first, last))


def test_a_k_block_past_the_last_query_keeps_one_masked_step():
    """Tk > Tq, k-major: k blocks 2 and 3 start after the last query; each
    keeps the last q block as its only step (the diagonal's mask hides all
    of it), so its zeros are written."""
    outer, inner, first, last = fa.causal_pairs(2, 4, 128, 128, k_major=True)
    assert list(zip(outer.tolist(), inner.tolist())) == [
        (0, 0), (0, 1), (1, 1), (2, 1), (3, 1)]
    assert first.tolist() == [1, 0, 1, 1, 1]
    assert last.tolist() == [0, 1, 1, 1, 1]


def test_kernel_names_and_gauge_carry_the_edges():
    """The tiling that engaged is in the kernels' names (the device trace's
    op names) and in ``flash_grid_steps{kernel}``: steps per call, causal
    ones counting visible pairs only."""
    from deeplearning4j_tpu.monitor import get_registry

    sds = jax.ShapeDtypeStruct((2, 512, 32), jnp.float32)
    st = jax.ShapeDtypeStruct((2, 512, 8), jnp.float32)

    def three(q, k, v, do, delta, lse):
        scale, kw = 0.25, dict(block_q=128, block_k=256)
        return (fa._fwd(q, k, v, None, None, True, scale, 0.0, **kw),
                fa.dq_block(q, k, v, None, do, delta, lse, True, scale, **kw),
                fa.dkv_block(q, k, v, None, do, delta, lse, False, scale,
                             **kw))

    jaxpr = str(jax.make_jaxpr(three)(sds, sds, sds, sds, st, st))
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"{kernel}_q128_k256" in jaxpr
    steps = {r["labels"]["kernel"]: r["value"]
             for r in get_registry().snapshot()["flash_grid_steps"]}
    # visible pairs of 4 q blocks of 128 over 2 k blocks of 256: 1+1+2+2
    assert steps["flash_fwd_q128_k256"] == 2 * 6
    assert steps["flash_dq_q128_k256"] == 2 * 6
    assert steps["flash_dkv_q128_k256"] == 2 * 2 * 4      # not causal: all
    # the chooser's own edges reach the name the same way
    fa._fwd(*_bh_inputs(1, 256, 256, 16, seed=0), None, None, True, 0.25, 0.0)
    bq, bk = fa.pick_blocks("flash_fwd", 256, 256, 16, jnp.float32)
    assert (bq, bk) == (256, 256)
    steps = {r["labels"]["kernel"]: r["value"]
             for r in get_registry().snapshot()["flash_grid_steps"]}
    assert steps["flash_fwd_q256_k256"] == 1


def test_flash_fully_masked_rows_zero():
    """A query row whose visible keys are ALL masked outputs 0 with zero
    gradients — the framework-wide convention (found on first hardware run:
    perf_flash_check r5 max-err 0.306 came entirely from causal row 0 when
    key 0 was padding; the kernel averaged block 0, the dense ref averaged
    all T — both garbage). Kernel and dense mha path must agree."""
    q, k, v = _qkv(b=2, T=256, h=2, d=32, seed=31)
    km = np.ones((2, 256), np.float32)
    km[0, 0] = 0.0          # batch 0: causal row 0 sees only masked keys
    km[1, :2] = 0.0         # batch 1: causal rows 0 AND 1 fully masked
    km = jnp.asarray(km)

    got = fa.flash_attention(q, k, v, causal=True, key_mask=km)
    # the convention itself: fully-masked rows are exactly 0
    np.testing.assert_array_equal(np.asarray(got[0, 0]), 0.0)
    np.testing.assert_array_equal(np.asarray(got[1, :2]), 0.0)

    # oracle with the same convention: remaining rows still match dense
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(32.0)
    vis = (km[:, None, None, :] > 0) & \
        jnp.tril(jnp.ones((256, 256), bool))[None, None]
    p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1)
    p = jnp.where(jnp.any(vis, axis=-1, keepdims=True), p, 0.0)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    # dense mha path applies the same convention: T=100 is not
    # block-divisible, so supported() is False and mha truly takes
    # _dense_attention (T=256 here would route to flash under the
    # interpret fixture's min_seq = 2*MIN_BLOCK = 256)
    qd, kd, vd = (a[:, :100] for a in (q, k, v))
    got_dense = mha(qd, kd, vd, True, jnp.float32, key_mask=km[:, :100])
    np.testing.assert_array_equal(np.asarray(got_dense[0, 0]), 0.0)
    np.testing.assert_array_equal(np.asarray(got_dense[1, :2]), 0.0)
    got_flash_trunc = fa.flash_attention(
        jnp.pad(qd, ((0, 0), (0, 156), (0, 0), (0, 0))),
        jnp.pad(kd, ((0, 0), (0, 156), (0, 0), (0, 0))),
        jnp.pad(vd, ((0, 0), (0, 156), (0, 0), (0, 0))), causal=True,
        key_mask=jnp.pad(km[:, :100], ((0, 0), (0, 156))))[:, :100]
    np.testing.assert_allclose(np.asarray(got_dense),
                               np.asarray(got_flash_trunc),
                               rtol=2e-4, atol=2e-5)

    # gradients: finite everywhere, exactly 0 into the dead rows' queries
    gq, gk, gv = jax.grad(
        lambda a, b_, c: jnp.sum(fa.flash_attention(
            a, b_, c, causal=True, key_mask=km) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_array_equal(np.asarray(gq[0, 0]), 0.0)
    np.testing.assert_array_equal(np.asarray(gq[1, :2]), 0.0)

    # and the masked key's k/v receive no gradient through dead rows only —
    # cross-check full grads against the zero-convention dense oracle
    gqd, gkd, gvd = jax.grad(
        lambda a, b_, c: jnp.sum(jnp.einsum(
            "bhqk,bkhd->bqhd",
            jnp.where(jnp.any(vis, axis=-1, keepdims=True), jax.nn.softmax(
                jnp.where(vis, jnp.einsum("bqhd,bkhd->bhqk", a, b_)
                          / jnp.sqrt(32.0), -1e30), axis=-1), 0.0),
            c) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((gq, gk, gv), (gqd, gkd, gvd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_mha_routes_masked_to_flash(monkeypatch):
    """supported() accepts a [b, T] array mask; mha with such a mask on a
    block-divisible sequence must ACTUALLY take the flash path (spied) and
    still match the dense masked computation."""
    assert fa.supported(256, 64, 0.0, np.ones((2, 256), np.float32))
    assert not fa.supported(256, 64, 0.0, object())   # not a [b, T] array
    q, k, v = _qkv(b=2, T=256, h=2, d=32, seed=25)
    rng = np.random.default_rng(26)
    km = jnp.asarray((rng.random((2, 256)) > 0.4).astype(np.float32))
    calls = []
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    got = mha(q, k, v, True, jnp.float32, key_mask=km)
    assert calls, "masked mha fell back to the dense path"
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(32.0)
    vis = (km[:, None, None, :] > 0) & \
        jnp.tril(jnp.ones((256, 256), bool))[None, None]
    p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_ring_flash_matches_full_attention():
    """Flash kernel INSIDE the ring schedule (round-3 VERDICT item 5): the
    sp path == dense full attention, forward and gradients, on a 4-device
    sequence mesh."""
    from deeplearning4j_tpu.parallel import (ring_flash_attention,
                                             ring_flash_supported,
                                             full_attention, make_mesh,
                                             SEQUENCE_AXIS)

    assert ring_flash_supported(4 * 128, 4, 32)
    assert not ring_flash_supported(4 * 100, 4, 32)   # shard not 128-divisible
    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    rng = np.random.default_rng(31)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 4 * 128, 2, 32)), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        got = ring_flash_attention(q, k, v, mesh, causal=causal)
        want = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def loss_ring(q, k, v):
        return jnp.sum(ring_flash_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_sequence_parallel_net_step_matches_unsharded():
    """Container-level sequence parallelism (sequence_parallel_step): the
    TIME-sharded net step — ring(-flash) attention inside shard_map,
    psum-reduced time-sliced gradients, replicated-reg correction — must
    equal the unsharded step's loss AND updated params (completes container
    integration for the last of the five mesh axes)."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer, DenseLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)

    def make(l2=1e-3):
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Adam(learning_rate=1e-3))
                .activation("identity").l2(l2).list()
                .layer(SelfAttentionLayer(n_in=16, n_out=16, num_heads=2,
                                          causal=True))
                .layer(DenseLayer(n_in=16, n_out=16, activation="relu"))
                .layer(RnnOutputLayer(n_in=16, n_out=4, activation="softmax",
                                      loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    rng = np.random.default_rng(0)
    T = 4 * 128                      # local shard 128 → flash-in-ring path
    f = rng.normal(size=(2, T, 16)).astype(np.float32)
    l = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (2, T))].astype(
        np.float32)

    net_a = make()
    step, place = sequence_parallel_step(net_a, mesh)
    place(net_a)
    pa, _, _, loss_a = step(net_a.params, net_a.states, net_a.updater_state,
                            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                            jnp.asarray(f), jnp.asarray(l))
    net_b = make()
    raw = jax.jit(net_b._raw_step(False))
    pb, _, _, loss_b = raw(net_b.params, net_b.states, net_b.updater_state,
                           jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                           jnp.asarray(f), jnp.asarray(l), None, None)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-4)


def test_sequence_parallel_step_rejects_recurrent_and_aux():
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Sgd
    from deeplearning4j_tpu.nn.conf.layers import (LSTM, RnnOutputLayer,
                                                   MoEDenseLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(learning_rate=0.1)).list()
            .layer(LSTM(n_in=4, n_out=8, activation="tanh"))
            .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax",
                                  loss="mcxent"))
            .build())
    with pytest.raises(ValueError, match="time-recurrent"):
        sequence_parallel_step(MultiLayerNetwork(conf).init(), mesh)

    conf2 = (NeuralNetConfiguration.builder().seed(1)
             .updater(Sgd(learning_rate=0.1)).activation("identity").list()
             .layer(MoEDenseLayer(n_in=4, n_out=8, num_experts=4, top_k=2,
                                  aux_loss_weight=1e-2))
             .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax",
                                   loss="mcxent"))
             .build())
    with pytest.raises(ValueError, match="aux"):
        sequence_parallel_step(MultiLayerNetwork(conf2).init(), mesh)


def test_sequence_parallel_flag_does_not_leak_to_dense_paths():
    """sequence_parallel_step's routing flag is trace-scoped: after building
    and running the sp step, output()/score() on the same net must use the
    normal dense path, not crash on an unbound axis (review finding)."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)
    from deeplearning4j_tpu import DataSet

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=1e-3)).activation("identity").list()
            .layer(SelfAttentionLayer(n_in=8, n_out=8, num_heads=2,
                                      causal=True))
            .layer(RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                                  loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    step, place = sequence_parallel_step(net, mesh)
    place(net)
    rng = np.random.default_rng(9)
    f = rng.normal(size=(2, 4 * 128, 8)).astype(np.float32)
    l = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, 4 * 128))].astype(
        np.float32)
    net.params, net.states, net.updater_state, _ = step(
        net.params, net.states, net.updater_state, jnp.asarray(0, jnp.int32),
        jax.random.PRNGKey(0), jnp.asarray(f), jnp.asarray(l))
    # dense-path entry points after sp training: must work unchanged
    out = np.asarray(net.output(f[:, :64]))
    assert out.shape == (2, 64, 3) and np.isfinite(out).all()
    assert np.isfinite(float(net.score(DataSet(f[:, :64], l[:, :64]))))


def test_sequence_parallel_step_rejects_activation_dropout():
    """Per-layer ACTIVATION dropout stays rejected (replicated rng would
    draw the same mask on every shard); attention-probability dropout on
    SelfAttentionLayer is allowed — it rides the ring-flash kernels."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Sgd
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer, DenseLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(learning_rate=0.1)).activation("identity").list()
            .layer(SelfAttentionLayer(n_in=8, n_out=8, num_heads=2))
            .layer(DenseLayer(n_in=8, n_out=8, dropout=0.5))
            .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax",
                                  loss="mcxent"))
            .build())
    with pytest.raises(ValueError, match="activation dropout"):
        sequence_parallel_step(MultiLayerNetwork(conf).init(), mesh)


@pytest.mark.slow
def test_sequence_parallel_step_attention_dropout_matches_unsharded():
    """Attention-probability dropout through the ring: the sp step derives
    the same per-step seed as the unsharded flash path (replicated rng) and
    the ring kernels hash GLOBAL coordinates — so the sp masked step equals
    the unsharded dropout step exactly, and dropout is genuinely active."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)

    def make(rate):
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Adam(learning_rate=1e-3)).activation("identity")
                .list()
                .layer(SelfAttentionLayer(n_in=16, n_out=16, num_heads=2,
                                          causal=True, dropout_rate=rate))
                .layer(RnnOutputLayer(n_in=16, n_out=4, activation="softmax",
                                      loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    rng = np.random.default_rng(3)
    T = 4 * 128
    f = rng.normal(size=(2, T, 16)).astype(np.float32)
    l = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (2, T))].astype(
        np.float32)

    net_a = make(0.3)
    step, place = sequence_parallel_step(net_a, mesh)
    place(net_a)
    pa, _, _, loss_a = step(net_a.params, net_a.states, net_a.updater_state,
                            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                            jnp.asarray(f), jnp.asarray(l))
    net_b = make(0.3)
    raw = jax.jit(net_b._raw_step(False))
    pb, _, _, loss_b = raw(net_b.params, net_b.states, net_b.updater_state,
                           jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                           jnp.asarray(f), jnp.asarray(l), None, None)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-4)

    # dropout is ACTIVE on the sp path: rate 0 gives a different loss
    net_c = make(0.0)
    step_c, place_c = sequence_parallel_step(net_c, mesh)
    place_c(net_c)
    _, _, _, loss_c = step_c(net_c.params, net_c.states, net_c.updater_state,
                             jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                             jnp.asarray(f), jnp.asarray(l))
    assert abs(float(loss_c) - float(loss_a)) > 1e-6


def test_sequence_parallel_step_dp_sp_composition():
    """DP×SP: batch over 'data', time over 'sequence' — psum over time ×
    pmean over batch must equal the unsharded step exactly (incl. l2)."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer, DenseLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)

    def make():
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Adam(learning_rate=1e-3))
                .activation("identity").l2(1e-3).list()
                .layer(SelfAttentionLayer(n_in=16, n_out=16, num_heads=2,
                                          causal=True))
                .layer(DenseLayer(n_in=16, n_out=16, activation="relu"))
                .layer(RnnOutputLayer(n_in=16, n_out=4, activation="softmax",
                                      loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    mesh = make_mesh(jax.devices(), axes=("data", SEQUENCE_AXIS),
                     shape=(2, 4))
    rng = np.random.default_rng(0)
    T = 4 * 128
    f = rng.normal(size=(4, T, 16)).astype(np.float32)
    l = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (4, T))].astype(
        np.float32)

    net_a = make()
    step, place = sequence_parallel_step(net_a, mesh, data_axis="data")
    place(net_a)
    pa, _, _, loss_a = step(net_a.params, net_a.states, net_a.updater_state,
                            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                            jnp.asarray(f), jnp.asarray(l))
    net_b = make()
    raw = jax.jit(net_b._raw_step(False))
    pb, _, _, loss_b = raw(net_b.params, net_b.states, net_b.updater_state,
                           jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                           jnp.asarray(f), jnp.asarray(l), None, None)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-4)


def test_sequence_parallel_step_computation_graph():
    """sequence_parallel_step on a ComputationGraph (tuple streams, vertex
    validation/reg by name): sp step == unsharded step, incl. l2."""
    from deeplearning4j_tpu import NeuralNetConfiguration, Adam
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer, DenseLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)

    def make():
        g = (NeuralNetConfiguration.builder().seed(5)
             .updater(Adam(learning_rate=1e-3))
             .activation("identity").l2(1e-3).graph_builder()
             .add_inputs("in"))
        g.add_layer("attn", SelfAttentionLayer(n_in=16, n_out=16, num_heads=2,
                                               causal=True), "in")
        g.add_layer("ff", DenseLayer(n_in=16, n_out=16, activation="relu"),
                    "attn")
        g.add_layer("out", RnnOutputLayer(n_in=16, n_out=4,
                                          activation="softmax",
                                          loss="mcxent"), "ff")
        g.set_outputs("out")
        return ComputationGraph(g.build()).init()

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    rng = np.random.default_rng(0)
    T = 4 * 128
    f = rng.normal(size=(2, T, 16)).astype(np.float32)
    l = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (2, T))].astype(
        np.float32)

    net_a = make()
    step, place = sequence_parallel_step(net_a, mesh)
    place(net_a)
    pa, _, _, loss_a = step(net_a.params, net_a.states, net_a.updater_state,
                            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                            (jnp.asarray(f),), (jnp.asarray(l),))
    net_b = make()
    raw = jax.jit(net_b._raw_step())
    pb, _, _, loss_b = raw(net_b.params, net_b.states, net_b.updater_state,
                           jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                           (jnp.asarray(f),), (jnp.asarray(l),), None, None)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-4)


@pytest.mark.slow
def test_ulysses_flash_matches_full_attention():
    """Ulysses layout + ONE local flash kernel over the gathered sequence
    == dense full attention (fwd AND grads): the sp path's preferred
    dropout-free impl (2 all_to_alls instead of n ring launches)."""
    from deeplearning4j_tpu.parallel import (ulysses_flash_attention,
                                             make_mesh, SEQUENCE_AXIS)
    from deeplearning4j_tpu.parallel.sequence import full_attention

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    rng = np.random.default_rng(5)
    b, T, h, d = 2, 4 * 128, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, T, h, d)), jnp.float32)
               for _ in range(3))
    for causal in (True, False):
        out = ulysses_flash_attention(q, k, v, mesh, causal=causal)
        ref = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg=f"causal={causal}")

    def loss_u(q, k, v):
        return jnp.sum(ulysses_flash_attention(q, k, v, mesh,
                                               causal=True) ** 2)

    def loss_f(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    for a, b2 in zip(gu, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   rtol=5e-3, atol=5e-4)


def test_sp_attend_routes_ulysses_when_heads_divide(monkeypatch):
    """sp step routing: heads divisible by the axis → Ulysses-flash (spied);
    dropout or indivisible heads → ring; and the Ulysses-routed sp step
    still equals the unsharded step."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Adam
    from deeplearning4j_tpu.nn.conf.layers import (SelfAttentionLayer,
                                                   RnnOutputLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)
    import deeplearning4j_tpu.parallel.sequence as seq

    calls = []
    real = seq._ulysses_flash_inner
    monkeypatch.setattr(seq, "_ulysses_flash_inner",
                        lambda *a, **k: (calls.append(1) or real(*a, **k)))

    def make(heads):
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Adam(learning_rate=1e-3)).activation("identity")
                .list()
                .layer(SelfAttentionLayer(n_in=16, n_out=16,
                                          num_heads=heads, causal=True))
                .layer(RnnOutputLayer(n_in=16, n_out=4,
                                      activation="softmax", loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    rng = np.random.default_rng(3)
    T = 4 * 128
    f = rng.normal(size=(2, T, 16)).astype(np.float32)
    l = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (2, T))].astype(
        np.float32)

    net_a = make(heads=4)            # 4 % 4 == 0 → ulysses
    step, place = sequence_parallel_step(net_a, mesh)
    place(net_a)
    pa, _, _, loss_a = step(net_a.params, net_a.states, net_a.updater_state,
                            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                            jnp.asarray(f), jnp.asarray(l))
    assert calls, "heads%axis==0 did not route through ulysses-flash"

    net_b = make(heads=4)
    raw = jax.jit(net_b._raw_step(False))
    pb, _, _, loss_b = raw(net_b.params, net_b.states, net_b.updater_state,
                           jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                           jnp.asarray(f), jnp.asarray(l), None, None)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-4)
    for a, b2 in zip(jax.tree_util.tree_leaves(pa),
                     jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   rtol=3e-3, atol=3e-4)

    # heads NOT divisible → ring path, no new ulysses calls
    calls.clear()
    net_c = make(heads=2)            # 2 % 4 != 0
    step_c, place_c = sequence_parallel_step(net_c, mesh)
    place_c(net_c)
    step_c(net_c.params, net_c.states, net_c.updater_state,
           jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
           jnp.asarray(f), jnp.asarray(l))
    assert not calls, "indivisible heads should stay on the ring"


@pytest.mark.slow
def test_sequence_parallel_transformer_lm_matches_unsharded():
    """The flagship composition: TransformerLM (pre-LN residual CG with
    [b, T] token-id input) trains through sequence_parallel_step — the
    rank-2 id stream is recognized as temporal (EmbeddingSequenceLayer
    consumer) and sharded on its time dim; loss + params equal the
    unsharded step."""
    from deeplearning4j_tpu.models import TransformerLM
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)

    def make():
        return TransformerLM(vocab_size=12, embed_dim=16, num_heads=4,
                             num_blocks=2, seed=9).init()

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    rng = np.random.default_rng(4)
    T = 4 * 128
    ids = jnp.asarray(rng.integers(0, 12, size=(2, T)), jnp.float32)
    l = jnp.asarray(np.eye(12, dtype=np.float32)[
        rng.integers(0, 12, (2, T))])

    net_a = make()
    step, place = sequence_parallel_step(net_a, mesh)
    place(net_a)
    pa, _, _, loss_a = step(net_a.params, net_a.states, net_a.updater_state,
                            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                            (ids,), (l,))
    net_b = make()
    raw = jax.jit(net_b._raw_step(False))
    pb, _, _, loss_b = raw(net_b.params, net_b.states, net_b.updater_state,
                           jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                           (ids,), (l,), None, None)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-4)
    for a, b2 in zip(jax.tree_util.tree_leaves(pa),
                     jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   rtol=3e-3, atol=3e-4)


def test_sequence_parallel_step_rejects_batchnorm():
    """BatchNormalization's train-time statistics reduce over batch AND time;
    a time shard would normalize with shard-local mean/var and silently
    diverge — the sp step must reject it loudly (review finding)."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Sgd
    from deeplearning4j_tpu.nn.conf.layers import (BatchNormalization,
                                                   DenseLayer, RnnOutputLayer)
    from deeplearning4j_tpu.parallel import (sequence_parallel_step, make_mesh,
                                             SEQUENCE_AXIS)

    mesh = make_mesh(jax.devices()[:4], axes=(SEQUENCE_AXIS,))
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(learning_rate=0.1)).activation("tanh").list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(BatchNormalization(n_in=8, n_out=8))
            .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax",
                                  loss="mcxent"))
            .build())
    with pytest.raises(ValueError, match="statistics"):
        sequence_parallel_step(MultiLayerNetwork(conf).init(), mesh)


def test_flash_bf16_matches_dense_bf16():
    """Mixed-precision path: bf16 q/k/v run SOURCE-dtype matmuls in the
    kernels (native MXU pass) with f32 softmax/accumulation — parity with a
    dense oracle computed from the same bf16 inputs, fwd and grads, to
    bf16-class tolerance."""
    q, k, v = _qkv(b=2, T=256, h=2, d=32, seed=9)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    out = fa.flash_attention(qb, kb, vb, causal=True)
    want = _dense_ref(qb.astype(jnp.float32), kb.astype(jnp.float32),
                      vb.astype(jnp.float32), True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_ref(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(qb, kb, vb)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=6e-2, atol=6e-2)


# ------------------------------------------- a value head size of its own
def _qk_v(b, T, h, d, d_v, seed):
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.normal(size=(b, T, h, d)), jnp.float32)
            for _ in range(2))
    return q, k, jnp.asarray(rng.normal(size=(b, T, h, d_v)), jnp.float32)


@pytest.mark.parametrize("variant", ["plain", "key_mask"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d,d_v", [(48, 32), (24, 40)])
def test_value_heads_of_their_own_size_match_the_dense_oracle(d, d_v, causal,
                                                              variant):
    """q and k at one head size, v (and o, do, dv) at another, through the
    three kernels at multi-block grids against ``_dense_attention``: loss
    and every gradient (latent attention's 192 / 128 is such a pair)."""
    from deeplearning4j_tpu.nn.layers.attention import _dense_attention
    b, T, h = 1, 256, 2
    q, k, v = _qk_v(b, T, h, d, d_v, seed=11)
    key_mask = None
    if variant == "key_mask":
        key_mask = jnp.asarray(np.r_[np.ones(200), np.zeros(56)][None],
                               jnp.float32)
    w = jnp.asarray(np.random.default_rng(12).normal(size=(b, T, h, d_v)),
                    jnp.float32)
    visible = jnp.tril(jnp.ones((T, T), bool))[None, None] if causal else None
    if key_mask is not None:
        km = key_mask[:, None, None, :] > 0
        visible = km if visible is None else visible & km

    def through_kernels(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal, key_mask=key_mask)
        assert o.shape == (b, T, h, d_v)
        return jnp.sum(o * w)

    def dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, visible, jnp.float32) * w)

    got, grads = jax.value_and_grad(through_kernels, (0, 1, 2))(q, k, v)
    want, ref = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-4)
    for a, r in zip(grads, ref):
        assert a.shape == r.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-3,
                                   atol=2e-4)


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256),
                                             (256, 128)])
def test_backward_blocks_take_a_value_head_size_of_their_own(block_q,
                                                             block_k):
    """``dq_block`` / ``dkv_block`` at forced edges: dq and dk come at the
    head size of q and k, dv at the values'."""
    bh, T, d, d_v = 2, 256, 48, 32
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(size=(bh, T, d)), jnp.float32)
            for _ in range(2))
    v, do = (jnp.asarray(rng.normal(size=(bh, T, d_v)), jnp.float32)
             for _ in range(2))
    scale = d ** -0.5

    def dense(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.sum(jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)
                       * do)

    o, lse = fa._fwd(q, k, v, None, None, True, scale, 0.0, block_q=block_q,
                     block_k=block_k)
    assert o.shape == (bh, T, d_v)
    delta = fa.rowwise_delta(do, o)
    dq = fa.dq_block(q, k, v, None, do, delta, lse, True, scale,
                     block_q=block_q, block_k=block_k)
    dk, dv = fa.dkv_block(q, k, v, None, do, delta, lse, True, scale,
                          block_q=block_q, block_k=block_k)
    for got, want in zip((dq, dk, dv), jax.grad(dense, (0, 1, 2))(q, k, v)):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-4)


def test_the_chooser_and_the_gate_reckon_with_the_value_head_size():
    """``vmem_bytes`` at ``d_v`` None or equal to ``d`` is what it was; a
    wider value head takes more, a narrower one less; ``supported`` holds
    both sizes to 256; the kernels keep their names."""
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        same = fa.vmem_bytes(kernel, 1024, 1024, 128, jnp.bfloat16)
        assert fa.vmem_bytes(kernel, 1024, 1024, 128, jnp.bfloat16, 128) == same
        assert fa.vmem_bytes(kernel, 1024, 1024, 128, jnp.bfloat16, 256) > same
        assert fa.vmem_bytes(kernel, 1024, 1024, 256, jnp.bfloat16, 128) \
            < fa.vmem_bytes(kernel, 1024, 1024, 256, jnp.bfloat16)
        bq, bk = fa.pick_blocks(kernel, 8192, 8192, 192, jnp.bfloat16, 128)
        assert fa.vmem_bytes(kernel, bq, bk, 192, jnp.bfloat16, 128) \
            <= fa.VMEM_LIMIT
    assert fa.supported(256, 192, 0.0, None, 128)
    assert not fa.supported(256, 192, 0.0, None, 384)
    assert not fa.supported(256, 384, 0.0, None, 128)
    q, k, v = _qk_v(1, 256, 1, 48, 32, seed=1)
    from deeplearning4j_tpu.monitor import get_registry
    fa.flash_attention(q, k, v)
    kernels = {row["labels"]["kernel"]
               for row in get_registry().snapshot()["flash_grid_steps"]}
    assert any(name.startswith("flash_fwd_q") for name in kernels)


def test_latent_attention_is_a_layout_of_the_one_layer():
    """``SelfAttentionLayer`` with a latent rank: keys and values through one
    normed latent, the shared key channels broadcast over the heads, value
    heads of their own size, into the one ``mha``; against the equations
    written out."""
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.base import impl_for
    conf = NeuralNetConfiguration.builder().seed(3).list().build()
    h, N, R, Dv, rank, d = 2, 16, 8, 12, 10, 20
    layer = impl_for(SelfAttentionLayer(
        n_in=d, n_out=d, num_heads=h, kv_latent_rank=rank,
        qk_nope_head_dim=N, qk_rope_head_dim=R, v_head_dim=Dv,
        has_bias=False, activation="identity"), conf.global_conf)
    params, _ = layer.init(jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in params.items()} == {
        "Wq": (d, h * (N + R)), "Wkv_a": (d, rank + R), "gc": (rank,),
        "Wkv_b": (rank, h * (N + Dv)), "Wo": (h * Dv, d)}
    params["gc"] = params["gc"] * 1.3
    b, T = 2, 256                                   # the flash path
    x = jnp.asarray(np.random.default_rng(0).normal(size=(b, T, d)),
                    jnp.float32)

    def written_out(p, x):
        q = (x @ p["Wq"]).reshape(b, T, h, N + R)
        latent = x @ p["Wkv_a"]
        c = latent[..., :rank]
        c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-5) * p["gc"]
        kv = (c @ p["Wkv_b"]).reshape(b, T, h, N + Dv)
        k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
            latent[:, :, None, rank:], (b, T, h, R))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (N + R) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., N:])
        return o.reshape(b, T, h * Dv) @ p["Wo"]

    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    got, grads = jax.value_and_grad(loss(
        lambda p, x: layer.forward(p, {}, x)[0]), (0, 1))(params, x)
    want, ref = jax.value_and_grad(loss(written_out), (0, 1))(params, x)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    for a, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-3,
                                   atol=2e-4)
    # the streaming state holds keys and values at their own sizes
    k_c, v_c, _, _ = layer.init_stream_state(1)
    assert k_c.shape[-1] == N + R and v_c.shape[-1] == Dv

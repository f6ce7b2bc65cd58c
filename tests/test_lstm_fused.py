"""Fused two-layer LSTM kernel vs the composition of two single-layer
kernels (themselves pinned against the lax.scan oracle in
test_lstm_kernel.py) — the SURVEY §4.4 cross-validation pattern one level
up: fused path == per-layer path, forward AND gradients, then the
container-level routing (MultiLayerNetwork fuses eligible pairs and the
escape hatch restores the per-layer path)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeplearning4j_tpu.ops.flash_attention as fa
import deeplearning4j_tpu.ops.lstm_cell as lk
import deeplearning4j_tpu.ops.lstm_fused as lf


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = fa._FORCE_INTERPRET
    fa._FORCE_INTERPRET = True
    yield
    fa._FORCE_INTERPRET = old


def _pair_inputs(b=8, T=6, H=128, peep=False, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s) * 0.4, jnp.float32)
    xp1 = mk(b, T, 4 * H)
    rw1, w2, rw2 = mk(H, 4 * H) / 8, mk(H, 4 * H) / 8, mk(H, 4 * H) / 8
    b2 = mk(4 * H) * 0.1
    p1 = tuple(mk(H) * 0.3 for _ in range(3)) if peep else None
    p2 = tuple(mk(H) * 0.3 for _ in range(3)) if peep else None
    h01, c01, h02, c02 = (mk(b, H) * 0.2 for _ in range(4))
    return xp1, rw1, p1, w2, b2, rw2, p2, h01, c01, h02, c02


def _compose(xp1, rw1, p1, w2, b2, rw2, p2, h01, c01, h02, c02):
    """Per-layer reference: layer 1 kernel, hoisted xw2 gemm, layer 2
    kernel — exactly what the unfused container does: time-major between
    the two (``xp1`` carries layer 1's bias already, so its kernel gets a
    zero one; layer 2's ``b2`` goes to its kernel)."""
    ys1, (h1T, c1T) = lk.lstm_scan(
        jnp.swapaxes(xp1, 0, 1), jnp.zeros_like(b2), rw1, p1, h01, c01)
    T, b, H = ys1.shape
    xw2 = (ys1.astype(jnp.float32).reshape(T * b, H) @ w2
           ).reshape(T, b, 4 * H)
    ys2, (h2T, c2T) = lk.lstm_scan(xw2, b2, rw2, p2, h02, c02)
    return jnp.swapaxes(ys2, 0, 1), (h1T, c1T), (h2T, c2T)


@pytest.mark.parametrize("peep", [False, True])
def test_fused_forward_matches_composition(peep):
    args = _pair_inputs(peep=peep)
    ys2, hc1, hc2 = lf.lstm_scan2(*args)
    want_ys2, whc1, whc2 = _compose(*args)
    np.testing.assert_allclose(np.asarray(ys2), np.asarray(want_ys2),
                               rtol=2e-5, atol=2e-5)
    for a, w in zip(hc1 + hc2, whc1 + whc2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("peep", [False, True])
def test_fused_grads_match_composition(peep):
    args = _pair_inputs(b=8, T=5, H=128, peep=peep, seed=3)

    def loss(run):
        def f(xp1, rw1, p1, w2, b2, rw2, p2, h01, c01, h02, c02):
            ys2, (h1T, c1T), (h2T, c2T) = run(xp1, rw1, p1, w2, b2, rw2,
                                              p2, h01, c01, h02, c02)
            return (jnp.sum(ys2.astype(jnp.float32) ** 2)
                    + jnp.sum(h1T * 0.3) + jnp.sum(c1T * 0.2)
                    + jnp.sum(h2T * 0.7) + jnp.sum(c2T * 0.5))
        return f

    argnums = ((0, 1, 3, 4, 5, 7, 8, 9, 10) if args[2] is None
               else (0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10))
    gf = jax.grad(loss(lf.lstm_scan2), argnums=argnums)(*args)
    gc = jax.grad(loss(_compose), argnums=argnums)(*args)
    for a, w in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=5e-4, atol=5e-4)


def _onehot_stream(V, b, T, seed):
    """Char-stream one-hot (features, next-char labels) pair."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, size=(b, T))
    f = np.eye(V, dtype=np.float32)[ids]
    l = np.eye(V, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    return f, l


def _spy_scan2(monkeypatch):
    """Patch lf.lstm_scan2 with a call-recording passthrough; returns the
    call list."""
    calls = []
    real = lf.lstm_scan2

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(lf, "lstm_scan2", spy)
    return calls


def _charrnn_net(V=16, H=128, tbptt=0):
    from deeplearning4j_tpu.nn.conf import (NeuralNetConfiguration,
                                            BackpropType)
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu import Adam

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=1e-3)).activation("tanh")
            .list()
            .layer(GravesLSTM(n_in=V, n_out=H))
            .layer(GravesLSTM(n_in=H, n_out=H))
            .layer(RnnOutputLayer(n_in=H, n_out=V, activation="softmax",
                                  loss="mcxent"))
            .build())
    if tbptt:
        conf.backprop_type = BackpropType.TruncatedBPTT
        conf.tbptt_fwd_length = tbptt
        conf.tbptt_back_length = tbptt
    return MultiLayerNetwork(conf).init()


def test_container_fuses_and_matches_per_layer_path(monkeypatch):
    """The 2xGravesLSTM stack must ACTUALLY route through lstm_scan2
    (spied), and a training step must produce the same score and params as
    the per-layer path under the DL4J_TPU_NO_FUSED_LSTM escape hatch."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    V, H, b, T = 16, 128, 8, 12
    f, l = _onehot_stream(V, b, T, seed=0)
    ds = DataSet(f, l)

    calls = _spy_scan2(monkeypatch)
    net = _charrnn_net(V, H)
    net.fit(ds)
    assert calls, "fused kernel did not engage for the eligible stack"
    s_fused = float(net.score_)
    p_fused = jax.tree_util.tree_map(np.asarray, net.params)

    monkeypatch.setenv("DL4J_TPU_NO_FUSED_LSTM", "1")
    net2 = _charrnn_net(V, H)
    net2.fit(ds)
    s_plain = float(net2.score_)
    assert abs(s_fused - s_plain) < 1e-4 * max(1.0, abs(s_plain))
    for k, v in p_fused.items():
        for pk, pv in v.items():
            np.testing.assert_allclose(
                pv, np.asarray(net2.params[k][pk]), rtol=2e-4, atol=2e-4,
                err_msg=f"{k}/{pk}")


def test_fused_tbptt_stream_state_continuity():
    """TBPTT segments must hand (h, c) across segment boundaries for BOTH
    fused layers: full-sequence fit == segmented fit with carried state
    (the existing per-layer continuity contract, now through the fused
    path)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    V, H, b, T = 16, 128, 8, 12
    f, l = _onehot_stream(V, b, T, seed=1)

    net_full = _charrnn_net(V, H)
    net_seg = _charrnn_net(V, H, tbptt=6)
    out_full = np.asarray(net_full.output(f), np.float32)
    # rnn_time_step through the fused path: two 6-step chunks must equal
    # the full forward (state continuity across the chunk boundary)
    o1 = np.asarray(net_seg.rnn_time_step(f[:, :6]), np.float32)
    o2 = np.asarray(net_seg.rnn_time_step(f[:, 6:]), np.float32)
    np.testing.assert_allclose(np.concatenate([o1, o2], axis=1), out_full,
                               rtol=2e-4, atol=2e-4)


def test_masked_batches_take_per_layer_path(monkeypatch):
    """Step masks are outside the fused kernel's scope: the pair must fall
    back to the per-layer kernels (correctness over speed)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    V, H, b, T = 16, 128, 8, 12
    f, l = _onehot_stream(V, b, T, seed=2)
    fm = np.ones((b, T), np.float32)
    fm[:, -3:] = 0.0

    calls = _spy_scan2(monkeypatch)
    net = _charrnn_net(V, H)
    net.fit(DataSet(f, l, features_mask=fm))
    assert not calls, "masked batch must not take the fused kernel"
    assert np.isfinite(float(net.score_))


def test_fused_under_shard_map_local_sgd(monkeypatch):
    """ParallelWrapper local SGD (averaging_frequency > 1) runs the step
    inside shard_map. Regression pinned: Pallas kernels (persistent/fused
    LSTM) declare out_shape ShapeDtypeStructs with no vma typing, which
    shard_map's default check_vma=True rejects at trace time — the wrapper
    must run its local-SGD shard_map with check_vma=False (like every
    other shard_map in parallel/). The fused kernel must ENGAGE (per-shard
    batch 8 satisfies the in-shard b%8 contract) and the fit must complete
    with a finite score."""
    from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                     ListDataSetIterator)
    from deeplearning4j_tpu.parallel import ParallelWrapper

    V, H, b, T = 16, 128, 64, 8   # 8 per shard: the in-shard kernel
    rng = np.random.default_rng(5)  # contract needs b%8 == 0 PER WORKER
    ids = rng.integers(0, V, size=(b, T))
    f = np.eye(V, dtype=np.float32)[ids]
    l = np.eye(V, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    dsets = [DataSet(f, l), DataSet(f, l)]

    calls = _spy_scan2(monkeypatch)
    net = _charrnn_net(V, H)
    pw = (ParallelWrapper.Builder(net).workers(8)
          .averaging_frequency(2).build())
    pw.fit(ListDataSetIterator(dsets))
    assert calls, "fused kernel did not engage under shard_map local SGD"
    assert np.isfinite(float(net.score_))


def test_fused_under_sharded_jit_sync_dp(monkeypatch):
    """averaging_frequency=1 takes the OTHER wrapper path (sharded jit /
    GSPMD, not shard_map): the fused kernel must engage and the synced-DP
    fit must complete — partitioning around a Pallas custom call is a
    different mechanism than shard_map's vma typing, so both paths need
    pinning."""
    from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                     ListDataSetIterator)
    from deeplearning4j_tpu.parallel import ParallelWrapper

    V, H, b, T = 16, 128, 64, 8
    f, l = _onehot_stream(V, b, T, seed=6)

    calls = _spy_scan2(monkeypatch)
    net = _charrnn_net(V, H)
    pw = (ParallelWrapper.Builder(net).workers(8)
          .averaging_frequency(1).build())
    pw.fit(ListDataSetIterator([DataSet(f, l)]))
    assert calls, "fused kernel did not engage under the sharded-jit path"
    assert np.isfinite(float(net.score_))

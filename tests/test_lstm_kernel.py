"""Persistent-LSTM Pallas kernel vs the ``lax.scan`` oracle (the
cuDNN-helper cross-validation pattern, SURVEY.md §4.4 — here for the
recurrent hot loop: forward, full BPTT gradients, peepholes, masks).

Interpret mode on CPU runs the exact kernel arithmetic the TPU executes
(no TPU-only primitives are used)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import deeplearning4j_tpu.ops.flash_attention as fa
import deeplearning4j_tpu.ops.lstm_cell as lk


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = fa._FORCE_INTERPRET
    fa._FORCE_INTERPRET = True
    yield
    fa._FORCE_INTERPRET = old


def _scan_oracle(xp, rw, peep, h0, c0, mask=None):
    """The recurrent.py scan body, verbatim semantics (batch-major, the
    bias already in ``xp``)."""
    b, T, H4 = xp.shape
    H = H4 // 4

    def step(carry, inp):
        h, cc = carry
        xp_t, m_t = inp
        z = xp_t + h @ rw
        zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
        if peep is not None:
            zi = zi + cc * peep[0]
            zf = zf + cc * peep[1]
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
        g = jnp.tanh(zg)
        c_new = f * cc + i * g
        zo2 = zo + c_new * peep[2] if peep is not None else zo
        o = jax.nn.sigmoid(zo2)
        h_new = o * jnp.tanh(c_new)
        if m_t is not None:
            mm = m_t[:, None].astype(h_new.dtype)
            h_new = mm * h_new + (1 - mm) * h
            c_new = mm * c_new + (1 - mm) * cc
        return (h_new, c_new), h_new

    xs = jnp.swapaxes(xp, 0, 1)
    if mask is not None:
        ms = jnp.swapaxes(mask, 0, 1)
        (hT, cT), ys = lax.scan(step, (h0, c0), (xs, ms))
    else:
        (hT, cT), ys = lax.scan(lambda cr, xt: step(cr, (xt, None)),
                                (h0, c0), xs)
    return jnp.swapaxes(ys, 0, 1), (hT, cT)


def _kernel(xp, rw, peep, h0, c0, mask=None, bias=None, out_dtype=None):
    """The oracle's (batch-major) signature over the kernel entry, which is
    time-major and takes the bias apart: swap in, call, swap out. ``xp`` is
    handed over as it is (no bias in it) with ``bias`` (zeros when None)
    beside it, so ``_kernel(xp, ..., bias=b)`` is ``_scan_oracle(xp + b)``."""
    if bias is None:
        bias = jnp.zeros((xp.shape[-1],), jnp.float32)
    ys, hc = lk.lstm_scan(
        jnp.swapaxes(xp, 0, 1), bias, rw, peep, h0, c0,
        None if mask is None else jnp.swapaxes(mask, 0, 1),
        out_dtype=out_dtype)
    return jnp.swapaxes(ys, 0, 1), hc


def _bias(H, seed=21):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(4 * H,))
                       * 0.3, jnp.float32)


def _inputs(b=8, T=5, H=128, peep=False, mask=False, seed=0):
    rng = np.random.default_rng(seed)
    xp = jnp.asarray(rng.normal(size=(b, T, 4 * H)) * 0.5, jnp.float32)
    rw = jnp.asarray(rng.normal(size=(H, 4 * H)) / np.sqrt(H), jnp.float32)
    pp = (tuple(jnp.asarray(rng.normal(size=(H,)) * 0.3, jnp.float32)
                for _ in range(3)) if peep else None)
    h0 = jnp.asarray(rng.normal(size=(b, H)) * 0.2, jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(b, H)) * 0.2, jnp.float32)
    mk = None
    if mask:
        lens = rng.integers(1, T + 1, size=b)
        mk = jnp.asarray((np.arange(T)[None, :] < lens[:, None]),
                         jnp.float32)
    return xp, rw, pp, h0, c0, mk


@pytest.mark.parametrize("peep", [False, True])
@pytest.mark.parametrize("mask", [False, True])
def test_forward_matches_scan(peep, mask):
    xp, rw, pp, h0, c0, mk = _inputs(peep=peep, mask=mask)
    bias = _bias(128)
    ys, (hT, cT) = _kernel(xp, rw, pp, h0, c0, mk, bias=bias)
    want_ys, (whT, wcT) = _scan_oracle(xp + bias, rw, pp, h0, c0, mk)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(want_ys),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(whT),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(wcT),
                               rtol=1e-5, atol=1e-5)


def _assert_grads_match(xp, rw, pp, h0, c0, mk):
    """Gradient parity harness shared by the binary- and fractional-mask
    tests: hand-written BPTT kernel == AD of the scan, for every input —
    xp (→ dW/dx outside), the bias (db in-kernel), RW, peepholes, h0, c0,
    including carry grads through hT/cT."""
    def loss(run):
        def f(xp, rw, pp, h0, c0, bias):
            ys, (hT, cT) = run(xp, rw, pp, h0, c0, bias)
            return jnp.sum(ys ** 2) + jnp.sum(hT * 0.7) + jnp.sum(cT * 0.3)
        return f

    bias = _bias(rw.shape[0])
    args = (xp, rw, pp, h0, c0, bias)
    argnums = (0, 1, 3, 4, 5) if pp is None else (0, 1, 2, 3, 4, 5)
    gk = jax.grad(loss(lambda xp, rw, pp, h0, c0, bias: _kernel(
        xp, rw, pp, h0, c0, mk, bias=bias)), argnums=argnums)(*args)
    gs = jax.grad(loss(lambda xp, rw, pp, h0, c0, bias: _scan_oracle(
        xp + bias, rw, pp, h0, c0, mk)), argnums=argnums)(*args)
    for a, want in zip(jax.tree_util.tree_leaves(gk),
                       jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("peep", [False, True])
@pytest.mark.parametrize("mask", [False, True])
def test_grads_match_scan(peep, mask):
    xp, rw, pp, h0, c0, mk = _inputs(b=8, T=4, H=128, peep=peep, mask=mask,
                                     seed=3)
    _assert_grads_match(xp, rw, pp, h0, c0, mk)


def test_layer_routes_through_kernel_and_matches():
    """GravesLSTM layer forward routes through the persistent kernel when
    supported (spied) and reproduces the scan path bit-for-bit at the layer
    level; unsupported widths fall back to the scan."""
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork, Sgd
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer

    def build(H):
        conf = (NeuralNetConfiguration.builder().seed(2)
                .updater(Sgd(learning_rate=0.1)).activation("tanh").list()
                .layer(GravesLSTM(n_in=10, n_out=H))
                .layer(RnnOutputLayer(n_in=H, n_out=6, activation="softmax",
                                      loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(5)
    f = rng.normal(size=(8, 7, 10)).astype(np.float32)
    ids = rng.integers(0, 6, size=(8, 7))
    l = np.eye(6, dtype=np.float32)[ids]

    calls = []
    real = lk.lstm_scan
    import deeplearning4j_tpu.ops.lstm_cell as lk_mod
    lk_mod.lstm_scan = lambda *a, **k: (calls.append(1) or real(*a, **k))
    try:
        net = build(128)
        out_kernel = np.asarray(net.output(f))
        assert calls, "kernel path not taken for H=128"
        # force the scan path by clearing support — on a FRESH net (same
        # seed → identical params): net.output caches its jitted forward,
        # so reusing `net` would be a cache hit re-running the kernel path
        # and the comparison would be vacuous
        import deeplearning4j_tpu.ops.flash_attention as fa_mod
        calls.clear()
        fa_mod._FORCE_INTERPRET = False   # off-TPU → supported() False
        try:
            out_scan = np.asarray(build(128).output(f))
        finally:
            fa_mod._FORCE_INTERPRET = True
        assert not calls, "scan leg still routed through the kernel"
        np.testing.assert_allclose(out_kernel, out_scan, rtol=1e-5,
                                   atol=1e-6)
    finally:
        lk_mod.lstm_scan = real

    # training through the kernel converges
    from deeplearning4j_tpu import DataSet
    net2 = build(128)
    ds = DataSet(f, l)
    s0 = float(net2.score(ds))
    for _ in range(10):
        net2.fit(ds)
    assert float(net2.score(ds)) < s0


def test_tbptt_stream_state_continuity():
    """Segment-wise execution through the kernel (h0/c0 carried between
    calls) equals one full-sequence run — the TBPTT contract."""
    xp, rw, pp, h0, c0, _ = _inputs(b=8, T=6, H=128, peep=True, seed=9)
    full, (hT, cT) = _kernel(xp, rw, pp, h0, c0)
    y1, (h1, c1) = _kernel(xp[:, :3], rw, pp, h0, c0)
    y2, (h2, c2) = _kernel(xp[:, 3:], rw, pp, h1, c1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hT), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("peep", [False, True])
def test_grads_match_scan_fractional_mask(peep):
    """FRACTIONAL mask values (soft step weighting) must differentiate
    exactly like AD of the scan: dc_prev gets the (1-m) residual WITHOUT an
    extra m factor (m² = m hides the bug for binary masks), and tanh/
    peephole-o differentiate the PRE-mask candidate cell, not the blended
    cseq value."""
    xp, rw, pp, h0, c0, _ = _inputs(b=8, T=4, H=128, peep=peep, seed=11)
    rng = np.random.default_rng(13)
    mk = jnp.asarray(rng.uniform(0.1, 0.9, size=(8, 4)), jnp.float32)

    # forward parity first (cseq stores post-mask c; candidate recomputed)
    np.testing.assert_allclose(
        np.asarray(_kernel(xp, rw, pp, h0, c0, mk)[0]),
        np.asarray(_scan_oracle(xp, rw, pp, h0, c0, mk)[0]),
        rtol=1e-5, atol=1e-5)
    _assert_grads_match(xp, rw, pp, h0, c0, mk)


def test_supported_vmem_budget_counts_batch_blocks():
    """The VMEM gate must reject configs whose BATCH-dependent blocks
    (streams + scratch, ~120·b·H bytes) overflow a core even when the
    resident weights alone fit — b=256, H=512 was exactly such a config."""
    assert lk.supported(64, 50, 512, "tanh", "sigmoid")
    assert not lk.supported(256, 50, 512, "tanh", "sigmoid")
    assert not lk.supported(2048, 50, 128, "tanh", "sigmoid")
    assert lk.supported(8, 50, 768, "tanh", "sigmoid")
    assert not lk.supported(8, 50, 1024, "tanh", "sigmoid")


def test_forward_matches_scan_bf16_weights():
    """Mixed-precision policy path: RW in bf16 (native MXU pass in the
    kernel), h/c/gate math f32 — kernel == scan oracle run with the SAME
    bf16 weights, to bf16-class tolerance."""
    xp, rw, pp, h0, c0, _ = _inputs(b=8, T=6, H=128, seed=3)
    rwb = rw.astype(jnp.bfloat16)
    ys, (hT, cT) = _kernel(xp, rwb, pp, h0, c0, None)

    def oracle(xp, rwb, h0, c0):
        def step(carry, xt):
            h, c = carry
            z = xt + jax.lax.dot_general(
                h.astype(jnp.bfloat16), rwb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
            i, f, o = (jax.nn.sigmoid(zi), jax.nn.sigmoid(zf),
                       jax.nn.sigmoid(zo))
            g = jnp.tanh(zg)
            c2 = f * c + i * g
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2
        (hT, cT), ys = jax.lax.scan(step, (h0, c0),
                                    jnp.swapaxes(xp, 0, 1))
        return jnp.swapaxes(ys, 0, 1), hT, cT

    wys, whT, wcT = oracle(xp, rwb, h0, c0)
    np.testing.assert_allclose(np.asarray(ys, np.float32),
                               np.asarray(wys, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(whT),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(wcT),
                               rtol=2e-2, atol=2e-2)


def test_grads_match_scan_bf16_weights():
    """The hand-written BPTT with bf16-resident RWᵀ still matches AD of an
    identically-cast scan."""
    xp, rw, pp, h0, c0, _ = _inputs(b=8, T=4, H=128, seed=4)
    rwb = rw.astype(jnp.bfloat16)

    def loss_k(xp, rwb, h0, c0):
        ys, (hT, cT) = _kernel(xp, rwb, pp, h0, c0, None)
        return jnp.sum(ys.astype(jnp.float32) ** 2) + jnp.sum(hT * 0.5)

    def loss_s(xp, rwb, h0, c0):
        def step(carry, xt):
            h, c = carry
            z = xt + jax.lax.dot_general(
                h.astype(jnp.bfloat16), rwb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
            i, f, o = (jax.nn.sigmoid(zi), jax.nn.sigmoid(zf),
                       jax.nn.sigmoid(zo))
            g = jnp.tanh(zg)
            c2 = f * c + i * g
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2
        (hT, cT), ys = jax.lax.scan(step, (h0, c0), jnp.swapaxes(xp, 0, 1))
        return jnp.sum(jnp.swapaxes(ys, 0, 1).astype(jnp.float32) ** 2) \
            + jnp.sum(hT * 0.5)

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(xp, rwb, h0, c0)
    gs = jax.grad(loss_s, argnums=(0, 1, 2, 3))(xp, rwb, h0, c0)
    for a, want in zip(gk, gs):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)


def test_unroll_factor_selection(monkeypatch):
    """U honors the env override, must divide T, and shrinks under the
    VMEM budget."""
    monkeypatch.delenv("DL4J_TPU_LSTM_UNROLL", raising=False)
    assert lk._unroll_factor(50, 64, 512, 2) == 2        # default
    assert lk._unroll_factor(5, 64, 512, 2) == 1         # 5 % 2 != 0
    monkeypatch.setenv("DL4J_TPU_LSTM_UNROLL", "5")
    assert lk._unroll_factor(50, 8, 128, 2) == 5
    assert lk._unroll_factor(50, 64, 512, 2) <= 5        # budget may shrink
    monkeypatch.setenv("DL4J_TPU_LSTM_UNROLL", "1")
    assert lk._unroll_factor(50, 8, 128, 2) == 1


@pytest.mark.parametrize("peep", [False, True])
def test_unrolled_kernel_matches_scan_u5(monkeypatch, peep):
    """U=5 (10 steps → 2 grid blocks): fwd AND grads equal the oracle, with
    masks + peepholes — the block-boundary c_prev handoff (cprev stream
    [U-1] vs in-block u-1) is exactly what this pins."""
    monkeypatch.setenv("DL4J_TPU_LSTM_UNROLL", "5")
    xp, rw, pp, h0, c0, mk = _inputs(b=8, T=10, H=128, peep=peep, mask=True,
                                     seed=6)
    ys, (hT, cT) = _kernel(xp, rw, pp, h0, c0, mk)
    want_ys, (whT, wcT) = _scan_oracle(xp, rw, pp, h0, c0, mk)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(want_ys),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(whT),
                               rtol=1e-5, atol=1e-5)
    _assert_grads_match(xp, rw, pp, h0, c0, mk)


@pytest.mark.parametrize("peep", [False, True])
def test_bf16_stream_dtype_matches_scan(monkeypatch, peep):
    """DL4J_TPU_LSTM_STREAM_DTYPE=bfloat16 halves the RESERVE (gates, cseq
    — the cuDNN reserve-space convention) and nothing else: the other
    streams follow their operands, here f32 ``xw`` in, f32 ``ys`` and
    ``dz`` out; h/c state and gate math stay f32. Forward and gradients
    must match the f32 scan oracle within bf16 rounding of the reserve
    (the RECURRENT state chain itself never rounds, so the error does not
    compound across steps)."""
    monkeypatch.setenv("DL4J_TPU_LSTM_STREAM_DTYPE", "bfloat16")
    xp, rw, pp, h0, c0, mk = _inputs(b=8, T=6, H=128, peep=peep, mask=True,
                                     seed=7)
    ys, (hT, cT) = _kernel(xp, rw, pp, h0, c0, mk)
    assert ys.dtype == xp.dtype              # not the knob's: the operand's
    assert hT.dtype == jnp.float32           # state precision kept
    reserve = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        _kernel(x, rw, pp, h0, c0, mk)[0])))(xp)
    assert "bf16[6,8,512]" in str(reserve) and "bf16[6,8,128]" in str(reserve)
    want_ys, (whT, wcT) = _scan_oracle(xp, rw, pp, h0, c0, mk)
    np.testing.assert_allclose(np.asarray(ys, np.float32),
                               np.asarray(want_ys), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(whT),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(wcT),
                               rtol=2e-2, atol=2e-2)

    def loss(run):
        def f(xp, rw, pp, h0, c0):
            ys, (hT, cT) = run(xp, rw, pp, h0, c0, mk)
            return (jnp.sum(ys.astype(jnp.float32) ** 2)
                    + jnp.sum(hT * 0.7) + jnp.sum(cT * 0.3))
        return f

    argnums = (0, 1, 3, 4) if pp is None else (0, 1, 2, 3, 4)
    gk = jax.grad(loss(_kernel), argnums=argnums)(xp, rw, pp, h0, c0)
    gs = jax.grad(loss(_scan_oracle), argnums=argnums)(xp, rw, pp, h0, c0)
    for a, want in zip(jax.tree_util.tree_leaves(gk),
                       jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(want), rtol=5e-2, atol=5e-2)


def test_stream_dtype_budget_doubles_unroll(monkeypatch):
    """bf16 streams halve the VMEM stream term, so the unroll the budget
    admits doubles at the char-RNN shape (b=64, H=512, bf16 weights)."""
    monkeypatch.setenv("DL4J_TPU_LSTM_UNROLL", "8")
    monkeypatch.delenv("DL4J_TPU_LSTM_STREAM_DTYPE", raising=False)
    u_f32 = lk._unroll_factor(40, 64, 512, 2)
    monkeypatch.setenv("DL4J_TPU_LSTM_STREAM_DTYPE", "bfloat16")
    u_bf16 = lk._unroll_factor(40, 64, 512, 2)
    assert u_bf16 >= 2 * u_f32


# ---------------------------------------------------------------- streams in
# the gemms' own dtype (bf16 xw / ys / dy / dz under the bf16 policy)
_BF16_CASES = {
    # name: (peep, mask kind, DL4J_TPU_LSTM_UNROLL, T)
    "plain": (False, None, None, 6),
    "peephole": (True, None, None, 6),
    "masked": (False, "binary", None, 6),
    "peephole_masked": (True, "binary", None, 6),
    "peephole_fractional_mask": (True, "fractional", None, 6),
    "peephole_masked_u5": (True, "binary", "5", 10),
}


def _bf16_case(name, monkeypatch):
    peep, mask, unroll, T = _BF16_CASES[name]
    if unroll:
        monkeypatch.setenv("DL4J_TPU_LSTM_UNROLL", unroll)
    xp, rw, pp, h0, c0, mk = _inputs(b=8, T=T, H=128, peep=peep,
                                     mask=mask == "binary", seed=17)
    if mask == "fractional":
        mk = jnp.asarray(np.random.default_rng(19).uniform(
            0.1, 0.9, size=(8, T)), jnp.float32)
    return (xp.astype(jnp.bfloat16), rw.astype(jnp.bfloat16), pp, h0, c0,
            mk, _bias(128))


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("case", list(_BF16_CASES))
def test_narrowed_streams_are_exact(case, monkeypatch):
    """Narrowing moves a rounding, it adds none: the kernels fed bf16 ``xw``
    and ``dy`` and writing bf16 ``ys`` and ``dz`` give, bit for bit, what
    the same kernels give with those values held in f32 and rounded
    afterwards by their consumers (what the layer did before the streams
    followed the gemms' dtype): ``ys``, ``dz``, ``dRW`` and the f32 carry
    ``hT``/``cT``. The f32 sums that never see a narrow copy (``db``, the
    peephole and carry gradients) are held to the last bits only: two
    compiled bodies may contract their adds differently."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    xw, rw, pp, h0, c0, mk, bias = _bf16_case(case, monkeypatch)
    rng = np.random.default_rng(23)
    dy = jnp.asarray(rng.normal(size=(8, xw.shape[1], 128)), bf16)
    dh, dc = (jnp.asarray(rng.normal(size=(8, 128)), f32) for _ in "hc")

    def run(xw, dy, out_dtype):
        (ys, (hT, cT)), vjp = jax.vjp(
            lambda xw, bias, rw, pp, h0, c0: _kernel(
                xw, rw, pp, h0, c0, mk, bias=bias, out_dtype=out_dtype),
            xw, bias, rw, pp, h0, c0)
        return ys, hT, cT, vjp((dy, (dh, dc)))

    ys, hT, cT, (dz, db, drw, dpp, dh0, dc0) = run(xw, dy, bf16)
    wys, whT, wcT, (wdz, wdb, _, wdpp, wdh0, wdc0) = run(
        xw.astype(f32), dy.astype(f32), f32)
    assert (ys.dtype, dz.dtype, wys.dtype, wdz.dtype) == (bf16, bf16, f32, f32)
    # the weight-gradient gemm as the f32-stream kernel's consumer ran it:
    # both operands rounded to the weight dtype first
    wys_tm, wdz_tm = jnp.swapaxes(wys, 0, 1), jnp.swapaxes(wdz, 0, 1)
    wdrw = jnp.einsum(
        "tbh,tbg->hg",
        jnp.concatenate([h0[None], wys_tm[:-1]]).astype(bf16),
        wdz_tm.astype(bf16), preferred_element_type=f32).astype(bf16)
    for name, got, want in [
            ("ys", ys, wys.astype(bf16)), ("dz", dz, wdz.astype(bf16)),
            ("drw", drw, wdrw), ("hT", hT, whT), ("cT", cT, wcT)]:
        assert got.dtype == want.dtype, name
        np.testing.assert_array_equal(_bits(got), _bits(want), err_msg=name)
    for name, got, want in [
            ("db", db, wdb), ("dh0", dh0, wdh0), ("dc0", dc0, wdc0)] + [
            (f"dpeep{i}", a, w) for i, (a, w) in enumerate(
                zip(dpp or (), wdpp or ()))]:
        assert got.dtype == want.dtype == f32, name
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6,
            atol=1e-6 * float(jnp.max(jnp.abs(want))), err_msg=name)


@pytest.mark.parametrize("case", list(_BF16_CASES))
def test_bf16_streams_match_scan(case, monkeypatch):
    """The same cases against the oracle: bf16 ``xw`` in, bf16 ``ys`` and
    ``dz`` out, forward and every gradient within bf16 rounding of the f32
    scan over the same (already rounded) ``xw`` and weights."""
    xw, rw, pp, h0, c0, mk, bias = _bf16_case(case, monkeypatch)
    f32 = jnp.float32

    def loss(run):
        def f(xw, rw, pp, h0, c0, bias):
            ys, (hT, cT) = run(xw, rw, pp, h0, c0, bias)
            return (jnp.sum(ys.astype(f32) ** 2) + jnp.sum(hT * 0.7)
                    + jnp.sum(cT * 0.3)), ys
        return f

    kern = loss(lambda xw, rw, pp, h0, c0, bias: _kernel(
        xw, rw, pp, h0, c0, mk, bias=bias))
    scan = loss(lambda xw, rw, pp, h0, c0, bias: _scan_oracle(
        xw.astype(f32) + bias, rw.astype(f32), pp, h0, c0, mk))
    args = (xw, rw, pp, h0, c0, bias)
    argnums = (0, 1, 3, 4, 5) if pp is None else (0, 1, 2, 3, 4, 5)
    gk, ys = jax.grad(kern, argnums=argnums, has_aux=True)(*args)
    gs, want_ys = jax.grad(scan, argnums=argnums, has_aux=True)(*args)
    assert ys.dtype == gk[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ys, np.float32),
                               np.asarray(want_ys), rtol=2e-2, atol=2e-2)
    for a, want in zip(jax.tree_util.tree_leaves(gk),
                       jax.tree_util.tree_leaves(gs)):
        scale = float(jnp.max(jnp.abs(want.astype(f32))))
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2 * max(scale, 1.0))


@pytest.mark.parametrize("mask", [False, True])
@pytest.mark.parametrize("unroll", ["2", "5"])
def test_in_kernel_bias_gradient_is_the_sum_of_dz(mask, unroll, monkeypatch):
    """``db`` leaves the backward kernel as Σ_t Σ_b dz of the f32 dz values
    (8 partial rows, summed outside); against the f32 ``dz`` stream's own
    sum it may differ by the order of the adds only."""
    monkeypatch.setenv("DL4J_TPU_LSTM_UNROLL", unroll)
    xp, rw, pp, h0, c0, mk = _inputs(b=16, T=10, H=128, peep=True,
                                     mask=mask, seed=29)

    def loss(xp, bias):
        ys, (hT, cT) = _kernel(xp, rw, pp, h0, c0, mk, bias=bias)
        return jnp.sum(ys ** 2) + jnp.sum(hT * 0.7) + jnp.sum(cT * 0.3)

    dz, db = jax.grad(loss, argnums=(0, 1))(xp, _bias(128))
    want = np.asarray(dz, np.float64).sum((0, 1))
    assert db.dtype == jnp.float32 and db.shape == (512,)
    np.testing.assert_allclose(np.asarray(db), want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


# ------------------------------------------------ the layers over the kernel
def _layer_net(layer, compute="float32", n_in=10, H=128, n_out=6):
    from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                    Sgd)
    from deeplearning4j_tpu.nn.conf import layers as L

    conf = (NeuralNetConfiguration.builder().seed(2)
            .updater(Sgd(learning_rate=0.1)).activation("tanh")
            .compute_dtype(compute).list()
            .layer(getattr(L, layer)(n_in=n_in, n_out=H))
            .layer(L.RnnOutputLayer(n_in=H, n_out=n_out, activation="softmax",
                                    loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
@pytest.mark.parametrize("layer", ["LSTM", "GravesLSTM",
                                   "GravesBidirectionalLSTM"])
def test_layer_kernel_branch_matches_scan_branch(layer, compute):
    """Every LSTM layer kind, both compute dtypes, with a step mask (the
    bidirectional one runs its second direction flipped along time): loss
    and parameter gradients through ``_run``'s kernel branch (time-major,
    streams in the compute dtype, bias and ``db`` in-kernel) against its
    scan branch (batch-major, f32, bias in XLA)."""
    net = _layer_net(layer, compute)
    rng = np.random.default_rng(5)
    f = jnp.asarray(rng.normal(size=(8, 7, 10)), jnp.float32)
    l = jnp.asarray(np.eye(6, dtype=np.float32)[rng.integers(0, 6, (8, 7))])
    m = jnp.asarray(np.arange(7)[None, :] < rng.integers(2, 8, (8, 1)),
                    jnp.float32)

    def run():
        return jax.value_and_grad(lambda p: net._loss_fn(
            p, net.states, f, l, m, m, True, None)[0])(net.params)

    loss_k, grads_k = run()
    fa._FORCE_INTERPRET = False              # off-TPU → the scan branch
    try:
        loss_s, grads_s = run()
    finally:
        fa._FORCE_INTERPRET = True
    tol = 2e-4 if compute == "float32" else 5e-2
    assert abs(float(loss_k) - float(loss_s)) < tol * abs(float(loss_s))
    for (path, a), want in zip(
            jax.tree_util.tree_leaves_with_path(grads_k),
            jax.tree_util.tree_leaves(grads_s)):
        assert a.dtype == want.dtype
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(want), rtol=tol,
            atol=tol * float(jnp.max(jnp.abs(want))), err_msg=str(path))


def test_kernel_under_shard_map_local_sgd(monkeypatch):
    """ParallelWrapper local SGD runs the step inside ``shard_map``: the
    zero (h, c) carry must take the varying type of the time-major ``xw``
    (``_match_vma``), and the per-layer kernel must engage (8 per shard)."""
    from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                     ListDataSetIterator)
    from deeplearning4j_tpu.parallel import ParallelWrapper

    calls = []
    real = lk.lstm_scan
    monkeypatch.setattr(lk, "lstm_scan",
                        lambda *a, **k: (calls.append(1) or real(*a, **k)))
    rng = np.random.default_rng(7)
    f = rng.normal(size=(64, 6, 10)).astype(np.float32)
    l = np.eye(6, dtype=np.float32)[rng.integers(0, 6, (64, 6))]
    net = _layer_net("GravesLSTM", "bfloat16")
    pw = (ParallelWrapper.Builder(net).workers(8)
          .averaging_frequency(2).build())
    pw.fit(ListDataSetIterator([DataSet(f, l), DataSet(f, l)]))
    assert calls, "the kernel did not engage under shard_map local SGD"
    assert np.isfinite(float(net.score_))

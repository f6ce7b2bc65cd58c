"""What can be checked about the chip path from the CPU: ``chip_smoke.py``
refuses to run without a TPU, the Pallas kernels refuse to run off-chip
unless a test asked for interpret mode, and every kernel family still
lowers to a Mosaic custom call at its bench shape (the cross-lowering
check to run before a chip call — Mosaic's own compile only happens on
the chip)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeplearning4j_tpu.ops.flash_attention as fa
import deeplearning4j_tpu.ops.lstm_cell as lk
import deeplearning4j_tpu.ops.lstm_fused as lf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu_naming_the_platform():
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "not a TPU" in p.stderr
    assert p.stdout == ""            # no result line


def test_result_line_holds_exactly_ok_and_device():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    line = chip_smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
               "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_perf_scripts_refuse_an_unknown_device_kind():
    """A roofline against another chip's peaks is wrong: ``perf_lstm``
    reads them from the benchmark's table, which has none for the CPU."""
    from benchmark import device
    sys.path.insert(0, ROOT)
    try:
        import perf_lstm
    finally:
        sys.path.remove(ROOT)
    assert "TPU v5 lite" in device.PEAKS
    with pytest.raises(SystemExit) as exc:
        perf_lstm.roofline(batch=2, width=8, tbptt=4, seq_len=8)
    assert "'cpu'" in str(exc.value.code)


def test_perf_script_parents_initialise_no_backend():
    """A sweep's parent leaves the chip to its children: importing the
    perf scripts initialises no jax backend and imports no other harness."""
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys, perf_lstm, perf_flash_check\n"
         "from jax._src import xla_bridge\n"
         "sys.exit(xla_bridge.backends_are_initialized()\n"
         "         or 'bench' in sys.modules)"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]


def test_kernel_call_off_chip_without_forced_interpret_raises():
    assert not fa._FORCE_INTERPRET
    q = jnp.zeros((1, 256, 2, 16), jnp.float32)
    with pytest.raises(RuntimeError, match="backend 'cpu'"):
        fa.flash_attention(q, q, q)
    xp = jnp.zeros((8, 4, 4 * 128), jnp.float32)
    rw = jnp.zeros((128, 4 * 128), jnp.float32)
    z = jnp.zeros((8, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="backend 'cpu'"):
        lk.lstm_scan(jnp.swapaxes(xp, 0, 1), jnp.zeros((4 * 128,)), rw,
                     None, z, z)
    with pytest.raises(RuntimeError, match="backend 'cpu'"):
        lf.lstm_scan2(xp, rw, None, rw, jnp.zeros((4 * 128,)), rw, None,
                      z, z, z, z)
    # and nothing routes there by itself
    assert not fa.supported(8192, 64, 0.0, None)
    assert not lk.supported(64, 50, 512, "tanh", "sigmoid", weight_bytes=2)
    assert not lf.supported2(64, 50, 512, weight_bytes=2)


def _custom_calls(fn, *avals):
    """``tpu_custom_call`` count of ``grad(fn)`` lowered FOR the TPU from
    this CPU process."""
    loss = lambda *a: jnp.sum(jax.tree_util.tree_leaves(fn(*a))[0]
                              .astype(jnp.float32) ** 2)
    traced = jax.jit(jax.grad(loss, argnums=tuple(range(len(avals))))
                     ).trace(*avals)
    return traced.lower(lowering_platforms=("tpu",)).as_text().count(
        "tpu_custom_call")


def test_every_kernel_family_lowers_for_tpu_at_bench_shape(monkeypatch):
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    sds = jax.ShapeDtypeStruct
    # flash at the smoke TransformerLM's attention shape: fwd + dq + dkv
    qkv = [sds((4, 8192, 8, 64), jnp.bfloat16)] * 3
    km = np.ones((4, 8192), np.float32)
    assert fa.supported(8192, 64, 0.3, km)
    assert _custom_calls(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True), *qkv) == 3
    assert _custom_calls(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           key_mask=jnp.asarray(km)),
        *qkv) == 3
    assert _custom_calls(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=False,
                                           dropout_rate=0.3,
                                           dropout_seed=7), *qkv) == 3
    # the LSTM kernels at the char-RNN's segment shape, bf16 weights:
    # lstm_cell with its streams in both dtypes the layer hands it (bf16
    # under the bf16 policy, f32 under f32 compute), over both reserves
    b, T, H = 64, 50, 512
    xp = sds((b, T, 4 * H), jnp.float32)
    bias = sds((4 * H,), jnp.float32)
    w = sds((H, 4 * H), jnp.bfloat16)
    st = sds((b, H), jnp.float32)
    peep = (sds((H,), jnp.float32),) * 3
    for reserve in ("float32", "bfloat16"):
        monkeypatch.setenv("DL4J_TPU_LSTM_STREAM_DTYPE", reserve)
        assert lk.supported(b, T, H, "tanh", "sigmoid", weight_bytes=2)
        for stream in (jnp.bfloat16, jnp.float32):
            assert _custom_calls(lk.lstm_scan, sds((T, b, 4 * H), stream),
                                 bias, w, peep, st, st) == 2
    assert lf.supported2(b, T, H, weight_bytes=2)     # bf16 streams only
    assert _custom_calls(lf.lstm_scan2, xp, w, peep, w,
                         sds((4 * H,), jnp.float32), w, peep,
                         st, st, st, st) == 2


def test_grouped_heads_lower_to_the_flash_kernels_at_the_hybrid_cells_shape(
        monkeypatch):
    """``mha`` at the granite cell's attention shape (32 query heads over 8
    key-value heads of 64, T 8192, the configuration's scale), lowered for
    the TPU from here: the three flash kernels and no dense scores."""
    from deeplearning4j_tpu.nn.layers.attention import mha
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    sds = jax.ShapeDtypeStruct
    q = sds((1, 8192, 32, 64), jnp.bfloat16)
    kv = sds((1, 8192, 8, 64), jnp.bfloat16)
    attend = lambda q, k, v: mha(q, k, v, True, jnp.bfloat16, scale=0.015625)
    assert _custom_calls(attend, q, kv, kv) == 3
    text = jax.jit(attend).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "8192x8192" not in text


def test_lstm_streams_cross_the_layer_kernel_boundary_narrow_and_time_major(
        monkeypatch):
    """The contract between ``recurrent._BaseLSTMImpl._run`` and
    ``ops/lstm_cell`` on the program's text: the gradient of two stacked
    GravesLSTM layers at the char-RNN bench shape (b 64, T 50, H 512, bf16
    compute), lowered for the TPU from here. No per-timestep [·, ·, 4H]
    tensor is transposed and no f32 tensor at all: what is swapped between
    the batch-major layers and the time-major kernels is the narrow bf16
    input / output ([·, ·, 80], [·, ·, 512]). The rank-2 transposes left are
    of weights (``RWᵀ`` for the backward kernel, the gemms' cotangents):
    the compiler's layout choice, not a pass over a stream. The kernels
    take ``xw`` / ``dy`` and return ``ys`` / ``dz`` in bf16 with an f32
    reserve and an f32 bias row in, ``db`` out. (This text is unoptimised:
    that XLA cancels the swap pair between the layers shows only in the
    chip's compiled body, PERF.md §5.)"""
    import re
    from deeplearning4j_tpu import Adam, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.delenv("DL4J_TPU_LSTM_STREAM_DTYPE", raising=False)
    b, T, V, H = 64, 50, 80, 512
    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(1)
        .updater(Adam(learning_rate=1e-3)).activation("tanh")
        .compute_dtype("bfloat16").list()
        .layer(GravesLSTM(n_in=V, n_out=H)).layer(GravesLSTM(n_in=H, n_out=H))
        .layer(RnnOutputLayer(n_in=H, n_out=V, activation="softmax",
                              loss="mcxent")).build()).init()
    l0, l1 = net.impls[0], net.impls[1]

    def loss(p0, p1, x):
        y0, _ = l0._run(p0, x, None, None)
        y1, _ = l1._run(p1, y0, None, None)
        return jnp.sum(y1.astype(jnp.float32) ** 2)

    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        jax.tree_util.tree_map(sds, net.params["0"]),
        jax.tree_util.tree_map(sds, net.params["1"]),
        jax.ShapeDtypeStruct((b, T, V), jnp.float32),
    ).lower(lowering_platforms=("tpu",)).as_text()

    swapped = re.findall(
        r"stablehlo\.transpose [^\n]*: \(tensor<([0-9x]+)x(\w+)>\)", text)
    assert swapped
    streams = [(shape, dt) for shape, dt in swapped if shape.count("x") == 2]
    assert sorted(set(streams)) == [
        ("50x64x512", "bf16"), ("50x64x80", "bf16"),
        ("64x50x512", "bf16"), ("64x50x80", "bf16")], streams
    for shape, dt in swapped:
        assert dt == "bf16", (shape, dt)
        assert shape.count("x") == 1 or not shape.endswith("x2048"), shape

    def calls(kernel):
        found = re.findall(
            r'kernel_name = "%s"[^\n]*\} : \(([^)]*)\) -> \(([^)]*)\)'
            % kernel, text)
        assert len(found) == 2, (kernel, len(found))      # one per layer
        return [tuple(re.findall(r"tensor<([^>]+)>", side) for side in io)
                for io in found]

    for ins, outs in calls("lstm_cell_fwd"):
        assert ins[:3] == ["50x64x2048xbf16", "8x2048xf32", "512x2048xbf16"]
        assert outs == ["50x64x512xbf16", "50x64x2048xf32", "50x64x512xf32",
                        "2x64x512xf32"]
    for ins, outs in calls("lstm_cell_bwd"):
        assert ins[:5] == ["50x64x512xbf16", "50x64x2048xf32",
                           "50x64x512xf32", "50x64x512xf32", "2048x512xbf16"]
        assert outs == ["50x64x2048xbf16", "64x512xf32", "64x512xf32",
                        "8x512xf32", "8x2048xf32"]

"""GravesLSTM char-RNN perf experiments (PERF.md records results).

  python perf_lstm.py sweep            # batch × width × tbptt sweep
  python perf_lstm.py roofline         # XLA cost model + bound analysis
  python perf_lstm.py profile DIR      # jax.profiler trace of steady state
  python perf_lstm.py check            # kernels vs the lax.scan path

Dispatch is asynchronous: every timed window closes on
``jax.block_until_ready`` of what its last call returned (:func:`timed`).
The modes that A/B a trace-time knob (``unroll``, ``stream``) run one
child per value; their parent never initialises a backend, because the
chip belongs to one process at a time.
"""
import contextlib
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmark import device


def graves_lstm_net(vocab=80, width=512, tbptt=50):
    """2×GravesLSTM(width) char-RNN with TBPTT (the reference
    CudnnLSTMHelper's showcase config), bf16 compute, the data cached on
    the device after its first transfer."""
    from deeplearning4j_tpu import Adam
    from deeplearning4j_tpu.nn.conf import BackpropType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Adam(learning_rate=1e-3)).activation("tanh")
            .compute_dtype("bfloat16").cache_mode("device")
            .list()
            .layer(GravesLSTM(n_in=vocab, n_out=width))
            .layer(GravesLSTM(n_in=width, n_out=width))
            .layer(RnnOutputLayer(n_in=width, n_out=vocab,
                                  activation="softmax", loss="mcxent"))
            .build())
    conf.backprop_type = BackpropType.TruncatedBPTT
    conf.tbptt_fwd_length = tbptt
    conf.tbptt_back_length = tbptt
    return MultiLayerNetwork(conf).init()


def char_stream(batch, seq_len, vocab=80):
    """Seeded one-hot character stream with next-character labels."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq_len))
    f = np.eye(vocab, dtype=np.float32)[ids]          # [b, T, vocab]
    l = np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    return DataSet(f, l)


def _charrnn(batch, width, tbptt, seq_len, vocab=80):
    return (graves_lstm_net(vocab, width, tbptt),
            char_stream(batch, seq_len, vocab))


def timed(fn, *args, iters):
    """Mean seconds per call of ``fn(*args)`` over ``iters`` calls, after
    one untimed call that compiles and warms it."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _fit(net, ds):
    """One ``fit``; returns what the step leaves behind, the parameters
    and the loss, for a window to close on."""
    net.fit(ds)
    return net.params, net.score_


def measure(batch=64, width=512, tbptt=50, seq_len=200, fits=3):
    net, ds = _charrnn(batch, width, tbptt, seq_len)
    return batch * seq_len / timed(_fit, net, ds, iters=fits)


def kernel_ab(batch=64, width=512, tbptt=50, seq_len=200):
    """A/B: persistent Pallas LSTM kernel (RW VMEM-resident,
    ops/lstm_cell.py) vs the lax.scan path — same config, same data, by
    toggling the kernel's DL4J_TPU_NO_PERSISTENT_LSTM escape hatch around
    the two legs (the operator's own setting is restored afterwards; if
    they exported the hatch as a rollback, the kernel leg is skipped)."""
    prior = os.environ.get("DL4J_TPU_NO_PERSISTENT_LSTM")
    try:
        if prior:
            print("escape hatch set by operator: skipping the kernel leg",
                  flush=True)
            r_kernel = None
        else:
            r_kernel = measure(batch=batch, width=width, tbptt=tbptt,
                               seq_len=seq_len)
            print(f"persistent-kernel chars/s: {r_kernel:,.0f}", flush=True)
        os.environ["DL4J_TPU_NO_PERSISTENT_LSTM"] = "1"
        r_scan = measure(batch=batch, width=width, tbptt=tbptt,
                         seq_len=seq_len)
        print(f"lax.scan        chars/s: {r_scan:,.0f}", flush=True)
        if r_kernel is not None:
            print(f"kernel speedup: {r_kernel / max(r_scan, 1e-9):.2f}x",
                  flush=True)
    finally:
        if prior is None:
            os.environ.pop("DL4J_TPU_NO_PERSISTENT_LSTM", None)
        else:
            os.environ["DL4J_TPU_NO_PERSISTENT_LSTM"] = prior


def micro(batch=64, width=512, tbptt=50):
    """Decompose the per-grid-step latency (r5: bench 431-580k chars/s vs
    740k raw step vs 7.6M roofline). Times, in isolation on-chip:
      a. empty pallas kernel, same grid as one TBPTT segment (pure
         grid-step overhead)
      b. the recurrent matmul chain alone ([b,H]@[H,4H] x tbptt, lax.scan)
      c. the full persistent-LSTM fwd kernel, one segment
      d. lstm_scan fwd+bwd (kernel + BPTT kernel + outside gemms)
    Each leg prints ms per call and µs per timestep, so the residual
    between (a)-(d) attributes the 33 µs/step directly."""
    from jax.experimental import pallas as pl
    import deeplearning4j_tpu.ops.lstm_cell as lc

    b, H, T = batch, width, tbptt
    U = lc._unroll_factor(T, b, H, 2)
    nb = T // U
    rng = np.random.default_rng(0)
    xp = jnp.asarray(rng.normal(size=(T, b, 4 * H)), jnp.float32)
    rw = jnp.asarray(rng.normal(size=(H, 4 * H)) / np.sqrt(H), jnp.bfloat16)
    bias = jnp.zeros((4 * H,), jnp.float32)
    h0 = jnp.zeros((b, H), jnp.float32)
    c0 = jnp.zeros((b, H), jnp.float32)

    def timeit(fn, *args):
        return timed(fn, *args, iters=20)

    # a. empty kernel on the same grid (streams the same xp blocks so the
    # DMA pattern matches; compute body is a single copy)
    def _empty_kernel(xp_ref, o_ref):
        o_ref[...] = xp_ref[...]

    empty = jax.jit(lambda x: pl.pallas_call(
        _empty_kernel,
        grid=(nb,),
        in_specs=[lc._vspec((U, b, 4 * H), lambda t: (t, 0, 0))],
        out_specs=lc._vspec((U, b, 4 * H), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, b, 4 * H), x.dtype),
        interpret=lc._interpret(),
    )(x))
    ta = timeit(empty, xp)
    print(f"a. empty {nb}-step grid:        {ta*1e3:8.3f} ms "
          f"({ta/T*1e6:6.1f} us/timestep)")

    # b. recurrent matmul chain alone (scan, no pallas)
    def chain(h, _):
        z = jax.lax.dot_general(h.astype(jnp.bfloat16), rw,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return jnp.tanh(z[:, :H]), None

    chain_j = jax.jit(lambda h: jax.lax.scan(chain, h, None, length=T)[0])
    tb = timeit(chain_j, h0)
    print(f"b. bare matmul chain (scan):   {tb*1e3:8.3f} ms "
          f"({tb/T*1e6:6.1f} us/timestep)")

    # c. persistent fwd kernel, one segment (training fwd w/ reserve)
    fwd_j = jax.jit(lambda x, r, h, c: lc._fwd(x, bias, r, None, h, c, None,
                                               x.dtype)[0])
    tc = timeit(fwd_j, xp, rw, h0, c0)
    print(f"c. persistent fwd kernel:      {tc*1e3:8.3f} ms "
          f"({tc/T*1e6:6.1f} us/timestep)")

    # d. lstm_scan fwd+bwd (time-major, as the layer calls it)
    grad_j = jax.jit(jax.grad(lambda x, r: jnp.sum(
        lc.lstm_scan(x, bias, r, None, h0, c0)[0]), argnums=(0, 1)))
    td = timeit(grad_j, xp, rw)
    print(f"d. lstm_scan fwd+bwd:          {td*1e3:8.3f} ms "
          f"({td/T*1e6:6.1f} us/timestep)")

    # e. fwd kernel with bf16 xw / ys streams (what the bf16 policy hands
    # it): a big win here means the step is HBM-stream-bound, not
    # latency-bound
    te = timeit(fwd_j, xp.astype(jnp.bfloat16), rw, h0, c0)
    print(f"e. fwd kernel, bf16 xw/ys:     {te*1e3:8.3f} ms "
          f"({te/T*1e6:6.1f} us/timestep)")

    # f. inference fwd (save_reserve=False: no gates/cseq HBM writes)
    inf_j = jax.jit(lambda x, r, h, c: lc._fwd(
        x, r, None, h, c, None, save_reserve=False)[0])
    tf2 = timeit(inf_j, xp, rw, h0, c0)
    print(f"f. fwd kernel, no reserve:     {tf2*1e3:8.3f} ms "
          f"({tf2/T*1e6:6.1f} us/timestep)")
    print(f"attribution: grid overhead {ta/T*1e6:.1f} us, +matmul "
          f"{(tb-ta)/T*1e6:+.1f} us, +gates/reserve {(tc-tb)/T*1e6:+.1f} us,"
          f" +bwd {(td-tc)/T*1e6:+.1f} us  (per timestep); "
          f"bf16-xp saves {(tc-te)/T*1e6:.1f} us, "
          f"reserve writes cost {(tc-tf2)/T*1e6:.1f} us")


def unroll_sweep(batch=64, width=512, tbptt=50, seq_len=200):
    """VERDICT r4 item 3: sweep DL4J_TPU_LSTM_UNROLL (U timesteps per
    pallas grid step) to find where the sequential-latency division
    saturates. Each U runs in a FRESH SUBPROCESS — the knob is trace-time
    (ops/lstm_cell.py::_unroll_factor), so an in-process sweep would
    silently reuse the first U's compiled step. U candidates divide
    tbptt=50; the kernel itself shrinks U when VMEM doesn't fit, so what
    we sweep is the CAP. Per-U failures are non-fatal by design: a hung U
    must not abort the rest of the sweep."""
    print(f"{'U':>4} {'chars/s':>12} {'vs U=1':>8}")
    base = None
    for u in (1, 2, 5, 10, 25, 50):
        env = dict(os.environ, DL4J_TPU_LSTM_UNROLL=str(u))
        r = _measure_one(env, batch, width, tbptt, seq_len)
        if isinstance(r, str):
            print(f"{u:>4} {r}", flush=True)
            continue
        if u == 1:
            base = r            # the column is "vs U=1", never a rebase
        ratio = f"{r / base:>7.2f}x" if base else "    n/a"
        print(f"{u:>4} {r:>12,.0f} {ratio}", flush=True)


def _measure_one(env, batch, width, tbptt, seq_len, timeout=900):
    """Run one measure() in a fresh subprocess (trace-time env knobs) and
    return chars/s, or a 'FAILED ...' string. Shared by unroll_sweep and
    stream_ab."""
    import json as _json
    import subprocess
    import sys as _sys
    try:
        p = subprocess.run(
            [_sys.executable, os.path.abspath(__file__), "measure-one",
             str(batch), str(width), str(tbptt), str(seq_len)],
            capture_output=True, text=True, env=env, timeout=timeout)
    except subprocess.TimeoutExpired:
        return f"FAILED timeout {timeout}s"
    line = None
    for ln in reversed((p.stdout or "").splitlines()):
        try:
            line = _json.loads(ln)
            break
        except ValueError:
            continue
    if p.returncode or not line:
        return f"FAILED rc={p.returncode} {(p.stderr or '')[-200:]}"
    return line["chars_per_sec"]


def stream_ab(batch=64, width=512, tbptt=50, seq_len=200):
    """A/B DL4J_TPU_LSTM_STREAM_DTYPE (f32 vs bf16 RESERVE: gates, cseq;
    the other streams follow the compute dtype whatever it says) x unroll
    caps. bf16 halves the reserve's HBM stream AND doubles the unroll the
    VMEM budget admits — if the chain is stream-bound this is the 2x lever.
    Trace-time knobs -> fresh subprocess per cell; U candidates divide
    tbptt=50 (the kernel decrements non-divisors, which would silently
    re-measure a duplicate point)."""
    print(f"{'config':>16} {'U':>4} {'chars/s':>12}")
    # under a bf16 reserve the fused two-layer kernel engages at the
    # char-RNN shape (lstm_fused.supported2 VMEM budget) — the +nofuse
    # rows isolate its contribution from the stream-dtype win
    cells = [("float32", 2, {}),
             ("bfloat16", 2, {}),
             ("bfloat16", 5, {}),
             ("bfloat16", 10, {}),
             ("bfloat16+nofuse", 2, {"DL4J_TPU_NO_FUSED_LSTM": "1"}),
             ("bfloat16+nofuse", 5, {"DL4J_TPU_NO_FUSED_LSTM": "1"})]
    for label, u, extra in cells:
        sd = label.split("+")[0]
        env = dict(os.environ, DL4J_TPU_LSTM_STREAM_DTYPE=sd,
                   DL4J_TPU_LSTM_UNROLL=str(u), **extra)
        r = _measure_one(env, batch, width, tbptt, seq_len)
        if isinstance(r, str):
            print(f"{label:>16} {u:>4} {r}", flush=True)
        else:
            print(f"{label:>16} {u:>4} {r:>12,.0f}", flush=True)


def sweep():
    print(f"{'batch':>6} {'width':>6} {'tbptt':>6} {'chars/s':>12}")
    for batch in (64, 128, 256, 512):
        for width in (512, 1024):
            for tbptt in (50, 200):
                try:
                    r = measure(batch=batch, width=width, tbptt=tbptt)
                    print(f"{batch:>6} {width:>6} {tbptt:>6} {r:>12,.0f}",
                          flush=True)
                except Exception as e:  # OOM etc.: record and continue
                    print(f"{batch:>6} {width:>6} {tbptt:>6} FAILED {e}",
                          flush=True)


def lower_tbptt_batch(net, ds):
    """Lower the ONE program ``fit`` runs for a TBPTT batch whose length is
    a multiple of the segment (``nn/training.py`` ``_fit_tbptt``: the
    scan over stacked segments). Shapes only — nothing runs."""
    f, l = ds.features, ds.labels
    b, L = f.shape[0], net.conf.tbptt_fwd_length
    S = f.shape[1] // L
    seg = lambda x: jax.ShapeDtypeStruct((S, b, L) + x.shape[2:], x.dtype)
    abstract = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    return net._ensure_tbptt_scan_step().lower(
        abstract(net.params), abstract(net.states),
        abstract(net.updater_state), jax.ShapeDtypeStruct((), jnp.int32),
        abstract(jax.random.PRNGKey(0)), seg(f), seg(l), None, None,
        abstract(net._init_rnn_state(b)))


def roofline(batch=64, width=512, tbptt=50, seq_len=200):
    """XLA cost model of one TBPTT batch + bound analysis at the device's
    published peaks. (utils.profiling.step_cost covers the plain step;
    this lowers the FUSED tbptt scan, which it cannot.)"""
    peaks = device.peaks(jax.devices()[0].device_kind)
    net, ds = _charrnn(batch, width, tbptt, seq_len)
    ca = lower_tbptt_batch(net, ds).compile().cost_analysis()
    flops = float(ca.get("flops", 0.0))
    by = float(ca.get("bytes accessed", 0.0))
    chars = batch * seq_len
    t_flops = flops / peaks["flops_bf16"]
    t_hbm = by / peaks["hbm_bytes_per_s"]
    bound = "HBM-bandwidth" if t_hbm > t_flops else "compute"
    print(f"per-batch: {flops/1e9:.1f} GFLOP, {by/1e9:.2f} GB accessed")
    print(f"ideal times: compute {t_flops*1e3:.2f} ms, HBM {t_hbm*1e3:.2f} ms"
          f" -> {bound}-bound")
    ideal = chars / max(t_flops, t_hbm)
    print(f"roofline chars/s: {ideal:,.0f}")
    r = measure(batch=batch, width=width, tbptt=tbptt, seq_len=seq_len)
    print(f"measured chars/s: {r:,.0f} ({100*r/ideal:.1f}% of roofline)")


@contextlib.contextmanager
def _env(**kv):
    """Trace-time kernel knobs for the traces made inside the block."""
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


#: max |kernel - scan| over max |scan| per gradient leaf (and for the
#: loss). The hand-written BPTT feeds dz to the MXU in bf16 where AD of the
#: scan keeps it f32 — 8 mantissa bits over a 50-step chain; on the v5e
#: all three variants land at 1.5–1.6 % (PERF.md "Bring-up on the chip")
CHECK_TOL = 5e-2


def kernel_check(batch=64, width=512, tbptt=50, vocab=80,
                 variants=("lstm_cell_f32", "lstm_cell_bf16",
                           "lstm_fused_bf16")):
    """The LSTM kernels against the repo's own oracle, the ``lax.scan``
    path of ``nn/layers/recurrent.py``, on one TBPTT segment of the
    char-RNN bench config: the network's loss and every parameter gradient
    through ``lstm_cell`` with an f32 reserve, ``lstm_cell`` with a bf16
    reserve and ``lstm_fused`` (a bf16 reserve admits it), each compared
    with the same network routed to the scan. The layer hands ``lstm_cell``
    its other streams (xw, ys, dy, dz) in the config's compute dtype, bf16,
    in both variants. The route is read off the lowered
    program — 4, 4 and 2 ``tpu_custom_call``s — so a variant cannot pass by
    quietly taking the oracle's path. Returns {variant: {...}}; raises
    AssertionError past :data:`CHECK_TOL`."""
    import deeplearning4j_tpu.ops.flash_attention as fa

    net, ds = _charrnn(batch, width, tbptt, tbptt, vocab)
    f = net._adapt_input(jnp.asarray(ds.features))
    l = jnp.asarray(ds.labels)

    def run(**knobs):
        with _env(**knobs):
            lowered = jax.jit(jax.value_and_grad(
                lambda p: net._loss_fn(p, net.states, f, l, None, None,
                                       True, None)[0])).lower(net.params)
        t0 = time.perf_counter()
        loss, grads = lowered.compile()(net.params)
        loss = float(loss)
        return (loss, grads, lowered.as_text().count("tpu_custom_call"),
                time.perf_counter() - t0)

    want_loss, want, _, _ = run(DL4J_TPU_NO_PERSISTENT_LSTM="1")
    runs = {
        "lstm_cell_f32": ({"DL4J_TPU_LSTM_STREAM_DTYPE": "float32"}, 4),
        "lstm_cell_bf16": ({"DL4J_TPU_LSTM_STREAM_DTYPE": "bfloat16",
                            "DL4J_TPU_NO_FUSED_LSTM": "1"}, 4),
        "lstm_fused_bf16": ({"DL4J_TPU_LSTM_STREAM_DTYPE": "bfloat16"}, 2),
    }
    report = {}
    for name in variants:
        knobs, n_want = runs[name]
        loss, grads, n_calls, secs = run(**knobs)
        rel = max(
            float(jnp.max(jnp.abs(a - w)) / (jnp.max(jnp.abs(w)) + 1e-12))
            for a, w in zip(jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(want)))
        row = {"custom_calls": n_calls, "loss": round(loss, 4),
               "loss_rel_err": round(abs(loss - want_loss)
                                     / abs(want_loss), 6),
               "grad_rel_err": round(rel, 6), "seconds": round(secs, 2)}
        print(f"lstm {name} b{batch} T{tbptt} H{width}: {row}", flush=True)
        # interpret mode (the CPU tests) lowers a kernel to plain HLO
        assert n_calls == n_want or fa._FORCE_INTERPRET, (name, n_calls)
        assert max(row["loss_rel_err"], rel) < CHECK_TOL, (name, row)
        report[name] = row
    return report


def profile(log_dir, batch=64, width=512):
    net, ds = _charrnn(batch, width, 50, 200)
    jax.block_until_ready(_fit(net, ds))
    jax.profiler.start_trace(log_dir)
    jax.block_until_ready(_fit(net, ds))    # the trace must be complete
    jax.profiler.stop_trace()
    print("trace written to", log_dir)


if __name__ == "__main__":
    cmd = sys.argv[1] if len(sys.argv) > 1 else "sweep"
    if cmd == "sweep":
        sweep()
    elif cmd == "unroll":
        unroll_sweep()
    elif cmd == "measure-one":
        # unroll_sweep child: one measurement, one JSON line
        import json as _json
        b, w, t, s = (int(x) for x in sys.argv[2:6])
        print(_json.dumps({"chars_per_sec": measure(batch=b, width=w,
                                                    tbptt=t, seq_len=s)}))
    elif cmd == "ab":
        kernel_ab()
    elif cmd == "micro":
        micro()
    elif cmd == "stream":
        stream_ab()
    elif cmd == "roofline":
        roofline()
    elif cmd == "check":
        kernel_check()
    elif cmd == "profile":
        profile(sys.argv[2] if len(sys.argv) > 2 else "/tmp/lstm_trace")
    else:
        raise SystemExit(f"unknown command {cmd}")

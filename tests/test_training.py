"""The train step and the fit loop that both containers inherit
(``nn/training.py``): every case runs for a ``MultiLayerNetwork`` and for the
``ComputationGraph`` of the same layers, so neither can drift from the
other."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (Adam, DataSet, ListDataSetIterator,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                Sgd)
from deeplearning4j_tpu.monitor import get_health, get_registry
from deeplearning4j_tpu.nn.conf import BackpropType
from deeplearning4j_tpu.nn.conf.layers import (LSTM, DenseLayer,
                                               EmbeddingSequenceLayer,
                                               LoopedBlockStack,
                                               LoopLMOutputLayer, OutputLayer,
                                               RnnOutputLayer, SimpleRnn)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.optimize.listeners import TrainingListener

both = pytest.mark.parametrize("kind", ["mln", "cg"])


def make(kind, layers, updater=None, seed=5, iterations=1, tbptt=None,
         tbptt_back=None):
    """``layers`` as a chain in either container. The graph's vertices are
    named ``"0"``, ``"1"``, … so that both key their parameters alike."""
    builder = (NeuralNetConfiguration.builder().seed(seed)
               .updater(updater or Sgd(learning_rate=0.1)).activation("tanh")
               .iterations(iterations))
    if kind == "mln":
        chain = builder.list()
        for layer in layers:
            chain = chain.layer(layer)
    else:
        chain = builder.graph_builder().add_inputs("in")
        for i, layer in enumerate(layers):
            chain = chain.add_layer(str(i), layer, str(i - 1) if i else "in")
        chain = chain.set_outputs(str(len(layers) - 1))
    if tbptt:
        chain = (chain.backprop_type(BackpropType.TruncatedBPTT)
                 .t_bptt_forward_length(tbptt)
                 .t_bptt_backward_length(tbptt_back or tbptt))
    conf = chain.build()
    return (MultiLayerNetwork if kind == "mln" else ComputationGraph)(
        conf).init()


def dense():
    return [DenseLayer(n_in=4, n_out=6),
            OutputLayer(n_in=6, n_out=3, activation="softmax", loss="mcxent")]


def lstm():
    return [LSTM(n_in=3, n_out=8, activation="tanh"),
            RnnOutputLayer(n_in=8, n_out=2, activation="softmax",
                           loss="mcxent")]


def rows(n=8, seed=0):
    rng = np.random.default_rng(seed)
    return DataSet(rng.normal(size=(n, 4)).astype(np.float32),
                   np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])


def sequences(T, n=6, seed=43, masked=False):
    rng = np.random.default_rng(seed)
    f = rng.normal(size=(n, T, 3)).astype(np.float32)
    l = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (n, T))]
    if not masked:
        return DataSet(f, l)
    m = (np.arange(T)[None, :] < rng.integers(T // 2, T + 1, (n, 1))).astype(
        np.float32)
    return DataSet(f, l, features_mask=m, labels_mask=m)


def assert_same_params(a, b, rtol=1e-6, atol=1e-7):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol,
                                   atol=atol)


@both
def test_iterations_config_scanned_equals_sequential(kind):
    """0.9.x ``Builder.iterations(n)``: n optimizer steps per minibatch,
    compiled as ONE lax.scan program — must match n sequential fits exactly
    (dropout-free net, same seed)."""
    ds = rows()
    scanned, sequential = make(kind, dense(), iterations=3), make(kind, dense())
    scanned.fit(ds)
    for _ in range(3):
        sequential.fit(ds)
    assert scanned.iteration_count == 3 == sequential.iteration_count
    assert_same_params(scanned.params, sequential.params)


@both
def test_single_iteration_applies_one_update_under_iterations(kind):
    """``_fit_batch(single_iteration=True)`` (ParallelWrapper's tail batch):
    one update whatever ``iterations(n)`` says, from a program of its own;
    with ``iterations(1)`` it is the plain step itself."""
    ds = rows()
    scanned, plain = make(kind, dense(), iterations=3), make(kind, dense())
    scanned._fit_batch(ds, single_iteration=True)
    plain.fit(ds)
    assert scanned.iteration_count == 1
    assert_same_params(scanned.params, plain.params)
    assert scanned._jit_step is None        # the scanned step was not built
    assert scanned._ensure_step(single_iteration=True) \
        is not scanned._ensure_step()
    assert plain._ensure_step(single_iteration=True) is plain._jit_step


@both
def test_iterations_config_tbptt_scanned(kind):
    """iterations(n) on the TBPTT path: n optimizer steps per segment inside
    one scanned program, equal to the sequential-iteration semantics."""
    layers = [SimpleRnn(n_in=3, n_out=5),
              RnnOutputLayer(n_in=5, n_out=2, activation="softmax",
                             loss="mcxent")]
    net = make(kind, layers, iterations=2, tbptt=4)
    net.fit(sequences(T=8, n=2))
    assert net.iteration_count == 4         # 2 segments x 2 iterations
    assert np.isfinite(float(net.score_))


@both
def test_tbptt_fused_scan_matches_per_segment_loop(kind):
    """The fused lax.scan TBPTT path (one dispatch per batch) must produce
    the same params as dispatching each segment separately (math identical,
    only the launch granularity changes)."""
    ds = sequences(T=12, masked=True)       # 3 equal segments: fused path
    fused = make(kind, lstm(), Sgd(learning_rate=1e-2), seed=41, tbptt=4)
    fused._fit_batch(ds)
    assert fused.iteration_count == 3

    manual = make(kind, lstm(), Sgd(learning_rate=1e-2), seed=41, tbptt=4)
    step = manual._ensure_tbptt_step()
    rnn = manual._init_rnn_state(6)
    streams = manual._batch_streams(ds)
    for s in range(3):
        f, l, fm, lm = jax.tree_util.tree_map(
            lambda x: x[:, 4 * s:4 * (s + 1)], streams)
        (manual.params, manual.states, manual.updater_state, loss,
         rnn) = step(manual.params, manual.states, manual.updater_state,
                     jnp.asarray(s, jnp.int32), manual._next_rng(),
                     f, l, fm, lm, rnn)
    assert_same_params(fused.params, manual.params, rtol=1e-5, atol=1e-6)


@both
def test_ragged_tbptt_tail_matches_scanned_segments(kind):
    """A length that the segment does not divide is dispatched segment by
    segment with the carries threaded on the host; the same sequences padded
    to whole segments under a mask run as the one scanned program. Both
    apply one update per segment and end with the same parameters."""
    T, L, n = 10, 4, 6
    short = sequences(T=T, n=n)
    ones = np.ones((n, T), np.float32)
    ragged_ds = DataSet(short.features, short.labels, features_mask=ones,
                        labels_mask=ones)
    pad = lambda x: np.concatenate(
        [x, np.zeros((n, 12 - T) + x.shape[2:], x.dtype)], axis=1)
    padded_ds = DataSet(pad(short.features), pad(short.labels),
                        features_mask=pad(ones), labels_mask=pad(ones))

    ragged = make(kind, lstm(), Sgd(learning_rate=1e-2), seed=41, tbptt=L)
    padded = make(kind, lstm(), Sgd(learning_rate=1e-2), seed=41, tbptt=L)
    ragged.fit(ragged_ds)
    padded.fit(padded_ds)
    assert ragged.iteration_count == 3 == padded.iteration_count
    assert (True, False, 1) in ragged._steps        # segment by segment
    assert (True, True, 1) in padded._steps         # the one scan
    assert_same_params(ragged.params, padded.params, rtol=1e-5, atol=1e-6)


@both
def test_tbptt_back_length_differing_from_forward_warns_once(kind, caplog):
    net = make(kind, lstm(), tbptt=4, tbptt_back=2)
    ds = sequences(T=8)
    with caplog.at_level(logging.WARNING,
                         logger="deeplearning4j_tpu.nn.training"):
        net.fit(ds)
        net.fit(ds)
    warned = [r for r in caplog.records
              if "tbptt_back_length=2 differs" in r.getMessage()]
    assert len(warned) == 1
    assert net.iteration_count == 4


@both
def test_halt_ends_a_fit_and_the_next_fit_clears_it(kind):
    class HaltAtOnce(TrainingListener):
        def iteration_done(self, model, iteration, score):
            model.halt_requested = True

    net = make(kind, dense())
    batches = [rows(seed=s) for s in range(3)]
    try:
        net.set_listeners(HaltAtOnce())
        net.fit(ListDataSetIterator(batches), epochs=5)
        assert net.iteration_count == 1 and net.epoch_count == 1
        assert net.halt_requested
        net.set_listeners()
        net.fit(ListDataSetIterator(batches), epochs=2)
        assert net.iteration_count == 7 and net.epoch_count == 3
        assert not net.halt_requested
        assert get_health().snapshot()["halted"] is None
    finally:
        get_health().reset()


@both
def test_error_in_fit_reaches_the_listeners_and_drains_the_steps(kind):
    """The error seam: ``on_training_error`` for every listener, and the
    steps dispatched before the failure are counted before it unwinds."""
    seen = []

    class Recorder(TrainingListener):
        def iteration_done(self, model, iteration, score):
            seen.append(iteration)

        def on_training_error(self, model, exception):
            seen.append(exception)

    def breaks():
        yield from (rows(seed=s) for s in range(3))
        raise RuntimeError("iterator broke")

    applied = get_registry().counter("training_iterations_total",
                                     "optimizer iterations applied")
    quiet = make(kind, dense())
    before = applied.value
    with pytest.raises(RuntimeError, match="iterator broke"):
        quiet.fit(breaks())
    assert applied.value - before == 3      # drained without a listener

    net = make(kind, dense()).set_listeners(Recorder())
    with pytest.raises(RuntimeError, match="iterator broke") as raised:
        net.fit(breaks())
    assert seen == [0, 1, 2, raised.value]


@both
def test_a_looped_stack_sets_the_gauge_under_its_containers_label(kind):
    layers = [EmbeddingSequenceLayer(n_in=20, n_out=16),
              LoopedBlockStack(n_in=16, n_out=16, num_blocks=2, num_passes=3,
                               num_heads=2, head_dim=8, n_hidden=24),
              LoopLMOutputLayer(n_in=16, n_out=20)]
    net = make(kind, layers, Adam(learning_rate=1e-3))
    ids = np.random.default_rng(1).integers(0, 20, (2, 9), dtype=np.int32)
    net.fit(DataSet(np.ascontiguousarray(ids[:, :-1]),
                    np.ascontiguousarray(ids[:, 1:])))
    assert np.isfinite(float(net.score_))
    gauge = get_registry().snapshot()["looped_block_applications"]
    assert [r["value"] for r in gauge
            if r["labels"] == {"network": kind}] == [3 * 2]


def test_masks_reach_the_gradient_of_both_containers_alike():
    """``compute_gradient_and_score`` hands the data set's masks to the
    loss, for a graph as for a ``MultiLayerNetwork`` (the graph's copy used
    to pass None for both)."""
    masked, bare = sequences(T=8, masked=True), sequences(T=8)
    assert masked.features_mask.min() == 0
    mln, cg = make("mln", lstm()), make("cg", lstm())
    cg.params = jax.tree_util.tree_map(lambda x: x, mln.params)
    g_mln, loss_mln = mln.compute_gradient_and_score(masked)
    g_cg, loss_cg = cg.compute_gradient_and_score(masked)
    assert loss_cg == pytest.approx(loss_mln, rel=1e-6)
    assert_same_params(g_cg, g_mln, rtol=1e-5, atol=1e-7)
    _, loss_bare = cg.compute_gradient_and_score(bare)
    assert abs(loss_bare - loss_cg) > 1e-3
    assert loss_cg == pytest.approx(cg.score(masked, training=True),
                                    rel=1e-6)

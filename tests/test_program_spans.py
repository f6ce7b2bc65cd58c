"""The program's own spans (``monitor/tracer.py``, the table in
docs/OBSERVABILITY.md "Span Tracer"): with the monitor off they reach a
profiler trace and not the ring buffer, with it on the ring holds them under
``epoch``; and the completion fetch that left the ``step`` span
(``monitor.StepCompletions``): never the newest loss without a listener,
eager with one, the counters whole when ``fit`` returns; and the set-up
spans (``init`` and its two children, ``pw/place_model``), which the tracer
keeps whatever the switch says."""
import glob

import jax
import numpy as np
import pytest

import deeplearning4j_tpu.monitor as monitor
from deeplearning4j_tpu import (DataSet, ListDataSetIterator,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                Sgd)
from deeplearning4j_tpu.datasets.dataset import DataSetIterator
from deeplearning4j_tpu.monitor import (StepCompletions, get_health,
                                        get_registry, get_tracer)
from deeplearning4j_tpu.nn.conf import BackpropType
from deeplearning4j_tpu.nn.conf.layers import (LSTM, DenseLayer, OutputLayer,
                                               RnnOutputLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMode

FIT = {"epoch", "fit/next_batch", "fit/prepare", "step", "input/transform",
       "input/put_ahead"}
PW = {"pw/place_model", "pw/group", "pw/global_batch", "pw/step",
      "pw/resolve_score", "input/transform"}


def _builder():
    return (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(learning_rate=0.1)).activation("tanh"))


def _mln(params=None):
    return MultiLayerNetwork(
        _builder().list().layer(DenseLayer(n_in=4, n_out=8))
        .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                           loss="mcxent")).build()).init(params)


def _graph():
    return ComputationGraph(
        _builder().graph_builder().add_inputs("in")
        .add_layer("dense", DenseLayer(n_in=4, n_out=8), "in")
        .add_layer("out", OutputLayer(n_in=8, n_out=3, activation="softmax",
                                      loss="mcxent"), "dense")
        .set_outputs("out").build()).init()


def _tbptt():
    return MultiLayerNetwork(
        _builder().list().layer(LSTM(n_in=3, n_out=8))
        .layer(RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                              loss="mcxent"))
        .backprop_type(BackpropType.TruncatedBPTT).t_bptt_forward_length(4)
        .t_bptt_backward_length(4).build()).init()


def _batches(n=4, rows=16, seed=0, nan_at=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        f = rng.normal(size=(rows, 4)).astype(np.float32)
        if i == nan_at:
            f[:] = np.nan
        out.append(DataSet(f, np.eye(3, dtype=np.float32)[
            rng.integers(0, 3, rows)]))
    return out


def _sequences(n=2):
    rng = np.random.default_rng(0)
    return [DataSet(rng.normal(size=(4, 8, 3)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, (4, 8))])
            for _ in range(n)]


def _fit_mln():
    _mln().fit(ListDataSetIterator(_batches()))


def _fit_graph():
    _graph().fit(ListDataSetIterator(_batches()))


def _fit_tbptt():
    _tbptt().fit(ListDataSetIterator(_sequences()))


def _fit_wrapper():
    (ParallelWrapper.Builder(_mln()).workers(4)
     .training_mode(TrainingMode.AVERAGING).averaging_frequency(1).build()
     .fit(ListDataSetIterator(_batches(n=8))))


CASES = [(_fit_mln, FIT), (_fit_graph, FIT), (_fit_tbptt, FIT),
         (_fit_wrapper, PW)]
IDS = ["multilayer", "graph", "tbptt", "parallel_wrapper"]


@pytest.fixture
def monitor_off():
    monitor.set_enabled(False)
    try:
        yield
    finally:
        monitor.set_enabled(True)


def _host_events(log_dir):
    """{name: [stats]} of every event of the trace's host planes."""
    from jax.profiler import ProfileData
    found = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    assert found, "the profiler left no .xplane.pb"
    out = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


@pytest.mark.parametrize("fit,names", CASES, ids=IDS)
def test_monitor_off_spans_reach_the_profiler_and_not_the_ring(
        fit, names, tmp_path, monitor_off):
    tracer = get_tracer()
    tracer.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fit()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    assert names <= set(events), sorted(names - set(events))
    assert len(tracer) == 0
    # the dispatch span is a StepTraceAnnotation: it carries its step number
    step = "pw/step" if "pw/step" in names else "step"
    assert all("step_num" in stats for stats in events[step])
    # with the monitor off and no listener nothing is fetched
    assert "fit/resolve" not in events


@pytest.mark.parametrize("fit,names", CASES, ids=IDS)
def test_monitor_on_ring_holds_the_spans_under_epoch(fit, names):
    tracer = get_tracer()
    tracer.clear()
    fit()
    events = tracer.events()
    by_name = {}
    for ev in events:
        by_name.setdefault(ev["name"], []).append(ev)
    assert names <= set(by_name), sorted(names - set(by_name))
    if "epoch" not in names:
        return
    assert "fit/resolve" in by_name
    epochs = {ev["args"]["span_id"] for ev in by_name["epoch"]}
    for name in ("fit/next_batch", "fit/prepare", "step", "fit/resolve"):
        assert all(ev["args"].get("parent_span_id") in epochs
                   for ev in by_name[name]), name
    # prefetch workers run beside the fit thread: no epoch above them
    assert all("parent_span_id" not in ev["args"]
               for ev in by_name["input/transform"])


def test_a_span_gives_its_seconds_and_skips_the_ring_when_off(monitor_off):
    tracer = monitor.Tracer()
    span = tracer.span("x", step_num=3)
    with span as ctx:
        assert tracer.current_span() == ctx      # the context still nests
    assert span.seconds >= 0 and len(tracer) == 0
    monitor.set_enabled(True)
    with tracer.span("y", step_num=4):
        pass
    assert tracer.events()[0]["args"]["step_num"] == 4


# ------------------------------------------------------- StepCompletions
class _Loss:
    """Stands in for a device array (in the manner of the benchmark
    harness's ``_Loss``): never ready by itself, records when it is fetched
    and which step was the newest dispatched then."""

    def __init__(self, value, iteration, model, log):
        self.value, self.iteration = value, iteration
        self.model, self.log = model, log

    def is_ready(self):
        return False

    def __float__(self):
        self.log.append((self.iteration, self.model.iteration_count - 1))
        return float(self.value)


@pytest.fixture
def fetches(monkeypatch):
    """Every completion fetch of a ``fit`` as ``(step fetched, newest step
    dispatched at that moment)``: the helper is instrumented here, the
    program has no option for it."""
    log = []
    dispatched = StepCompletions.dispatched

    def spying(self, loss, batch_size, etl_ms=None):
        model = self._model
        dispatched(self, _Loss(loss, model.iteration_count - 1, model, log),
                   batch_size, etl_ms)

    monkeypatch.setattr(StepCompletions, "dispatched", spying)
    return log


def _iterations():
    return get_registry().counter("training_iterations_total",
                                  "optimizer iterations applied").value


def test_fit_never_waits_for_the_newest_loss(fetches):
    before = _iterations()
    net = _mln()
    net.fit(ListDataSetIterator(_batches(n=7)))
    lag = StepCompletions.LAG
    assert [step for step, _ in fetches] == list(range(7))
    # in the loop a step is fetched once LAG newer ones are dispatched; the
    # last LAG are the drain's, after the loop
    assert [newest - step for step, newest in fetches[:-lag]] == [lag] * 5
    assert [newest for _, newest in fetches[-lag:]] == [6, 6]
    # nothing is left behind when fit returns
    assert _iterations() - before == 7 == net.iteration_count
    assert get_health().snapshot()["last_iteration"] == 6


def test_tbptt_fit_lags_the_same_way(fetches):
    before = _iterations()
    net = _tbptt()
    net.fit(ListDataSetIterator(_sequences(n=4)))
    # one completion per scanned call, two segments each
    assert [step for step, _ in fetches] == [1, 3, 5, 7]
    assert fetches[0] == (1, 5) and fetches[1] == (3, 7)
    assert _iterations() - before == 4


def test_with_a_listener_the_fetch_is_eager(fetches):
    seen = []

    class Listener(TrainingListener):
        def iteration_done(self, model, iteration, score):
            seen.append((iteration, model.iteration_count - 1))

    net = _graph()
    net.set_listeners(Listener())
    net.fit(ListDataSetIterator(_batches(n=4)))
    assert fetches == [(i, i) for i in range(4)]
    assert seen == fetches          # the callback sees the model of its step


class _Watching(DataSetIterator):
    """A synchronous iterator that notes, at every ``next``, whether
    ``/healthz`` has seen a NaN yet."""

    def __init__(self, batches):
        self.batches, self.at, self.nan_seen = batches, 0, []

    def async_supported(self):
        return False             # no prefetch thread: next() runs in the loop

    def reset(self):
        self.at = 0

    def batch(self):
        return self.batches[0].num_examples()

    def __next__(self):
        self.nan_seen.append(bool(get_health().snapshot()["nan"]))
        if self.at >= len(self.batches):
            raise StopIteration
        self.at += 1
        return self.batches[self.at - 1]


def test_a_nan_loss_flips_healthz_within_the_lag(fetches):
    get_health().reset()
    try:
        it = _Watching(_batches(n=7, nan_at=1))
        _mln().fit(it)
        # step 1 is the NaN; by the next() after step 1 + LAG was dispatched
        # the health state has it, and not before its own dispatch
        first = it.nan_seen.index(True)
        assert 2 <= first <= 1 + StepCompletions.LAG + 1
        assert get_health().snapshot()["healthy"] is False
    finally:
        get_health().reset()


def test_ready_losses_resolve_without_waiting_and_errors_still_drain():
    done = []

    class Model:
        listeners = []
        iteration_count = 0

    class Ready(float):
        def is_ready(self):
            return True

    model = Model()
    completions = StepCompletions(model)
    before = _iterations()
    for i in range(3):
        model.iteration_count += 1
        completions.dispatched(Ready(0.5), 4)
        done.append(_iterations() - before)
    assert done == [1, 2, 3]         # each at once: nothing pending
    # the error path of fit: what was dispatched before the failure counts
    net = _mln()

    def boom():
        yield from _batches(n=3)
        raise RuntimeError("iterator broke")

    before = _iterations()
    with pytest.raises(RuntimeError, match="iterator broke"):
        net.fit(boom())
    assert _iterations() - before == 3


# ----------------------------------------------------------- set-up spans
def _kept(name):
    return [r for r in get_tracer().kept() if r["name"] == name]


@pytest.mark.parametrize("build,network", [(_mln, "mln"), (_graph, "cg")],
                         ids=["multilayer", "graph"])
def test_init_is_a_kept_span_over_its_params_and_updater_state(
        build, network, monitor_off):
    get_tracer().clear()
    net = build()
    assert len(get_tracer()) == 0               # the ring obeys the switch
    (init,), (params,), (state,) = (_kept("init"), _kept("init/params"),
                                    _kept("init/updater_state"))
    leaves = jax.tree_util.tree_leaves(net.params)
    assert init["cat"] == "setup" and init["args"] == {
        "network": network, "leaves": len(leaves),
        "parameters": net.num_params(),
        "bytes": sum(x.nbytes for x in leaves)}
    for child in (params, state):
        assert child["parent_span_id"] == init["span_id"]
        assert init["start"] <= child["start"] <= child["end"] <= init["end"]
    assert params["end"] <= state["start"]
    seconds = lambda r: r["end"] - r["start"]
    assert seconds(init) > seconds(params) + seconds(state)
    # what nn/weights.py summed into each: host drawing and the hand-over
    # (Sgd keeps no state: the updater's span is there and made nothing)
    assert params["args"]["leaves"] == len(leaves)
    assert state["args"] == {"draw_s": 0.0, "place_s": 0.0, "leaves": 0}
    args = params["args"]
    assert set(args) == {"draw_s", "place_s", "leaves"}
    assert args["draw_s"] > 0 and args["place_s"] > 0
    assert args["draw_s"] + args["place_s"] <= seconds(params)


def test_init_with_given_parameters_takes_them_and_draws_the_state():
    given = _mln().params
    get_tracer().clear()
    net = _mln(given)
    assert net.params is given and set(net.states) == set(given)
    assert _kept("init")[0]["args"]["parameters"] == net.num_params()


def test_parallel_wrapper_places_the_model_once_per_fit(monitor_off):
    get_tracer().clear()
    net = _mln()
    wrapper = (ParallelWrapper.Builder(net).workers(4)
               .training_mode(TrainingMode.AVERAGING).averaging_frequency(1)
               .build())
    state = jax.tree_util.tree_leaves(
        (net.params, net.states, net.updater_state))
    for calls in (1, 2):
        wrapper.fit(ListDataSetIterator(_batches(n=8)))
        placed = _kept("pw/place_model")
        assert len(placed) == calls
    assert all(r["cat"] == "setup" and r["args"] == {
        "leaves": len(state), "bytes": sum(x.nbytes for x in state)}
        for r in placed)

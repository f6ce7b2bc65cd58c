"""The train step and the fit loop, written once for both containers.

``MultiLayerNetwork`` and ``ComputationGraph`` inherit :class:`_TrainingBase`
and keep what is theirs: the constructor and ``init``, the forward pass,
``_loss_fn``, and how a data set becomes the step's streams. Everything from
``value_and_grad`` to ``StepCompletions`` is here, so a change to the step or
to the loop lands once and every cell of the benchmark runs it.

The step's four streams ``f, l, fm, lm`` (features, labels, their masks) are
pytrees in the container's own shape: bare arrays for ``MultiLayerNetwork``,
tuples of arrays for ``ComputationGraph``, None for an absent mask.
"""
from __future__ import annotations

import contextlib
import logging

import numpy as np
import jax
import jax.numpy as jnp

from .conf import BackpropType, CacheMode
from .conf.dropout import apply_constraints
from .layers.base import remat_enabled, remat_policy
from ..datasets.dataset import DataSet, MultiDataSet, ListDataSetIterator
from ..datasets.prefetch import wrap_for_training
from .weights import read_init_clock, reset_init_clock
from ..optimize.updater import NetworkUpdater, normalize_gradients
from .. import monitor as _mon
from ..monitor.jitwatch import monitored_jit, watch_compile_phases

log = logging.getLogger(__name__)

_tm = jax.tree_util.tree_map


def _n_iterations(gc):
    """Configured optimizer iterations per minibatch/segment (0.9.x
    ``iterations`` config), with the legacy-config fallback in ONE place."""
    return int(getattr(gc, "iterations", 1) or 1)


def _scan_iterations(step, n_iter, with_rnn_state=False):
    """Wrap a train-step fn in a ``lax.scan`` running ``n_iter`` optimizer
    iterations on the SAME minibatch inside one compiled program — the
    TPU-native realization of the reference's 0.9.x ``iterations`` config
    (``NeuralNetConfiguration.Builder.iterations``): small-model training
    pays the dispatch latency once per n steps. Same signature as ``step``;
    the iteration counter advances per scanned step and the rng is split so
    dropout differs across iterations; returns the LAST loss (and, on the
    TBPTT variant, the last rnn state — every iteration of a segment starts
    from the same carried-in state, reference solver-per-segment
    semantics)."""
    def scanned(params, states, upd_state, iteration, rng, f, l, fm, lm,
                rnn_state_in=None):
        def body(carry, i):
            params, states, upd_state, rng = carry
            rng, key = jax.random.split(rng)
            out = step(params, states, upd_state, iteration + i, key, f, l,
                       fm, lm, rnn_state_in)
            params, states, upd_state, loss = out[:4]
            extra = out[4] if with_rnn_state else None
            return (params, states, upd_state, rng), (loss, extra)
        (params, states, upd_state, _), (losses, extras) = jax.lax.scan(
            body, (params, states, upd_state, rng),
            jnp.arange(n_iter, dtype=jnp.int32))
        if with_rnn_state:
            last_rnn = _tm(lambda x: x[-1], extras)
            return params, states, upd_state, losses[-1], last_rnn
        return params, states, upd_state, losses[-1]
    return scanned


def _build_tbptt_scan(step, n_iter):
    """Jit a with-rnn-state train step into ONE program running the whole
    TBPTT segment loop (``lax.scan`` over stacked segments, params/updater/
    RNN state carried, segments detached by the step itself). One device
    dispatch per minibatch instead of one per segment: a 200-char/50-TBPTT
    batch saves 3 of 4 dispatches (same move as the ``iterations(n)`` scan,
    applied to the segment dimension). Inputs are segment-stacked pytrees
    ``[S, ...]`` (tuples of streams for the graph container ride through
    untouched — scan maps over every leaf's leading dim)."""
    if n_iter > 1:
        step = _scan_iterations(step, n_iter, with_rnn_state=True)

    def scanned(params, states, upd, it0, rng, f_s, l_s, fm_s, lm_s, rnn0):
        def body(carry, xs):
            params, states, upd, rnn, s = carry
            f_c, l_c, fm_c, lm_c = xs
            params, states, upd, loss, rnn = step(
                params, states, upd, it0 + s * n_iter,
                jax.random.fold_in(rng, s), f_c, l_c, fm_c, lm_c, rnn)
            return (params, states, upd, rnn, s + 1), loss

        init = (params, states, upd, rnn0, jnp.asarray(0, jnp.int32))
        (params, states, upd, _, _), losses = jax.lax.scan(
            body, init, (f_s, l_s, fm_s, lm_s))
        return params, states, upd, losses[-1]

    return monitored_jit(scanned, name="nn/tbptt_scan",
                         donate_argnums=(0, 2))


def _map_streams(fn, x):
    """Apply ``fn`` to every stream array — bare arrays (MultiLayerNetwork),
    tuples of optional streams (ComputationGraph), None passthrough. Exactly
    ``tree_map`` semantics; the alias names the intent at the call sites."""
    return jax.tree_util.tree_map(fn, x)


def _device_arrays(ds, cached=False):
    """``(features, labels, feature masks, label masks)`` of a DataSet (bare
    arrays) or a MultiDataSet (tuples) as device arrays, None where absent.
    ``cached`` (``CacheMode.DEVICE``) keeps them on the CALLER's data set, so
    a later fit of the same one finds them. Nothing goes through a data
    set's constructor: it calls ``np.asarray``, which would pull a put-ahead
    (device-resident) batch straight back to the host."""
    if cached:
        return ds.device_arrays()
    if isinstance(ds, MultiDataSet):
        host = tuple(None if s is None else tuple(s) for s in
                     (ds.features, ds.labels, ds.features_masks,
                      ds.labels_masks))
    else:
        host = (ds.features, ds.labels, ds.features_mask, ds.labels_mask)
    return _map_streams(jnp.asarray, host)


@contextlib.contextmanager
def _drawing(name):
    """The set-up span ``name`` around eager initialisers: every array is
    made on the host and handed to the device by ``nn/weights.py``, which
    sums its two parts of the time on this thread; the span takes the sums
    as ``draw_s`` and ``place_s`` over ``leaves`` arrays when it closes (two
    clock reads an array and a half, and no span per leaf)."""
    reset_init_clock()
    span = _mon.get_tracer().span(name, cat="setup")
    with span:
        yield
        span.note(**read_init_clock())


class _TrainingBase:
    """What both containers train through. A container supplies:

    - ``_jit_prefix``: the first part of its programs' jitwatch names and
      its ``network=`` label (``"mln"`` / ``"cg"``);
    - ``_layers()``: its ``(params key, layer conf, impl)`` triples, in
      order (an impl's ``index`` keys its stream state);
    - ``_adapt_inputs(f)``: the features as the layers take them (NCHW →
      NHWC), traced inside the step;
    - ``_batch_streams(ds, cached=False)``: a data set as the step's
      ``(f, l, fm, lm)``;
    - ``_loss_fn(params, states, f, l, fm, lm, train, rng,
      rnn_state_in=None)`` → ``(loss, (new_states, rnn_state_out))``;
    - optionally ``_before_fit(iterator)``.
    """

    _jit_prefix = None

    def __init__(self, conf):
        self.conf = conf
        self.gc = conf.global_conf
        self.params = None          # {"0": {"W": ..., "b": ...}, ...}
        self.states = None          # non-trainable layer state
        self.updater = None         # NetworkUpdater
        self.updater_state = None
        self.iteration_count = 0
        self.epoch_count = 0
        self.listeners = []
        self.score_ = float("nan")
        self.last_batch_size = 0
        self.halt_requested = False  # TrainingHealthListener "halt" action
        self._completions = _mon.StepCompletions(self)   # fit starts its own
        self._rng = None
        self._steps = {}            # (with_rnn_state, scan, n_iter) → jitted
        self._jit_score = {}

    def _before_fit(self, iterator):
        """Called by ``fit`` with the data as an iterator, before the
        prefetch pipeline wraps it."""

    # ----------------------------------------------------------------- init
    def init(self, params=None):
        """Build the layers' implementations and initialise parameters, layer
        state and updater state (reference ``init()``,
        ``MultiLayerNetwork.java`` :541, ``ComputationGraph.java`` :394);
        ``params`` given, they are taken as they are. The call is the
        ``init`` span (``cat="setup"``: kept by the tracer whatever the
        monitor switch says, docs/OBSERVABILITY.md "Start-up"), with
        ``init/params`` and ``init/updater_state`` below it."""
        watch_compile_phases()
        span = _mon.get_tracer().span("init", cat="setup",
                                      network=self._jit_prefix)
        with span:
            self._init(params)
            leaves = jax.tree_util.tree_leaves(self.params)
            span.note(leaves=len(leaves), parameters=self.num_params(),
                      bytes=sum(int(v.nbytes) for v in leaves))
        return self

    def _init_layers(self, keyed, params):
        """``params`` and ``states`` from the ``(params key, impl, rng)``
        triples ``keyed``, under the ``init/params`` span."""
        with _drawing("init/params"):
            made = {key: impl.init(rng) for key, impl, rng in keyed}
        self.params = (params if params is not None
                       else {key: p for key, (p, _) in made.items()})
        self.states = {key: s for key, (_, s) in made.items()}

    def _init_updater(self, layer_updaters):
        """One updater per layer, and its state for the parameters (Adam's
        zeros), under the ``init/updater_state`` span."""
        self.updater = NetworkUpdater(layer_updaters)
        with _drawing("init/updater_state"):
            self.updater_state = self.updater.init_state(self.params)

    # ---------------------------------------------------------- train step
    def _raw_update_core(self, grads_reduce=None):
        """Shared step core: loss → AD grads → gradient normalization →
        updater transform. Returns ``(updates, new_states, new_upd, loss,
        rnn_out)`` WITHOUT applying the update, so both ``_raw_step`` (apply
        in-graph) and ``_raw_update_step`` (ship the update through the
        SHARED_GRADIENTS codec) stay in lock-step by construction.

        ``grads_reduce(grads, loss, new_states) -> (grads, loss,
        new_states)``: optional cross-device reduction hook applied right
        after AD, BEFORE the minimize flip / normalization / updater —
        the seam ``parallel.sequence.sequence_parallel_step`` uses to psum
        time-sliced gradients while inheriting this core's remat/adapt/aux
        behavior instead of duplicating it."""
        gn_mode = self.gc.gradient_normalization
        gn_thresh = self.gc.gradient_normalization_threshold
        minimize = self.gc.minimize

        use_remat = remat_enabled(self.gc,
                                  [impl for _, _, impl in self._layers()])

        def core(params, states, upd_state, iteration, rng, f, l, fm, lm,
                 rnn_state_in=None):
            f = self._adapt_inputs(f)

            def loss_fn(p):
                return self._loss_fn(p, states, f, l, fm, lm, True, rng,
                                     rnn_state_in)

            if use_remat:
                loss_fn = jax.checkpoint(loss_fn, policy=remat_policy())
            (loss, (new_states, rnn_out)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if grads_reduce is not None:
                grads, loss, new_states = grads_reduce(grads, loss,
                                                       new_states)
            with jax.named_scope("updater"):
                if not minimize:
                    grads = _tm(lambda g: -g, grads)
                grads = normalize_gradients(grads, gn_mode, gn_thresh)
                updates, new_upd = self.updater.apply(upd_state, grads,
                                                      iteration)
            return updates, new_states, new_upd, loss, rnn_out

        return core

    def _raw_step(self, with_rnn_state=False):
        """The pure (unjitted) train-step function. ``_build_step`` jits it for
        single-device training; ``deeplearning4j_tpu.parallel`` re-jits it with
        explicit ``NamedSharding``s over a device mesh (SPMD data parallelism —
        the reference's ParallelWrapper role, SURVEY.md §2.4/§7 Phase 3)."""
        update = self._raw_update_step(with_rnn_state)

        def step(params, states, upd_state, iteration, rng, f, l, fm, lm,
                 rnn_state_in=None):
            updates, *rest = update(params, states, upd_state, iteration,
                                    rng, f, l, fm, lm, rnn_state_in)
            with jax.named_scope("updater"):
                new_params = _tm(lambda p, u: p - u.astype(p.dtype), params,
                                 updates)
                new_params = self._apply_constraints(new_params)
            return (new_params, *rest)

        return step

    def _raw_update_step(self, with_rnn_state=False):
        """Updater-transformed update without application — the
        SHARED_GRADIENTS wire seam: the reference encodes post-updater updates
        for peer broadcast (``SymmetricTrainer`` via
        ``EncodingHandler.java:136``), so the codec must see the update, not
        the raw gradient. ``with_rnn_state``: thread the detached RNN/KV
        carry through (TBPTT segments under SHARED_GRADIENTS)."""
        core = self._raw_update_core()

        def step(params, states, upd_state, iteration, rng, f, l, fm, lm,
                 rnn_state_in=None):
            updates, new_states, new_upd, loss, rnn_out = core(
                params, states, upd_state, iteration, rng, f, l, fm, lm,
                rnn_state_in)
            if with_rnn_state:
                rnn_out = (_tm(jax.lax.stop_gradient, rnn_out)
                           if rnn_out else rnn_out)
                return updates, new_states, new_upd, loss, rnn_out
            return updates, new_states, new_upd, loss

        return step

    def _apply_constraints(self, params):
        """Per-layer parameter constraints after each update (reference
        ``BaseConstraint.applyConstraint`` timing)."""
        out = dict(params)
        for key, lc, _ in self._layers():
            cons = getattr(lc, "constraints", None) or \
                getattr(getattr(lc, "inner", None), "constraints", None)
            if cons:
                out[key] = apply_constraints(cons, params[key])
        return out

    def _build_step(self, with_rnn_state, single_iteration=False):
        step = self._raw_step(with_rnn_state)
        n_iter = 1 if single_iteration else _n_iterations(self.gc)
        if n_iter > 1:
            step = _scan_iterations(step, n_iter, with_rnn_state)
        looped = sum(getattr(impl, "block_applications", 0)
                     for _, _, impl in self._layers())
        if looped:
            _mon.get_registry().gauge(
                "looped_block_applications",
                "Block applications per step of the network's looped stacks "
                "(passes x blocks), set when the step is built",
                network=self._jit_prefix).set(looped)
        for _, _, impl in self._layers():
            for kind, n in getattr(impl, "block_kinds", {}).items():
                _mon.get_registry().gauge(
                    "hybrid_blocks",
                    "Blocks of each kind in the network's hybrid stacks, "
                    "set when the step is built",
                    network=self._jit_prefix, kind=kind).set(n)
        return monitored_jit(step, name=f"{self._jit_prefix}/step",
                             donate_argnums=(0, 2))

    def _step(self, with_rnn_state, scan, single_iteration):
        """The one cache of jitted train steps. ``scan``: the WHOLE TBPTT
        loop as one program (``_build_tbptt_scan``)."""
        n_iter = 1 if single_iteration else _n_iterations(self.gc)
        key = (with_rnn_state, scan, n_iter)
        if key not in self._steps:
            self._steps[key] = (
                _build_tbptt_scan(self._raw_step(True), n_iter) if scan
                else self._build_step(with_rnn_state, single_iteration))
        return self._steps[key]

    def _ensure_step(self, single_iteration=False):
        return self._step(False, False, single_iteration)

    def _ensure_tbptt_step(self, single_iteration=False):
        return self._step(True, False, single_iteration)

    def _ensure_tbptt_scan_step(self, single_iteration=False):
        return self._step(True, True, single_iteration)

    @property
    def _jit_step(self):
        """The plain step as ``fit`` dispatches it, None until it is built."""
        return self._steps.get((False, False, _n_iterations(self.gc)))

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _init_rnn_state(self, batch):
        return {impl.index: impl.init_stream_state(batch)
                for _, _, impl in self._layers()
                if hasattr(impl, "init_stream_state")}

    # ----------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs=1):
        """Train (reference ``MultiLayerNetwork.fit(DataSetIterator)`` :1156,
        ``ComputationGraph.fit`` overloads :863/:988). Accepts a DataSet (a
        MultiDataSet for a graph), an iterator of them, or (features,
        labels) arrays.

        .. note:: Timing caution: steps are dispatched asynchronously, so
           ``fit`` can return before the device has finished. Close a timed
           window with ``jax.block_until_ready(net.params)`` or a value
           fetch — e.g. ``float(net.score_)`` — or attach
           :class:`deeplearning4j_tpu.utils.profiling.StepTimerListener`,
           which does this for you."""
        if labels is not None:
            data = DataSet(np.asarray(data), np.asarray(labels))
        if isinstance(data, (DataSet, MultiDataSet)):
            data = ListDataSetIterator([data])
        self._before_fit(data)
        # multi-worker prefetch + device-put-ahead (datasets/prefetch.py):
        # batch k+1 is transferred while step k computes, so etl_ms
        # measures a queue pop. DL4J_TPU_PREFETCH_WORKERS=0 restores the
        # fully synchronous path.
        it, own_pipeline = wrap_for_training(
            data, cache_device=self.gc.cache_mode == CacheMode.DEVICE)
        # a new fit() supersedes a previous health halt — without this, one
        # halt would silently truncate every later fit to a single batch
        self.halt_requested = False
        _mon.get_health().clear_halt()
        done = self._completions = _mon.StepCompletions(self)
        try:
            for _ in range(epochs):
                for lst in self.listeners:
                    lst.on_epoch_start(self, self.epoch_count)
                with _mon.get_tracer().span("epoch", cat="train",
                                            epoch=self.epoch_count):
                    for ds, waited in _mon.spanned(it, "fit/next_batch"):
                        self._fit_batch(ds, etl_ms=waited * 1e3)
                        if self.halt_requested:
                            break
                    done.drain()
                for lst in self.listeners:
                    lst.on_epoch_end(self, self.epoch_count)
                self.epoch_count += 1
                if self.halt_requested:
                    log.warning("fit halted at epoch %d (halt_requested; see "
                                "TrainingHealthListener)", self.epoch_count)
                    break
        except BaseException as e:
            # error seam: listeners holding process-global resources (an
            # active ProfilerListener trace window) must release them
            # before the exception unwinds out of fit
            from ..optimize.listeners import dispatch_training_error
            dispatch_training_error(self, self.listeners, e)
            # the steps dispatched before the failure still count; a fetch
            # that fails in turn must not hide ``e``
            with contextlib.suppress(Exception):
                done.drain()
            raise
        finally:
            if own_pipeline:
                it.shutdown()   # no prefetch worker outlives its fit
        return self

    def _fit_batch(self, ds, single_iteration=False, etl_ms=None):
        """One minibatch. ``single_iteration=True`` applies exactly ONE
        optimizer update even when ``iterations(n)`` scans are configured —
        the ParallelWrapper tail-batch fallback needs update-count parity
        with its sharded dispatches (masks and TBPTT routing preserved).
        ``etl_ms``: what ``fit`` waited for ``ds`` (``fit/next_batch``)."""
        with _mon.get_tracer().span("fit/prepare", cat="train"):
            f, l, fm, lm = self._batch_streams(
                ds, cached=self.gc.cache_mode == CacheMode.DEVICE)
            feats = jax.tree_util.tree_leaves(f)
            self.last_batch_size = int(feats[0].shape[0])
            tbptt = (self.conf.backprop_type == BackpropType.TruncatedBPTT
                     and all(x.ndim == 3 for x in feats)
                     and feats[0].shape[1] > self.conf.tbptt_fwd_length)
            if not tbptt:
                step = self._ensure_step(single_iteration=single_iteration)
                it = jnp.asarray(self.iteration_count, jnp.int32)
                rng = self._next_rng()
        if tbptt:
            self._fit_tbptt(f, l, fm, lm, single_iteration=single_iteration)
            return
        # dispatch only: a span is host time, the fetch is StepCompletions'
        with _mon.step_span(self.iteration_count):
            self.params, self.states, self.updater_state, loss = step(
                self.params, self.states, self.updater_state, it, rng,
                f, l, fm, lm)
        self.score_ = loss
        self.iteration_count += (1 if single_iteration
                                 else _n_iterations(self.gc))
        self._completions.dispatched(loss, self.last_batch_size, etl_ms)

    def _fit_tbptt(self, f, l, fm, lm, single_iteration=False):
        """Truncated BPTT (reference ``doTruncatedBPTT`` in
        ``MultiLayerNetwork.java:1219`` and ``ComputationGraph.java``): time
        is chunked to ``tbptt_fwd_length`` and the per-layer (h, c) carries
        are detached between chunks. Equal segments fuse into ONE scanned
        program (segment stacking [b, T, ...] → [S, b, L, ...], rank-2
        labels/static streams broadcast over S); a ragged tail falls back to
        per-segment dispatch with the carries threaded on the host.
        Like the reference's practical behavior, the backward truncation
        equals the forward chunk length; a differing ``tbptt_back_length``
        is treated as ``tbptt_fwd_length`` (warned once)."""
        conf = self.conf
        if (conf.tbptt_back_length != conf.tbptt_fwd_length
                and not getattr(self, "_warned_tbptt", False)):
            log.warning("tbptt_back_length=%d differs from tbptt_fwd_length=%d; "
                        "backprop truncation uses the forward chunk length",
                        conf.tbptt_back_length, conf.tbptt_fwd_length)
            self._warned_tbptt = True
        first = jax.tree_util.tree_leaves(f)[0]
        b, T = int(first.shape[0]), int(first.shape[1])
        L = conf.tbptt_fwd_length
        n_applied = 1 if single_iteration else _n_iterations(self.gc)
        if T % L == 0:
            S = T // L

            def stack(x):
                return jnp.swapaxes(x.reshape(b, S, L, *x.shape[2:]), 0, 1)

            def stack_lbl(x):
                return (stack(x) if x.ndim == 3
                        else jnp.broadcast_to(x, (S,) + x.shape))

            scan_step = self._ensure_tbptt_scan_step(single_iteration)
            with _mon.get_tracer().span("fit/prepare", cat="train"):
                it0 = jnp.asarray(self.iteration_count, jnp.int32)
                rng = self._next_rng()
                streams = (_map_streams(stack, f), _map_streams(stack_lbl, l),
                           _map_streams(stack, fm), _map_streams(stack, lm))
                rnn0 = self._init_rnn_state(b)
            with _mon.step_span(self.iteration_count):
                (self.params, self.states, self.updater_state,
                 loss) = scan_step(
                    self.params, self.states, self.updater_state, it0, rng,
                    *streams, rnn0)
            # one iteration per TBPTT segment × iterations(n) applied per
            # segment (reference increments iterationCount per applied update,
            # so Adam bias correction and lr schedules see each one)
            self.iteration_count += S * n_applied
        else:
            step = self._ensure_tbptt_step(single_iteration=single_iteration)
            rnn_state = self._init_rnn_state(b)
            for start in range(0, T, L):
                sl = slice(start, min(start + L, T))
                with _mon.get_tracer().span("fit/prepare", cat="train"):
                    it = jnp.asarray(self.iteration_count, jnp.int32)
                    rng = self._next_rng()
                    streams = (
                        _map_streams(lambda x: x[:, sl], f),
                        _map_streams(lambda x: x[:, sl] if x.ndim == 3 else x,
                                     l),
                        _map_streams(lambda x: x[:, sl], fm),
                        _map_streams(lambda x: x[:, sl], lm))
                with _mon.step_span(self.iteration_count):
                    (self.params, self.states, self.updater_state, loss,
                     rnn_state) = step(
                        self.params, self.states, self.updater_state, it, rng,
                        *streams, rnn_state)
                self.iteration_count += n_applied
        self.score_ = loss
        self._completions.dispatched(loss, b)

    # ----------------------------------------------------------------- score
    def score(self, ds=None, training=False):
        """Loss (+reg) on a dataset (reference ``score(DataSet)``), or last
        training score when called without arguments."""
        if ds is None:
            return float(self.score_)
        f, l, fm, lm = self._batch_streams(ds)
        key = (bool(training), fm is not None, lm is not None)
        if key not in self._jit_score:
            # jitted: early stopping / evaluative listeners call this every
            # epoch over the full validation set — eager tracing per batch
            # would make evaluation the epoch bottleneck on TPU
            def score_fn(params, states, f, l, fm, lm):
                loss, _ = self._loss_fn(params, states,
                                        self._adapt_inputs(f), l, fm, lm,
                                        training, None)
                return loss
            self._jit_score[key] = monitored_jit(
                score_fn, name=f"{self._jit_prefix}/score")
        return float(self._jit_score[key](self.params, self.states, f, l,
                                          fm, lm))

    def compute_gradient_and_score(self, ds):
        """Reference ``computeGradientAndScore`` (``MultiLayerNetwork.java``
        :2206, ``ComputationGraph.java`` :1298) — returns (grads, score)
        without updating params (used by gradient checks and external
        optimizers)."""
        f, l, fm, lm = self._batch_streams(ds)
        f = self._adapt_inputs(f)

        def loss_fn(p):
            loss, _ = self._loss_fn(p, self.states, f, l, fm, lm, True, None)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(self.params)
        self.score_ = loss
        return grads, float(loss)

    # ------------------------------------------------------------ parameters
    def num_params(self) -> int:
        return sum(int(v.size) for v in jax.tree_util.tree_leaves(self.params))

    numParams = num_params

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    setListeners = set_listeners

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

"""Profiling utilities — the build's tracing subsystem (SURVEY.md §5).

The reference has no in-framework tracer; deep profiling is delegated to
ND4J's external ``OpProfiler`` and throughput to ``PerformanceListener``.
Here the device is XLA, so the natural equivalents are:

- :func:`trace` / :class:`ProfilerListener` — capture a ``jax.profiler``
  device trace (viewable in TensorBoard/Perfetto) around a code block or a
  chosen window of training iterations.
- :func:`step_cost` — XLA's static cost model for a container's compiled
  train step (flops / bytes accessed / peak memory), the numbers behind the
  roofline analysis in PERF.md.
- :class:`StepTimerListener` — per-iteration wall times closed by the
  fit loops' device→host value fetch.
- :class:`ParamServerMetricsListener` (re-exported from
  ``paramserver/metrics.py``) — push/pull counters, wire bytes, retries and
  op-latency histograms for server-mediated async training, on the same
  listener bus.

This module covers *device* traces and per-step timing; the process-wide
metrics/span/health layer lives in ``deeplearning4j_tpu/monitor/`` (one
``MetricsRegistry`` scraped at ``GET /metrics``, a host-side span tracer
exporting Chrome trace JSON, and a NaN/divergence/stall watchdog) — see
docs/OBSERVABILITY.md. The monitor's spans are HOST time and hold no fetch
(``monitor/tracer.py``): under a trace taken here, the program's spans
(``fit/next_batch``, ``fit/prepare``, ``step`` with its ``step_num``, …)
sit on the profiler's clock beside the device's ops, whose time is the
step's. The completion barrier :class:`StepTimerListener` and
:class:`ProfilerListener` rely on is the eager ``float(loss)`` the fit
loops keep for as long as a listener is attached.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..optimize.listeners import TrainingListener


def __getattr__(name):
    # lazy re-export: pulling the PS listener eagerly would make a plain
    # profiling import pay for the whole paramserver+parallel stack
    if name == "ParamServerMetricsListener":
        from ..paramserver.metrics import ParamServerMetricsListener
        return ParamServerMetricsListener
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a jax.profiler device trace for the enclosed block."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class ProfilerListener(TrainingListener):
    """Trace a window of training iterations: starts a jax.profiler trace at
    ``start_iteration`` and stops it ``num_iterations`` later. Attach like
    any listener (reference listener-bus pattern,
    ``optimize/api/IterationListener.java``)."""

    def __init__(self, log_dir: str, start_iteration: int = 3,
                 num_iterations: int = 3):
        self.log_dir = log_dir
        self.start_iteration = start_iteration
        self.num_iterations = num_iterations
        self._active = False
        self.done = False

    def iteration_done(self, model, iteration, score):
        import jax

        if self.done:
            return
        if not self._active and iteration >= self.start_iteration:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
            self._until = iteration + self.num_iterations
        elif self._active and iteration >= self._until:
            # completion barrier: with a listener attached (this one) the
            # fit loops fetch float(loss) — a device→host VALUE fetch of
            # this step's output — before dispatching listeners, so the
            # traced step has already finished when we get here.
            self.close()

    def close(self):
        """Stop the trace if still active — called automatically when the
        window fills or the epoch ends, and safe to call explicitly when
        training stops early (an active jax profiler trace is process-global;
        leaking it breaks the next start_trace)."""
        import jax

        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self.done = True

    def on_epoch_end(self, model, epoch):
        self.close()

    def on_training_error(self, model, exception):
        # fit raised mid-window: an active jax.profiler trace is
        # process-global and leaking it breaks the NEXT start_trace —
        # the fit loops' error seam guarantees this close runs
        self.close()


class StepTimerListener(TrainingListener):
    """Per-iteration wall-clock times behind a completion barrier.

    Dispatch is asynchronous: a timed window has to close on
    ``jax.block_until_ready`` or on a device→host value fetch
    (``np.asarray`` / ``float()`` of a result), or it measures the enqueue.
    With a listener attached the fit loops evaluate ``float(loss)`` right
    after each dispatch and before ``iteration_done`` (without one the fetch
    lags, ``monitor.StepCompletions``), so the score this listener receives
    IS post-barrier — timing here is honest by construction, and attaching
    it is what makes the loop synchronous. User code timing its own
    steps outside a listener must close its window the same way (on the
    v5e both barriers close a window at the same time — PERF.md "Bring-up
    on the chip")."""

    def __init__(self):
        self.times_ms: List[float] = []
        self._t0: Optional[float] = None

    def iteration_done(self, model, iteration, score):
        # score arrives as a host float — the caller's float(loss) was the
        # completion barrier for this step (see class docstring)
        now = time.perf_counter()
        if self._t0 is not None:
            self.times_ms.append((now - self._t0) * 1e3)
        self._t0 = now

    def summary(self) -> Dict[str, float]:
        if not self.times_ms:
            return {}
        arr = np.asarray(self.times_ms)
        return {"mean_ms": float(arr.mean()), "p50_ms": float(np.median(arr)),
                "p95_ms": float(np.percentile(arr, 95)),
                "n": float(arr.size)}


#: attribute name for per-net step_cost state: ONE jitwatch wrapper per
#: net (the wrapper's cached_lowering memoizes the trace by abstract
#: signature) plus the finished cost dicts per shape key. Stored ON the
#: net object — its lifetime IS the net's (a module-level
#: WeakKeyDictionary would never evict here: the wrapper's step closure
#: captures the net, so the value would strongly reference its own key).
#: Repeated step_cost(net, ds) with the same shapes therefore pays ZERO
#: re-trace and ZERO re-compile — the pre-fix code built a fresh wrapper
#: every call, so even an already-compiled step paid a full second trace
#: per cost query.
_STEP_COST_ATTR = "_step_cost_state"


def step_cost(net, ds) -> Dict[str, Any]:
    """XLA cost analysis of the container's compiled train step on this
    DataSet's shapes: {'flops', 'bytes_accessed', ...} plus derived
    per-example numbers. Works for MultiLayerNetwork and ComputationGraph.
    Memoized per (net, shapes) — see ``_STEP_COST_ATTR``; with the
    persistent compile cache enabled (``DL4J_TPU_COMPILE_CACHE_DIR``,
    ``compilecache/``) even the first call's ``.compile()`` rides the
    disk cache."""
    import jax
    import jax.numpy as jnp

    from ..datasets.dataset import DataSet

    if isinstance(ds, DataSet):
        f = jnp.asarray(ds.features)
        l = jnp.asarray(ds.labels)
        feats, labels = f, l
        is_graph = hasattr(net, "conf") and hasattr(net.conf, "vertices")
        if is_graph:
            feats, labels = (f,), (l,)
        batch = int(f.shape[0])
    else:  # MultiDataSet
        feats = tuple(jnp.asarray(x) for x in ds.features)
        labels = tuple(jnp.asarray(x) for x in ds.labels)
        batch = int(ds.features[0].shape[0])

    state = getattr(net, _STEP_COST_ATTR, None)
    if state is None:
        from ..monitor.jitwatch import monitored_jit
        # both containers take with_rnn_state
        state = {"wrapper": monitored_jit(net._raw_step(False),
                                          name="profiling/step_cost"),
                 "costs": {}}
        setattr(net, _STEP_COST_ATTR, state)

    def leaf_key(tree):
        return tuple((tuple(x.shape), str(x.dtype))
                     for x in jax.tree_util.tree_leaves(tree))

    key = (leaf_key(feats), leaf_key(labels))
    cached = state["costs"].get(key)
    if cached is None:
        lowered = state["wrapper"].cached_lowering(
            net.params, net.states, net.updater_state,
            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
            feats, labels, None, None)
        cached = state["costs"][key] = dict(
            lowered.compile().cost_analysis() or {})
    ca = cached
    flops = float(ca.get("flops", 0.0))
    by = float(ca.get("bytes accessed", 0.0))
    return {"flops": flops, "bytes_accessed": by, "batch": batch,
            "gflop_per_example": flops / batch / 1e9,
            "mb_per_example": by / batch / 1e6,
            "raw": dict(ca)}

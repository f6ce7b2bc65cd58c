"""Unified monitor subsystem (docs/OBSERVABILITY.md).

One place to scrape, correlate, and alarm on everything the framework
does — replacing the three ad-hoc holders observability was fragmented
across (``ParamServerMetrics``, ``PerformanceListener``/
``StepTimerListener``, ``ui/stats``):

- :func:`get_registry` — the process-global :class:`MetricsRegistry`
  (labeled counters / gauges / histograms, Prometheus text rendering;
  served at ``GET /metrics`` on ``ui/server.py``).
- :func:`get_tracer` — the host-side span :class:`Tracer` (ring buffer,
  Chrome trace-event JSON at ``GET /trace``, nests
  ``jax.profiler.TraceAnnotation``).
- :func:`get_health` — the :class:`HealthState` behind ``GET /healthz``,
  plus :class:`TrainingHealthListener`, the NaN/divergence/stall watchdog
  with ``warn``/``raise``/``halt`` actions.
- :func:`get_flight_recorder` — the bounded structured event log (worker
  join/leave/rejoin, retry exhaustion, peer failures, health transitions)
  that dumps JSONL to disk on halt or crash.
- :func:`get_fleet` — per-worker telemetry shipped over the paramserver's
  ``OP_TELEMETRY``: the merged ``GET /fleet`` scrape, the merged
  multi-``pid`` Chrome trace, and worker staleness for ``/healthz``.
- :func:`get_collector` — the pull-based scrape plane: a
  :class:`TelemetryCollector` polling each replica's ``GET /telemetry``
  (registry + trace tail + seq-cursored flight events + health in one
  round trip) into the same :class:`FleetState` table, with a private
  history ring so the alert rules evaluate FLEET-scope SLOs
  (``default_fleet_scope_rules``).
- :func:`get_prober` — the probe plane: a :class:`Prober` firing real
  ``POST /v1/models/<m>/predict`` requests at each :class:`ProbeTarget`
  from the outside and comparing answers against the target's golden
  set (``ServedModel.golden()``) — the black-box correctness signal
  self-reported telemetry cannot provide (``default_probe_rules``).
- :func:`get_incident_recorder` — the incident plane: an
  :class:`IncidentRecorder` that captures the full diagnostic state at
  every alert *fire* edge (history window, pinned exemplar spans, flight
  events, jit table, lock census, probe/collector snapshots) into one
  merged :class:`Incident` per overlapping firing window and persists
  resolved incidents as content-addressed ``.dl4jinc`` bundles
  (``GET /incidents``, ``incident show``).
- :func:`get_history` — the bounded ring of timestamped registry
  snapshots behind ``GET /history`` and the ``trends`` block of
  ``/profile`` (opt-in background sampler; windowed rate/delta/quantile
  readers).
- :func:`get_alert_engine` — declarative threshold / burn-rate SLO rules
  evaluated over the history: OK→PENDING→FIRING with hold-down,
  ``alert_firing``/``alert_resolved`` flight events,
  ``alerts_firing{rule=}`` gauge, ``GET /alerts``.

The fit loops, transport channel, parameter-server client/server, and
async dataset iterator are pre-instrumented against these globals. A span
is host time and holds no fetch (``monitor/tracer.py``): the fit loops'
spans go to the profiler whatever :func:`set_enabled` says, and with the
monitor on and no listener the per-iteration score reaches the registry and
``/healthz`` through :class:`StepCompletions`, at most
``StepCompletions.LAG`` steps after its dispatch and without the host ever
waiting for the newest step. :func:`set_enabled` (False) turns the ring
buffer and the fit loops' metric and health writes off.
"""
from __future__ import annotations

import collections
import contextlib
import time

from .lockwatch import (InstrumentedLock, LockWatch, get_lockwatch,
                        make_lock, make_rlock, make_condition)
from .registry import (MetricsRegistry, LatencyHistogram, Counter, Gauge,
                       Histogram, get_registry, render_prometheus_dump)
from .tracer import (SpanContext, Tracer, enabled, get_tracer, new_context,
                     set_enabled)
from .health import (HealthState, get_health, TrainingHealthListener,
                     TrainingHealthError)
from .flightrec import FlightRecorder, get_flight_recorder
from .fleet import FleetState, get_fleet, merge_traces
from .history import MetricsHistory, get_history
from .alerts import (AlertEngine, AlertError, AlertRule, BurnRateRule,
                     FleetStalenessRule, HealthRule, ThresholdRule,
                     default_fleet_rules, default_fleet_scope_rules,
                     default_probe_rules, default_rules,
                     default_serving_rules, default_training_rules,
                     get_alert_engine)
from .collector import (ScrapeTarget, TelemetryCollector, get_collector,
                        telemetry_snapshot)
from .probes import ProbeTarget, Prober, get_prober
from .incidents import (Incident, IncidentRecorder, abort_open_incidents,
                        get_incident_recorder, load_bundle,
                        render_incident_text)
from .jitwatch import (MonitoredJit, JitRegistry, monitored_jit,
                       get_jit_registry, sample_device_memory,
                       maybe_sample_device_memory, profile_report,
                       render_profile_text)

__all__ = [
    "MetricsRegistry", "LatencyHistogram", "Counter", "Gauge", "Histogram",
    "get_registry", "render_prometheus_dump", "SpanContext", "Tracer",
    "get_tracer", "new_context", "HealthState", "get_health",
    "TrainingHealthListener", "TrainingHealthError",
    "FlightRecorder", "get_flight_recorder", "FleetState", "get_fleet",
    "merge_traces", "MonitoredJit", "JitRegistry", "monitored_jit",
    "get_jit_registry", "sample_device_memory",
    "maybe_sample_device_memory", "profile_report",
    "render_profile_text", "InstrumentedLock", "LockWatch",
    "get_lockwatch", "make_lock", "make_rlock", "make_condition",
    "MetricsHistory", "get_history", "AlertEngine", "AlertError",
    "AlertRule", "ThresholdRule", "BurnRateRule", "HealthRule",
    "FleetStalenessRule", "get_alert_engine", "default_rules",
    "default_serving_rules", "default_training_rules",
    "default_fleet_rules", "default_fleet_scope_rules",
    "default_probe_rules",
    "ScrapeTarget", "TelemetryCollector", "get_collector",
    "telemetry_snapshot", "ProbeTarget", "Prober", "get_prober",
    "Incident", "IncidentRecorder", "get_incident_recorder",
    "abort_open_incidents", "load_bundle", "render_incident_text",
    "set_enabled", "enabled", "record_training_iteration", "step_span",
    "spanned", "StepCompletions",
]

@contextlib.contextmanager
def step_span(iteration: int):
    """The per-minibatch training span, around the DISPATCH of the jitted
    step and nothing else: host time (``monitor/tracer.py``). Its
    ``step_num`` makes it a ``StepTraceAnnotation``, so a profiler trace
    groups the device ops it launched under it; the step's device time is
    theirs. Span close also samples the device-memory gauges (throttled,
    AFTER the span ends so the sampling cost never inflates the span) —
    the step boundary is where donation/sharding decisions have just
    landed, so ``device_memory_in_use_bytes`` tracks the working set
    step-by-step (docs/OBSERVABILITY.md "Compilation & memory")."""
    try:
        with get_tracer().span("step", cat="train",
                               step_num=int(iteration)) as ctx:
            yield ctx
    finally:
        if enabled():
            maybe_sample_device_memory()


def spanned(items, name: str, cat: str = "train"):
    """``(item, seconds its pull took)`` for every item of ``items``, each
    pull (the last, exhausted one too) under a span ``name``: the fit
    loops' wait for the iterator, timed by the span itself (one clock)."""
    items = iter(items)
    tracer = get_tracer()
    while True:
        span = tracer.span(name, cat=cat)
        with span:
            try:
                item = next(items)
            except StopIteration:
                return
        yield item, span.seconds


def record_training_iteration(model, iteration: int, score: float,
                              batch_size: int = 0, step_ms: float = None,
                              etl_ms: float = None):
    """One call per applied minibatch from the container fit loops: bumps
    the training counters/gauges and the health liveness state."""
    reg = get_registry()
    reg.counter("training_iterations_total",
                "optimizer iterations applied").inc()
    reg.gauge("training_score", "last minibatch score").set(score)
    reg.gauge("training_iteration", "last iteration index").set(iteration)
    if batch_size:
        reg.counter("training_examples_total",
                    "examples consumed by fit").inc(batch_size)
    if step_ms is not None:
        reg.histogram("training_step_ms",
                      "interval between the completions of successive "
                      "steps as the fit loop saw them (the first of a "
                      "fit: since it began); input wait included"
                      ).observe(step_ms)
    if etl_ms is not None:
        reg.histogram("training_etl_ms",
                      "host wait for the next minibatch").observe(etl_ms)
    get_health().record_iteration(iteration, score)


class StepCompletions:
    """What the fit loops know about steps they have dispatched and whose
    loss they have not fetched yet. One per ``fit``; serves both containers
    and the TBPTT path.

    With listeners attached every step is resolved at once: a callback must
    see the model as that step left it (``ParallelWrapper._resolve_score``
    has the reasons). Without, a step is resolved once its loss
    ``is_ready()``, or when more than :attr:`LAG` steps are pending, oldest
    first, so the host never waits for the step it has just dispatched and
    the device always has work queued behind the one waited for.
    ``record_training_iteration``, ``/healthz`` liveness and NaN detection
    therefore see step *n* at most ``LAG`` steps late; :meth:`drain` (epoch
    end, halt, error path) brings them up to date. ``training_step_ms`` is
    the interval between successive resolutions. The fetch happens under
    the ``fit/resolve`` span, which is a wait and named as one."""

    #: steps the host may run ahead of the newest loss it has fetched; the
    #: benchmark's own ``run_ahead`` brake uses the same lag
    LAG = 2

    def __init__(self, model):
        self._model = model
        self._pending = collections.deque()
        self._last_done = time.perf_counter()

    def dispatched(self, loss, batch_size: int, etl_ms: float = None):
        """Call after a step's dispatch, with ``iteration_count`` already
        moved past it."""
        model = self._model
        if not (model.listeners or enabled()):
            return
        self._pending.append((loss, model.iteration_count - 1, batch_size,
                              etl_ms))
        self._resolve(all_of_them=bool(model.listeners))

    def drain(self):
        self._resolve(all_of_them=True)

    def _resolve(self, all_of_them):
        pending = self._pending

        def due():
            return pending and (all_of_them or len(pending) > self.LAG
                                or pending[0][0].is_ready())

        if not due():
            return
        with get_tracer().span("fit/resolve", cat="train"):
            while due():
                loss, iteration, batch_size, etl_ms = pending.popleft()
                score = float(loss)     # device→host fetch: the wait
                now = time.perf_counter()
                record_training_iteration(
                    self._model, iteration, score, batch_size=batch_size,
                    step_ms=(now - self._last_done) * 1e3, etl_ms=etl_ms)
                self._last_done = now
                for lst in self._model.listeners:
                    lst.iteration_done(self._model, iteration, score)

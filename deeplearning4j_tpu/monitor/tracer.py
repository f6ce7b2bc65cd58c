"""Host-side span tracer: ring buffer → Chrome trace-event JSON.

The reference delegates all tracing to external tools; ``utils/profiling``
wraps ``jax.profiler`` for *device* traces. This tracer is the cheap
*host*-side complement: a context manager that records wall-clock
spans into a bounded ring buffer and exports them as Chrome trace-event
JSON (``GET /trace`` on the UI server, or :meth:`Tracer.export`) — open the
dump in Perfetto / ``chrome://tracing``. When jax is importable, every span
also nests a ``jax.profiler.TraceAnnotation`` so host spans line up with
device traces captured through ``utils.profiling.trace``.

One span, three sinks. The ``TraceAnnotation`` is entered on EVERY span: it
costs ~0.3 µs while no profiler session runs and lands the span on the
profiler's clock, next to the device's ops, while one does — whatever the
monitor switch says. The ring-buffer event (two ids, a dict, a lock) is
written only while ``monitor.enabled()``; that switch is the one there is.
The exception to it is the third sink: a span whose ``cat`` is ``"setup"``
or ``"compile"`` (:data:`KEPT_CATS`) is also KEPT, in a small list of its
own, whatever the switch says. Set-up (``init``, a compile and its phases,
jitwatch's cost capture, the model's placement) happens while no profiler
runs, and in a job started with ``DL4J_TPU_MONITOR=0`` while no ring is
written either: without the list, the seconds before the first step are
recorded nowhere. It is safe to leave on because these spans happen a
bounded number of times per network and never per step, batch or leaf: a
few hundred records of a few microseconds each against seconds of work.
The list keeps the FIRST ``kept_capacity`` records (a process's start-up is
what it is for) and counts what came after in ``Tracer.kept_dropped``.

The rule: a span is HOST time. Dispatch is asynchronous, so a span around a
jitted call measures its dispatch, and that is what it is for: the host work
needed to keep the device fed. Never put a device→host fetch
(``float(loss)``, ``np.asarray``, ``block_until_ready``) in a span to make
it "mean the step": that serialises host and device (−14 % on ResNet50,
PERF.md §6). Device time comes from the device trace (the ops that ran
under the ``step`` span's ``step_num``), or, live, from the lagged
completion the fit loops record (``monitor.StepCompletions``). The two
spans that ARE waits say so by name, ``fit/resolve`` and
``pw/resolve_score``, and no metric counts them as host work.

Trace-context propagation: every span carries a ``trace_id`` shared with
its whole causal chain and a fresh ``span_id``; :meth:`Tracer.current_span`
exposes the active :class:`SpanContext` so an RPC layer can ship it to the
peer (the paramserver client prefixes flagged ops with it), and
``span(parent=ctx)`` lets the receiving side record a child span under the
REMOTE parent — a merged export then shows client push → server apply as
one chain across processes (docs/OBSERVABILITY.md "Fleet observability").
"""
from __future__ import annotations

import os
import random
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

__all__ = ["SpanContext", "Tracer", "get_tracer", "new_context",
           "set_enabled", "enabled", "KEPT_CATS"]

#: the monitor's one switch (``monitor.set_enabled`` / ``monitor.enabled``;
#: it lives here because the tracer is the lowest module that reads it).
#: While False no span writes the ring buffer and the fit loops skip their
#: metric and health writes unless listeners are attached; the profiler
#: annotations are not switched. Defaults on (a bare fit populates /metrics,
#: /healthz and /trace); flip per process with DL4J_TPU_MONITOR=0 or at
#: runtime with set_enabled(False).
_ENABLED = os.environ.get("DL4J_TPU_MONITOR", "1") not in ("0", "false", "")

#: the categories of the third sink: a span of one of these is kept whatever
#: the switch says (module docstring). Set-up work only: nothing per step.
KEPT_CATS = frozenset(("setup", "compile"))


def set_enabled(value: bool):
    global _ENABLED
    _ENABLED = bool(value)


def enabled() -> bool:
    return _ENABLED


class SpanContext(NamedTuple):
    """Identity of one span in one trace. IDs are 63-bit ints (JSON-safe,
    16 hex chars on the wire); ``parent_span_id`` is 0 for a root span."""

    trace_id: int
    span_id: int
    parent_span_id: int = 0


def _new_id() -> int:
    # 63 bits: fits JSON/JS number precision limits and struct "<Q"
    return random.getrandbits(63) | 1       # never 0 (0 = "no parent")


def new_context() -> SpanContext:
    """A fresh root :class:`SpanContext` — for subsystems that mint a
    trace identity per unit of work without opening a thread-bound span
    (the serving batcher stamps one per request at submit time so the
    queue-wait and flush spans recorded later can join it)."""
    return SpanContext(_new_id(), _new_id(), 0)


def _trace_annotation():
    """jax.profiler.TraceAnnotation class, or None when jax is absent.
    Resolved lazily so a metrics-only import never pays for jax."""
    global _ANNOTATION
    if _ANNOTATION is _UNRESOLVED:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        # deliberately broad + silent: ANY import failure (absent jax,
        # broken profiler build) means "no device annotations", and trace
        # emission must never raise into the training loop
        except Exception:  # tpulint: disable=EXC001
            _ANNOTATION = None
    return _ANNOTATION


_UNRESOLVED = object()
_ANNOTATION = _UNRESOLVED


class _Span:
    """The context manager :meth:`Tracer.span` returns."""

    __slots__ = ("_tracer", "_name", "_cat", "_parent", "_args", "_ann",
                 "_ctx", "_stack", "_start", "seconds")

    def __init__(self, tracer, name, cat, parent, step_num, args):
        self._tracer, self._name, self._cat = tracer, name, cat
        self._parent, self._args = parent, args
        self.seconds = None
        ann_cls = _trace_annotation()
        if ann_cls is None:
            self._ann = None
        elif step_num is None:
            self._ann = ann_cls(name)
        else:       # what jax.profiler.StepTraceAnnotation adds to the name
            self._ann = ann_cls(name, _r=1, step_num=step_num)
            args["step_num"] = step_num

    def __enter__(self) -> SpanContext:
        if self._ann is not None:
            self._ann.__enter__()
        stack = self._stack = self._tracer._stack()
        up = self._parent if self._parent is not None else (
            stack[-1] if stack else None)
        self._ctx = SpanContext(up.trace_id if up else _new_id(), _new_id(),
                                up.span_id if up else 0)
        stack.append(self._ctx)
        self._start = time.perf_counter()
        return self._ctx

    def note(self, **args):
        """Add ``args`` that are known only inside the block (what ``init``
        made: leaves, parameters, bytes) to the open span's."""
        self._args.update(args)

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._start
        tracer = self._tracer
        self._stack.pop()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if _ENABLED or self._cat in KEPT_CATS:
            tracer._record(self._name, self._cat, self._start, self.seconds,
                           self._ctx, self._args)
        return False


class Tracer:
    """Bounded ring buffer of completed host spans.

    ``capacity`` bounds memory: the newest ``capacity`` spans win (a
    steady-state training loop keeps the recent window, which is what a
    ``GET /trace`` snapshot wants). Spans on different threads interleave
    naturally — the export carries ``tid`` so Perfetto lays them out per
    thread, and nesting within a thread is reconstructed from ts/dur
    containment.

    ``kept_capacity`` bounds the list of kept spans (``cat`` in
    :data:`KEPT_CATS`; module docstring): the OLDEST win there, because the
    list is a process's start-up; a record that finds it full is dropped
    and counted in ``kept_dropped``.
    """

    def __init__(self, capacity: int = 8192, kept_capacity: int = 1024):
        from .lockwatch import make_lock
        self._lock = make_lock("Tracer._lock")
        self._events = deque(maxlen=int(capacity))
        self._kept: List[Dict] = []
        self._kept_capacity = int(kept_capacity)
        self._t0 = time.perf_counter()
        self._local = threading.local()     # per-thread span-context stack
        self.dropped = 0                    # ring-buffer overflow count
        self.kept_dropped = 0               # kept records refused: list full

    # ----------------------------------------------------- span contexts
    def _stack(self) -> List[SpanContext]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current_span(self) -> Optional[SpanContext]:
        """The innermost open span's context on THIS thread, or None. This
        is what an RPC client ships to the server so the server's handling
        span becomes a child of the in-flight client span."""
        stack = self._stack()
        return stack[-1] if stack else None

    def span(self, name: str, cat: str = "host",
             parent: Optional[SpanContext] = None,
             step_num: Optional[int] = None, **args) -> "_Span":
        """One span of HOST time around the enclosed block: ``with
        tracer.span(...) as ctx`` yields the span's :class:`SpanContext`,
        and the returned object holds the span's ``seconds`` once it has
        closed (the fit loops time their input wait with it: one clock).
        Always enters the profiler annotation (recorded only while a
        profiler session runs); writes the ring-buffer event only while
        ``monitor.enabled()``; keeps a ``cat`` of :data:`KEPT_CATS`
        (:meth:`kept`) always. ``args`` become the ring event's ``args``
        (must be JSON-serializable scalars). ``step_num`` makes the
        annotation a ``StepTraceAnnotation``, so the profiler's own tools
        group device ops by step; the ring event carries it among its
        ``args``. The trace/parent IDs come from the innermost open span on
        this thread, or from ``parent`` — pass a context that arrived over
        the wire to join a REMOTE trace."""
        return _Span(self, name, cat, parent, step_num, args)

    def record_complete(self, name: str, start: float, dur: float,
                        cat: str = "host",
                        parent: Optional[SpanContext] = None, **args):
        """Record an ALREADY-timed span after the fact — for events only
        detectable at their end (e.g. a jit compile, recognized by the
        cache-size delta once the call returns). ``start`` is the
        ``perf_counter`` value at the event's start, ``dur`` seconds. The
        span is parented under ``parent`` when given (the serving batcher
        parents a request's queue-wait span under the REQUEST's context,
        not the scheduler thread's), else under the innermost OPEN span on
        this thread (a compile detected mid-step nests under the step
        span); either way it does not touch the context stack itself.
        Like every write to the ring, only while ``monitor.enabled()``;
        kept, like every span, where ``cat`` is one of :data:`KEPT_CATS`."""
        if not (_ENABLED or cat in KEPT_CATS):
            return
        up = parent if parent is not None else self.current_span()
        ctx = SpanContext(up.trace_id if up else _new_id(), _new_id(),
                          up.span_id if up else 0)
        self._record(name, cat, start, dur, ctx, args)

    def _event(self, name, cat, start, dur, tid, ctx, args) -> Dict:
        """The Chrome trace event of one span (``ts`` relative to this
        tracer's ``_t0``, microseconds)."""
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": (start - self._t0) * 1e6, "dur": dur * 1e6,
              "pid": os.getpid(), "tid": tid,
              "args": {"trace_id": f"{ctx.trace_id:x}",
                       "span_id": f"{ctx.span_id:x}", **args}}
        if ctx.parent_span_id:
            ev["args"]["parent_span_id"] = f"{ctx.parent_span_id:x}"
        return ev

    def _record(self, name, cat, start, dur, ctx, args):
        """One closed span into the sinks it belongs to: the kept list
        where its ``cat`` says so, the ring while the monitor is on."""
        tid = threading.get_ident()
        if cat in KEPT_CATS:
            with self._lock:
                full = len(self._kept) >= self._kept_capacity
                if full:
                    self.kept_dropped += 1
                else:
                    self._kept.append({
                        "name": name, "cat": cat, "start": start,
                        "end": start + dur, "tid": tid,
                        "trace_id": ctx.trace_id, "span_id": ctx.span_id,
                        "parent_span_id": ctx.parent_span_id,
                        "args": dict(args)})
        if not _ENABLED:
            return
        ev = self._event(name, cat, start, dur, tid, ctx, args)
        with self._lock:
            overflow = len(self._events) == self._events.maxlen
            if overflow:
                self.dropped += 1
            self._events.append(ev)
        if overflow:
            # registry write OUTSIDE the ring lock (scrapes take both)
            from .registry import get_registry
            get_registry().counter(
                "tracer_spans_dropped_total",
                "spans evicted from the trace ring buffer").inc()

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def kept(self) -> List[Dict]:
        """The kept spans (``cat`` in :data:`KEPT_CATS`), oldest first, each
        ``{"name", "cat", "start", "end", "tid", "trace_id", "span_id",
        "parent_span_id", "args"}``: ``start`` and ``end`` are
        ``time.perf_counter()`` seconds (the clock a caller times its own
        work on; the ring's ``ts`` is relative to a private origin), the
        ids are ints and ``parent_span_id`` is 0 for a root."""
        with self._lock:
            return list(self._kept)

    def export(self) -> Dict:
        """Chrome trace-event JSON object (the ``/trace`` payload): load it
        in Perfetto or ``chrome://tracing`` as-is. The kept spans come
        first, each once (one that is still in the ring is the ring's), so
        a process shows its start-up however long ago the ring wrapped."""
        with self._lock:
            events, kept = list(self._events), list(self._kept)
        in_ring = {ev["args"]["span_id"] for ev in events}
        start_up = [
            self._event(r["name"], r["cat"], r["start"], r["end"] - r["start"],
                        r["tid"], SpanContext(r["trace_id"], r["span_id"],
                                              r["parent_span_id"]), r["args"])
            for r in kept if f"{r['span_id']:x}" not in in_ring]
        return {"traceEvents": start_up + events, "displayTimeUnit": "ms"}

    def clear(self):
        with self._lock:
            self._events.clear()
            self._kept.clear()

    def __len__(self):
        with self._lock:
            return len(self._events)


#: the process-global tracer the fit loops / transport / PS client write to
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER

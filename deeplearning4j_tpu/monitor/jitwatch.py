"""jitwatch: compilation & device-memory observability for every jit.

The two dominant invisible costs on an XLA device are **recompilation**
(shape/dtype churn silently re-tracing a step — the classic "training
mysteriously 10x slower" failure) and **device memory** (donation and
sharding decisions live or die by peak HBM). Neither shows up in step
timings: a retrace storm just makes every step slow, and an OOM arrives
long after the allocation decisions that caused it. This module makes
both first-class monitor citizens:

- :func:`monitored_jit` — the package-wide replacement for bare
  ``jax.jit`` (tpulint rule JAX003 enforces the migration stays
  complete). Per named function it records compile count vs call count
  (cache-miss ratio), compile wall-time (``jit_compile_seconds``
  histogram + ``jit_compiles_total{fn=}`` / ``jit_calls_total{fn=}``
  series), a ``compile/<name>`` tracer span (compiles appear on
  ``/trace`` and the merged fleet trace, parented under the step span
  they interrupted), and on-compile ``cost_analysis`` capture (flops /
  bytes / peak memory per compiled variant — the same numbers
  ``utils.profiling.step_cost`` reports).
- the **retrace-storm detector**: ``RETRACE_THRESHOLD`` compiles of the
  same wrapper within ``RETRACE_WINDOW`` seconds records a health
  problem and a ``retrace_storm`` flight-recorder event naming the
  function and the argument-signature delta that triggered the retrace
  (the runbook: read the delta, pad/bucket your batch shapes —
  docs/OBSERVABILITY.md "Compilation & memory"). ``TrainingHealthListener``
  drains :meth:`JitRegistry.drain_storms` per iteration to apply its
  warn/raise/halt action.
- :func:`sample_device_memory` — ``device_memory_in_use_bytes{device=}``
  / ``device_memory_peak_bytes{device=}`` / ``device_live_buffers``
  gauges, sampled on every ``/metrics`` scrape and at step-span close,
  degrading gracefully on backends without memory stats (CPU's
  ``memory_stats()`` is None; the live-buffer count still works).
- :func:`profile_report` — the step-anatomy view behind ``GET /profile``
  and ``monitor --profile``: the per-fn jit table, the memory gauges,
  and the step/ETL timing split merged into one JSON+text report.
- :func:`watch_compile_phases` — one ``jax.monitoring`` listener pair that
  records every compile of the process, whoever asked for it, by phase:
  ``jax/trace``, ``jax/lower``, ``jax/backend_compile`` and
  ``jax/cache_retrieval`` spans (``cat="compile"``, so the tracer keeps
  them with the monitor off) and the ``jax_compile_phase_seconds_total``
  / ``jax_compiles_total`` counters. They fire only when something
  compiles; ``profile_report()["startup"]`` reads them beside the ``init``
  spans: why a job took as long as it did to reach its first step.

Hot-path cost per monitored call: two counter increments, two
``perf_counter`` reads, and one C++-side jit-cache-size probe — all the
expensive work (signatures, spans) happens only on a compile, which is
already a multi-ms event, and the cost_analysis re-lower runs on a
background worker thread so it never extends the training call that
triggered the compile.
"""
from __future__ import annotations

import functools
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ..compilecache.cache import (claim_persistent_hit as _cc_claim_hit,
                                  enabled as _cc_enabled,
                                  hits_count as _cc_hits_count)

log = logging.getLogger(__name__)

__all__ = ["monitored_jit", "MonitoredJit", "JitRegistry",
           "get_jit_registry", "sample_device_memory",
           "maybe_sample_device_memory", "wait_cost_captures",
           "profile_report", "render_profile_text", "watch_compile_phases",
           "RETRACE_THRESHOLD", "RETRACE_WINDOW"]

#: compiles of ONE wrapper instance within RETRACE_WINDOW seconds that
#: count as a retrace storm. Per instance, not per name: fifty networks
#: each compiling their own "mln/step" once is healthy; one network
#: compiling its step three times in a minute is shape churn.
RETRACE_THRESHOLD = int(os.environ.get("DL4J_TPU_RETRACE_THRESHOLD", "3"))
RETRACE_WINDOW = float(os.environ.get("DL4J_TPU_RETRACE_WINDOW", "60"))

#: "0" skips the on-compile cost_analysis capture (it re-lowers the
#: function abstractly — cheap next to the compile it annotates, but not
#: free on very large graphs)
_COST_CAPTURE = os.environ.get("DL4J_TPU_JITWATCH_COST", "1") \
    not in ("0", "false", "")


# ------------------------------------------------------------- signatures
def _leaf_sig(x) -> str:
    """One leaf's cache identity: ``f32[16,4]`` for array-likes (shape
    metadata survives buffer donation — only the data is freed), repr for
    static/python leaves."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            return f"{dtype.name}[{','.join(str(int(d)) for d in shape)}]"
        # exotic dtype/shape objects (symbolic dims, custom dtypes):
        # the repr fallback below IS the answer, nothing to log
        except Exception:  # tpulint: disable=EXC001
            pass
    r = repr(x)
    return r if len(r) <= 40 else r[:37] + "..."


def _signature(args, kwargs) -> Tuple[Tuple[Tuple[str, str], ...], str]:
    """((keypath, leaf-sig), ...) plus the treedef repr — the abstract
    identity jax's jit cache keys on, path-labeled so a retrace delta can
    name the argument that changed."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path((args,
                                                            dict(kwargs)))
    sig = tuple((jax.tree_util.keystr(kp), _leaf_sig(leaf))
                for kp, leaf in leaves)
    return sig, str(treedef)


def _sig_delta(old, new) -> str:
    """Human-readable diff between two signatures: WHICH arguments changed
    shape/dtype (the retrace-storm runbook's first question)."""
    if old is None:
        return "first compile"
    o, n = dict(old[0]), dict(new[0])
    diffs = [f"{k}: {o[k]} -> {n[k]}" for k in n if k in o and o[k] != n[k]]
    added = [k for k in n if k not in o]
    removed = [k for k in o if k not in n]
    if added:
        diffs.append(f"+{len(added)} new leaves ({added[0]}, ...)"
                     if len(added) > 1 else f"new leaf {added[0]}")
    if removed:
        diffs.append(f"-{len(removed)} leaves")
    if not diffs:
        return ("tree structure changed" if old[1] != new[1]
                else "signature unchanged (static-argument retrace)")
    head = "; ".join(diffs[:4])
    if len(diffs) > 4:
        head += f" (+{len(diffs) - 4} more)"
    return head


def _abstractify(x):
    """Array-likes → ShapeDtypeStruct for a data-free re-lower (donated
    inputs are already dead by the time a compile is detected); python
    scalars and other statics pass through concretely."""
    import jax
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    return x


# --------------------------------------------------------- compile phases
#: jax.monitoring's time-span events of one compile, by the phase's name here
_PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile"}
#: the persistent cache's read, fired as a duration only, on a hit
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: installed once: the offset from ``time.time()`` (jax's clock for the
#: spans) to ``time.perf_counter()`` (the tracer's), and the counters
_PHASES: Dict[str, Any] = {}
_PHASES_LOCK = threading.Lock()


def watch_compile_phases():
    """Install the listener pair (idempotent; every network's ``init`` and
    every :class:`MonitoredJit` calls it, so it is there before a process's
    first compile of its own). Each phase of each compile becomes a span
    recorded after the fact under whatever span is open on the compiling
    thread. Only the outermost trace is recorded: every ``jnp`` function is
    a jitted one, so tracing a step fires the event once per op it calls
    (thousands of records whose time is the outer trace's). A program that
    compiles while another is traced (a constant computed eagerly) still
    nests, and the backend's span holds the cache's read where there is
    one, so a reader that wants seconds takes the union of the intervals
    per thread."""
    if _PHASES:
        return
    import jax
    from jax import monitoring
    from .registry import get_registry
    reg = get_registry()
    with _PHASES_LOCK:
        if _PHASES:
            return
        _PHASES.update(
            seconds={phase: reg.counter(
                "jax_compile_phase_seconds_total",
                "seconds in each phase of every jax compile of the process",
                phase=phase)
                for phase in (*_PHASE_EVENTS.values(), "cache_retrieval")},
            compiles=reg.counter(
                "jax_compiles_total",
                "programs handed to the backend's compiler or read from "
                "the persistent cache, the eager one-op programs among them"),
            offset=time.perf_counter() - time.time(),
            # the tracing context is per thread; asked when a trace has
            # ended, it says whether another encloses it
            top_level=jax.core.trace_ctx.is_top_level)
    monitoring.register_event_time_span_listener(_on_phase_span)
    monitoring.register_event_duration_secs_listener(_on_cache_retrieval)


def _note_phase(phase, start, dur, **args):
    try:
        _PHASES["seconds"][phase].inc(max(dur, 0.0))
        if phase == "backend_compile":
            _PHASES["compiles"].inc()
        from .tracer import get_tracer
        get_tracer().record_complete(f"jax/{phase}", start, dur,
                                     cat="compile", **args)
    except Exception as e:
        # a listener runs inside jax's compile path: never raise into it
        log.debug("jitwatch: compile phase %s not recorded: %r", phase, e)


def _on_phase_span(event, start_time, end_time, **kw):
    phase = _PHASE_EVENTS.get(event)
    if phase == "trace" and not _PHASES["top_level"]():
        return
    if phase is not None:
        _note_phase(phase, start_time + _PHASES["offset"],
                    end_time - start_time,
                    fun_name=str(kw.get("fun_name", "")))


def _on_cache_retrieval(event, duration, **kw):
    if event == _RETRIEVAL_EVENT:     # fired right after the read
        _note_phase("cache_retrieval", time.perf_counter() - duration,
                    duration)


# ------------------------------------------------------------- registry
class _FnStats:
    """Per-NAME aggregate (instances of the same named fn pool here)."""

    __slots__ = ("name", "compiles", "compile_seconds", "variants",
                 "last_cost", "last_delta", "storms", "persistent_hits")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.compile_seconds = 0.0
        self.variants: Dict[str, Dict[str, Any]] = {}
        self.last_cost: Optional[Dict[str, float]] = None
        self.last_delta: Optional[str] = None
        self.storms = 0
        #: compiles of this fn that the persistent on-disk cache served
        #: (compilecache/ — still in-process jit-cache misses, but disk
        #: reads rather than XLA work; the hit/miss split keeps the
        #: bimodal jit_compile_seconds distribution honest)
        self.persistent_hits = 0


class JitRegistry:
    """Process-global table of monitored jit functions: per-fn compile /
    call / cost aggregates (:meth:`table` is the ``/profile`` jit block)
    and the pending retrace-storm queue ``TrainingHealthListener`` drains
    to apply its action."""

    def __init__(self):
        from .lockwatch import make_lock
        self._lock = make_lock("JitRegistry._lock")
        self._stats: Dict[str, _FnStats] = {}
        self._pending_storms: List[Dict[str, Any]] = []

    def stats(self, name: str) -> _FnStats:
        with self._lock:
            st = self._stats.get(name)
            if st is None:
                st = self._stats[name] = _FnStats(name)
            return st

    def note_compile(self, name: str, seconds: float, sig_key: str,
                     delta: str, persistent_hit: bool = False):
        st = self.stats(name)
        with self._lock:
            st.compiles += 1
            st.compile_seconds += seconds
            st.last_delta = delta
            if persistent_hit:
                st.persistent_hits += 1
            var = st.variants.setdefault(sig_key, {"compiles": 0})
            var["compiles"] += 1
            var["compile_seconds"] = round(
                var.get("compile_seconds", 0.0) + seconds, 4)

    def note_cost(self, name: str, sig_key: str,
                  cost: Dict[str, float]):
        """Landing point for the async cost worker (may arrive any time
        after the compile it describes)."""
        st = self.stats(name)
        with self._lock:
            var = st.variants.setdefault(sig_key, {"compiles": 0})
            var["cost"] = cost
            st.last_cost = cost

    def report_storm(self, name: str, count: int, delta: str):
        msg = (f"retrace storm: jit fn {name!r} compiled {count} times "
               f"within {RETRACE_WINDOW:.0f}s — argument-signature churn "
               f"({delta}); pad or bucket the offending shapes "
               f"(docs/OBSERVABILITY.md, 'Compilation & memory')")
        # thread affinity: detection runs synchronously inside the
        # training call, on the fit thread — the listener driving THAT
        # fit runs iteration_done on the same thread, so "thread" lets
        # it act only on its own model's storms (health.py)
        info = {"t": time.time(), "fn": name, "count": count,
                "window_s": RETRACE_WINDOW, "signature_delta": delta,
                "message": msg, "thread": threading.get_ident()}
        with self._lock:
            self._stats.setdefault(name, _FnStats(name)).storms += 1
            self._pending_storms.append(info)
            del self._pending_storms[:-32]    # bounded, newest win
        log.warning("jitwatch %s", msg)
        # flight recorder first (the delta is the forensic payload), then
        # the health problem (visible on /healthz without any listener)
        from .flightrec import get_flight_recorder
        get_flight_recorder().record("retrace_storm", fn=name, count=count,
                                     window_s=RETRACE_WINDOW,
                                     signature_delta=delta)
        from .health import get_health
        get_health().record_problem("retrace", msg)

    def drain_storms(self) -> List[Dict[str, Any]]:
        """Pop the pending storms (listener action seam)."""
        with self._lock:
            out, self._pending_storms = self._pending_storms, []
        return out

    def requeue_storms(self, storms: List[Dict[str, Any]]):
        """Put drained storms back (a listener drained storms belonging
        to ANOTHER fit thread — its own listener must still see them).
        Original timestamps are kept, so arm-time filtering and the
        bounded queue still expire them."""
        if not storms:
            return
        with self._lock:
            self._pending_storms.extend(storms)
            del self._pending_storms[:-32]

    def table(self) -> Dict[str, Dict[str, Any]]:
        """{name: {calls, compiles, cache_miss_ratio, compile_seconds,
        variants, flops, bytes_accessed, peak_memory_bytes, ...}} — the
        jit block of the step-anatomy report."""
        from .registry import get_registry
        # read through the snapshot, never through handle lookups: a
        # /profile scrape must not materialize empty children for fns
        # that never ran (the lazy-handles principle, _metric_handles)
        snap = get_registry().snapshot()

        def fn_row(metric, name):
            for r in snap.get(metric, []):
                if r["labels"].get("fn") == name:
                    return r
            return None

        with self._lock:
            stats = list(self._stats.items())
        out: Dict[str, Dict[str, Any]] = {}
        for name, st in sorted(stats):
            calls_row = fn_row("jit_calls_total", name)
            calls = int(calls_row["value"]) if calls_row else 0
            row: Dict[str, Any] = {
                "calls": calls,
                "compiles": st.compiles,
                "cache_miss_ratio": (round(st.compiles / calls, 4)
                                     if calls else None),
                "compile_seconds": round(st.compile_seconds, 4),
                "variants": len(st.variants),
                "storms": st.storms,
                # the hit/miss split (compilecache/): of `compiles`, how
                # many were disk-cache hits vs true XLA compiles — a
                # fleet warm from a shared cache dir shows compiles ==
                # persistent_cache_hits and near-zero compile_seconds
                "persistent_cache_hits": st.persistent_hits,
                "true_compiles": st.compiles - st.persistent_hits,
            }
            cs_row = fn_row("jit_compile_seconds", name)
            cs = cs_row.get("summary") if cs_row else None
            if cs:
                # honest per-fn compile-latency quantiles: the histogram
                # rides the unit="s" bucket geometry (sub-100ms compiles
                # no longer collapse into one bucket)
                row["compile_s"] = {k: round(v, 4)
                                    for k, v in cs.items()}
            if st.last_cost:
                row.update(st.last_cost)
            if st.last_delta:
                row["last_signature_delta"] = st.last_delta
            out[name] = row
        return out

    def clear(self):
        with self._lock:
            self._stats.clear()
            self._pending_storms.clear()


_JIT_REGISTRY = JitRegistry()


def get_jit_registry() -> JitRegistry:
    return _JIT_REGISTRY


# -------------------------------------------------------------- wrapper
class MonitoredJit:
    """``jax.jit`` plus the bookkeeping above. Calls pass straight
    through; compile detection is a jit-cache-size delta (falling back to
    a shadow signature set on jax builds without ``_cache_size``), so the
    compiled path pays no tracing, hashing, or locking beyond two counter
    bumps."""

    def __init__(self, fn, name: Optional[str] = None, **jit_kwargs):
        import jax
        watch_compile_phases()
        self._fn = fn
        self.name = name or getattr(fn, "__qualname__",
                                    getattr(fn, "__name__", "jit_fn"))
        from .lockwatch import make_lock
        self._jit = jax.jit(fn, **jit_kwargs)
        self._lock = make_lock("MonitoredJit._lock")
        self.calls = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        self._last_sig = None
        self._seen_sigs = set()           # fallback-mode shadow cache
        self._seen_cache_size = 0         # compiles claimed so far
        self._compile_times = deque(maxlen=max(RETRACE_THRESHOLD, 8))
        self._handles = None
        self._phit_handle = None      # jit_persistent_cache_hits_total —
                                      # created on the FIRST disk hit only
                                      # (lazy-handles principle: processes
                                      # without the cache never materialize
                                      # the series)
        self._lowerings: Dict[Any, Any] = {}   # cached_lowering memo
        self._has_cache_size = hasattr(self._jit, "_cache_size")
        functools.update_wrapper(self, fn, updated=())

    def _metric_handles(self):
        # lazy: importing a module full of decorated steps must not
        # populate /metrics with never-called fn labels
        if self._handles is None:
            from .registry import get_registry
            reg = get_registry()
            self._handles = (
                reg.counter("jit_calls_total",
                            "calls into monitored jit functions",
                            fn=self.name),
                reg.counter("jit_compiles_total",
                            "XLA compilations (jit cache misses)",
                            fn=self.name),
                reg.histogram("jit_compile_seconds",
                              "wall-clock seconds per jit compilation "
                              "(trace+compile, first-call latency)",
                              unit="s", fn=self.name),
            )
        return self._handles

    def __call__(self, *args, **kwargs):
        calls_c, compiles_c, hist = self._metric_handles()
        calls_c.inc()
        with self._lock:
            self.calls += 1
        # persistent-cache attribution window (compilecache/): snapshot
        # the disk-hit counter before the call so a detected compile can
        # be classified hit-vs-miss precisely. Both reads are lock-free
        # (flag + GIL-atomic int) — cache on or off, the hot path takes
        # no lock for this
        phits0 = _cc_hits_count() if _cc_enabled() else None
        t0 = time.perf_counter()
        out = self._jit(*args, **kwargs)
        dur = time.perf_counter() - t0
        if self._has_cache_size:
            # claim-the-delta: N threads racing through one compile all
            # observe the same grown cache, but only the first to take
            # the lock claims it — no double-counted compiles, no
            # spurious retrace storm from a thread pile-up, and only
            # the claimer's wall-time lands in the histogram
            compiled = False
            after = self._jit._cache_size()
            if after > self._seen_cache_size:
                with self._lock:
                    if after > self._seen_cache_size:
                        self._seen_cache_size = after
                        compiled = True
            sig = None
        else:
            sig = self._safe_signature(args, kwargs)
            key = sig[0] if sig else None
            with self._lock:
                compiled = key not in self._seen_sigs
                self._seen_sigs.add(key)
        if compiled:
            try:
                phit = (phits0 is not None
                        and _cc_claim_hit(phits0))
                self._record_compile(args, kwargs, t0, dur, sig,
                                     compiles_c, hist, phit)
            except Exception as e:
                # observability must never fail the training step it
                # observes — degrade to the bare counters
                log.debug("jitwatch: compile bookkeeping for %s failed: %r",
                          self.name, e)
        return out

    def _safe_signature(self, args, kwargs):
        try:
            return _signature(args, kwargs)
        except Exception as e:
            log.debug("jitwatch: signature of %s failed: %r", self.name, e)
            return None

    def _record_compile(self, args, kwargs, t0, dur, sig, compiles_c, hist,
                        phit: bool = False):
        if sig is None:
            sig = self._safe_signature(args, kwargs)
        compiles_c.inc()
        hist.observe(dur)          # seconds (the metric name carries units)
        if phit:
            # this "compile" was a persistent-cache disk read, not XLA
            # work (claimed in __call__ against the pre-call hit window)
            if self._phit_handle is None:
                from .registry import get_registry
                self._phit_handle = get_registry().counter(
                    "jit_persistent_cache_hits_total",
                    "jit compiles served from the persistent on-disk "
                    "compile cache (disk reads, not XLA compiles)",
                    fn=self.name)
            self._phit_handle.inc()
        delta = _sig_delta(self._last_sig, sig) if sig else "unknown"
        now = time.time()
        with self._lock:
            self.compiles += 1
            self.compile_seconds += dur
            self._last_sig = sig
            self._compile_times.append(now)
            recent = [t for t in self._compile_times
                      if now - t <= RETRACE_WINDOW]
            storm = len(recent) >= RETRACE_THRESHOLD
            if storm:
                self._compile_times.clear()   # re-arm: a sustained storm
                                              # re-fires every N compiles
        # the compile happened inside whatever span is open on this thread
        # (usually the step span), so parent it there — step anatomy shows
        # the compile eating the step it interrupted
        from .tracer import get_tracer
        get_tracer().record_complete(f"compile/{self.name}", t0, dur,
                                     cat="compile", fn=self.name,
                                     signature_delta=delta,
                                     persistent_hit=bool(phit))
        sig_key = ";".join(f"{k}={v}" for k, v in sig[0]) if sig else "?"
        reg = get_jit_registry()
        reg.note_compile(self.name, dur, sig_key, delta,
                         persistent_hit=phit)
        if _COST_CAPTURE:
            _submit_cost_capture(self._jit, self.name, sig_key,
                                 args, kwargs)
        if storm:
            reg.report_storm(self.name, len(recent), delta)

    # ------------------------------------------------- jit API passthrough
    def lower(self, *args, **kwargs):
        """AOT lowering passthrough (``utils.profiling.step_cost`` seam)."""
        return self._jit.lower(*args, **kwargs)

    def cached_lowering(self, *args, **kwargs):
        """:meth:`lower`, memoized by abstract argument signature.

        ``jax.jit.lower`` re-TRACES on every call even when the same
        signature's executable is already compiled — fine for a one-off
        export, wasteful for repeated cost analysis over the same shapes
        (``utils.profiling.step_cost`` used to pay a full second trace
        per call). Bounded memo (the signature set of any analysis
        caller is tiny); falls through to a live lower when the
        signature cannot be computed."""
        sig = self._safe_signature(args, kwargs)
        key = sig[0] if sig else None
        if key is not None:
            with self._lock:
                got = self._lowerings.get(key)
            if got is not None:
                return got
        lowered = self._jit.lower(*args, **kwargs)
        if key is not None:
            with self._lock:
                self._lowerings[key] = lowered
                while len(self._lowerings) > 16:   # bounded, oldest out
                    self._lowerings.pop(next(iter(self._lowerings)))
        return lowered

    @property
    def cache_miss_ratio(self) -> Optional[float]:
        with self._lock:
            return self.compiles / self.calls if self.calls else None

    def __repr__(self):
        return (f"MonitoredJit({self.name!r}, calls={self.calls}, "
                f"compiles={self.compiles})")


def monitored_jit(fn=None, name: Optional[str] = None, **jit_kwargs):
    """``jax.jit`` with compile observability (see module docstring).

    Use exactly like ``jax.jit`` — ``monitored_jit(step, name="mln/step",
    donate_argnums=(0, 2))`` — or as a decorator factory::

        @monitored_jit(name="nlp/hs_step", donate_argnums=(0, 1))
        def _hs_step(...): ...

    ``name`` labels every metric/span/flight event; it defaults to the
    function's qualname but SHOULD be set to a stable ``area/fn`` slug so
    dashboards survive refactors.
    """
    if fn is None:
        return functools.partial(monitored_jit, name=name, **jit_kwargs)
    return MonitoredJit(fn, name=name, **jit_kwargs)


# ---------------------------------------------------- async cost capture
# Single-thread ThreadPoolExecutor, NOT a bare daemon thread: a daemon
# thread mid-XLA-compile when the interpreter finalizes aborts the whole
# process ("terminate called without an active exception" — seen in the
# multiprocess worker tests). Executor threads are JOINED at interpreter
# shutdown; _cancel_pending_captures (registered BEFORE the executor
# module's own shutdown hook) cancels not-yet-started captures first, so
# exit waits only for the one in-flight compile, never the whole queue.
_COST_WORKER_LOCK = threading.Lock()
_COST_EXECUTOR = None
_COST_FUTURES: deque = deque()
_COST_MAX_PENDING = 16
_COST_SHUTDOWN = False


def _cancel_pending_captures():
    global _COST_SHUTDOWN
    _COST_SHUTDOWN = True
    with _COST_WORKER_LOCK:
        futures = list(_COST_FUTURES)
        _COST_FUTURES.clear()
    for f in futures:
        f.cancel()


def _ensure_cost_executor():
    global _COST_EXECUTOR
    with _COST_WORKER_LOCK:
        if _COST_EXECUTOR is None:
            # import (and thereby let concurrent.futures install its
            # join-at-shutdown hook) FIRST, then register our canceller:
            # threading._shutdown runs _threading_atexits in REVERSED
            # registration order, so the later-registered canceller runs
            # before the executor's join — pending captures are cancelled
            # and exit waits only for the one in-flight compile
            from concurrent.futures import ThreadPoolExecutor
            # never shutdown() explicitly BY DESIGN: concurrent.futures
            # joins this worker at interpreter exit, and the canceller
            # registered below trims the queue first — see the comment
            # block above (a daemon thread here SIGABRTs mid-compile)
            _COST_EXECUTOR = ThreadPoolExecutor(  # tpulint: disable=RES001
                max_workers=1, thread_name_prefix="jitwatch-cost")
            try:
                threading._register_atexit(_cancel_pending_captures)
            # private API absent (older python): the atexit fallback below
            # IS the handling — exit then waits for queued captures too
            except Exception:  # tpulint: disable=EXC001
                import atexit
                atexit.register(_cancel_pending_captures)
        return _COST_EXECUTOR


def _submit_cost_capture(jitted, name: str, sig_key: str, args, kwargs):
    """Queue an XLA cost_analysis capture for the variant just compiled.
    The abstract signature (ShapeDtypeStructs — no data, donation-safe) is
    built eagerly on the calling thread; the expensive lower+compile runs
    on the worker, so cost capture never extends the training call that
    triggered the compile. Bounded: a retrace storm must not queue
    unbounded recompilation work — overflow drops the capture (the compile
    counters/spans already landed)."""
    if _COST_SHUTDOWN:
        return
    try:
        import jax
        a_args, a_kwargs = jax.tree_util.tree_map(_abstractify,
                                                  (args, dict(kwargs)))
    except Exception as e:
        log.debug("jitwatch: abstractify for %s failed: %r", name, e)
        return
    ex = _ensure_cost_executor()
    with _COST_WORKER_LOCK:
        while _COST_FUTURES and _COST_FUTURES[0].done():
            _COST_FUTURES.popleft()
        if len(_COST_FUTURES) >= _COST_MAX_PENDING:
            log.debug("jitwatch: cost queue full, dropping capture for %s",
                      name)
            return
    try:
        fut = ex.submit(_capture_cost_task, jitted, name, sig_key,
                        a_args, a_kwargs)
    except RuntimeError:      # executor already shut down (interpreter exit)
        return
    with _COST_WORKER_LOCK:
        _COST_FUTURES.append(fut)


def _capture_cost_task(jitted, name, sig_key, a_args, a_kwargs):
    try:
        # the abstract re-lower below re-compiles the variant that just
        # compiled — with the persistent cache on, that is a guaranteed
        # disk hit, and it must not enter the hit-attribution pool (a
        # FOREGROUND compile racing this worker would claim it and read
        # as a disk hit it never had) — compilecache.suppress_events
        from ..compilecache.cache import suppress_events
        from .tracer import get_tracer
        # the span is this worker thread's: the second lowering and compile
        # stay out of any sum over the thread that called the step
        with suppress_events(), get_tracer().span(
                "jitwatch/cost_capture", cat="setup", fn=name):
            _capture_cost_now(jitted, name, sig_key, a_args, a_kwargs)
    except Exception as e:
        log.debug("jitwatch: cost capture for %s failed: %r", name, e)


def _capture_cost_now(jitted, name: str, sig_key: str, a_args, a_kwargs):
    """Worker body: abstract re-lower + compile + cost_analysis /
    memory_analysis. Best-effort by contract — sharded/exotic signatures
    that refuse the abstract re-lower simply report no cost."""
    compiled = jitted.lower(*a_args, **a_kwargs).compile()
    ca = compiled.cost_analysis() or {}
    cost = {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    ma = compiled.memory_analysis()
    peak = sum(float(getattr(ma, k, 0) or 0)
               for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes"))
    if peak:
        cost["peak_memory_bytes"] = peak
    get_jit_registry().note_cost(name, sig_key, cost)


def wait_cost_captures(timeout: float = 10.0) -> bool:
    """Block until every queued cost capture has landed (tests and
    snapshot-then-exit CLI paths want deterministic flops). Returns False
    on timeout — the report is then merely missing its newest cost rows."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with _COST_WORKER_LOCK:
            pending = [f for f in _COST_FUTURES if not f.done()]
        if not pending:
            return True
        time.sleep(0.01)
    return False


# --------------------------------------------------------- device memory
def sample_device_memory(registry=None) -> Dict[str, Any]:
    """Sample per-device allocator stats + the process live-buffer count
    into gauges; returns the same data as a dict (the ``/profile`` memory
    block). Backends without ``memory_stats()`` (CPU) just skip the byte
    gauges — the sampler never raises."""
    out: Dict[str, Any] = {"devices": {}, "live_buffers": None}
    try:
        import jax
        from .registry import get_registry
        reg = registry if registry is not None else get_registry()
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            # documented graceful degradation: backends without
            # allocator stats (CPU) skip the byte gauges entirely
            except Exception:  # tpulint: disable=EXC001
                stats = None
            if not stats:
                continue
            dev = f"{d.platform}:{d.id}"
            row = out["devices"].setdefault(dev, {})
            in_use = stats.get("bytes_in_use")
            if in_use is not None:
                reg.gauge("device_memory_in_use_bytes",
                          "device bytes currently allocated",
                          device=dev).set(float(in_use))
                row["bytes_in_use"] = int(in_use)
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                reg.gauge("device_memory_peak_bytes",
                          "peak device bytes over the process lifetime",
                          device=dev).set(float(peak))
                row["peak_bytes_in_use"] = int(peak)
            limit = stats.get("bytes_limit")
            if limit:
                row["bytes_limit"] = int(limit)
        n = len(jax.live_arrays())
        reg.gauge("device_live_buffers",
                  "live jax arrays held by this process").set(float(n))
        out["live_buffers"] = n
    except Exception as e:
        log.debug("jitwatch: device memory sample failed: %r", e)
    return out


#: per-step sampling throttle (seconds): the fit loops sample at step-span
#: close, but jax.live_arrays() is O(live buffers) — once a second is
#: plenty for a gauge and keeps the hot loop honest
_SAMPLE_INTERVAL = float(os.environ.get("DL4J_TPU_MEMSAMPLE_INTERVAL", "1.0"))
_LAST_SAMPLE = [0.0]


def maybe_sample_device_memory():
    """Throttled :func:`sample_device_memory` for per-step call sites: at
    most one sample per ``DL4J_TPU_MEMSAMPLE_INTERVAL`` seconds (default
    1.0; scrape-time sampling on ``/metrics`` stays unthrottled)."""
    now = time.monotonic()
    if now - _LAST_SAMPLE[0] < _SAMPLE_INTERVAL:
        return
    _LAST_SAMPLE[0] = now
    sample_device_memory()


# ----------------------------------------------------------- step anatomy
def _snap_value(snap, metric) -> Optional[float]:
    """Sum of a snapshot family's scalar children (None when absent)."""
    rows = snap.get(metric, [])
    return sum(r.get("value", 0) for r in rows) if rows else None


def _snap_summary(snap, metric) -> Optional[Dict[str, float]]:
    """First child's histogram summary from a snapshot (None when absent)."""
    rows = snap.get(metric, [])
    return rows[0].get("summary") if rows else None


def profile_report() -> Dict[str, Any]:
    """The step-anatomy report (``GET /profile`` / ``monitor --profile``):
    per-fn jit table + device memory + the step/ETL timing split, merged
    from the monitor registry — one view answering "where does a step's
    wall-clock actually go: compute, compile, or ETL?" — and, from the
    tracer's kept spans, ``startup``: where the time before the first
    step went."""
    from .registry import get_registry
    snap = get_registry().snapshot()

    def value(metric):
        return _snap_value(snap, metric)

    def summary(metric):
        return _snap_summary(snap, metric)

    return {
        "jit": get_jit_registry().table(),
        "memory": sample_device_memory(),
        "steps": {
            "iterations": value("training_iterations_total"),
            "examples": value("training_examples_total"),
            "step_ms": summary("training_step_ms"),
            "etl_ms": summary("training_etl_ms"),
        },
        "pipeline": _pipeline_block(snap),
        "training": _training_block(snap),
        "serving": _serving_block(snap),
        "mesh": _mesh_block(),
        "locks": _locks_block(),
        "control": _control_block(),
        "trends": _trends_block(),
        "startup": _startup_block(),
    }


def _covered(intervals) -> float:
    """Seconds the ``(start, end)`` intervals cover, overlaps once."""
    total, edge = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > edge:
            total += e - max(s, edge)
            edge = e
    return total


def _startup_block() -> Dict[str, Any]:
    """Why this process took as long as it did to reach its first step,
    from the tracer's kept spans (``monitor/tracer.py``, the third sink):
    every ``init`` with what it drew and sent, the phases of every compile
    (per thread, a trace nested in a trace once; the backend's span holds
    the cache's read), jitwatch's own cost captures, the model's placements
    under ``ParallelWrapper``, and the seconds from the first ``init``'s
    start to the end of the first compile of a step. Empty until a network
    was initialised or something compiled."""
    from .tracer import get_tracer
    tracer = get_tracer()
    kept = tracer.kept()
    if not kept:
        return {}
    children: Dict[int, List[Dict]] = {}
    for r in kept:
        children.setdefault(r["parent_span_id"], []).append(r)

    def seconds(r):
        return round(r["end"] - r["start"], 6)

    inits = []
    for r in kept:
        if r["name"] != "init":
            continue
        row = {k: r["args"].get(k)
               for k in ("network", "leaves", "parameters", "bytes")}
        row["seconds"] = seconds(r)
        for child in children.get(r["span_id"], ()):
            if child["name"] == "init/params":
                row["params_s"] = seconds(child)
                row["draw_s"] = round(child["args"].get("draw_s", 0.0), 6)
                row["place_s"] = round(child["args"].get("place_s", 0.0), 6)
            elif child["name"] == "init/updater_state":
                row["updater_state_s"] = seconds(child)
        inits.append(row)
    phases = {}
    for phase in (*_PHASE_EVENTS.values(), "cache_retrieval"):
        by_thread: Dict[int, List] = {}
        for r in kept:
            if r["name"] == f"jax/{phase}":
                by_thread.setdefault(r["tid"], []).append(
                    (r["start"], r["end"]))
        phases[phase] = round(sum(map(_covered, by_thread.values())), 6)

    def named(name):
        return [r for r in kept if r["name"] == name]

    out: Dict[str, Any] = {
        "inits": inits,
        "compile_phase_s": phases,
        "compiles": len(named("jax/backend_compile")),
        "cost_capture_s": round(sum(
            r["end"] - r["start"] for r in named("jitwatch/cost_capture")), 6),
        "place_model_s": round(sum(
            r["end"] - r["start"] for r in named("pw/place_model")), 6),
        "kept": len(kept), "kept_dropped": tracer.kept_dropped,
    }
    if inits:
        first_init = min(r["start"] for r in named("init"))
        compiled = [r["end"] for r in kept
                    if r["name"].startswith("compile/")
                    and _dispatches_step(r["args"].get("fn", ""))
                    and r["start"] >= first_init]
        if compiled:
            out["init_to_first_step_compiled_s"] = round(
                min(compiled) - first_init, 6)
    return out


def _dispatches_step(fn: str) -> bool:
    """Whether the monitored function ``fn`` is a train step (``cg/step``,
    ``mln/step``, ``nn/tbptt_scan``, ``sharding/dp_step``, …)."""
    return "step" in fn or fn.endswith("_scan")


def _mesh_block() -> Dict[str, Any]:
    """Active parallel topologies (parallel/mesh.py registry): per style
    the mesh axis names/extents, device count, steps built, and
    sharded-vs-replicated model-state leaf counts — what topology is this
    process's training/inference actually running on. Read through
    sys.modules so a process that never imported the parallel substrate
    pays nothing (and reports an honest empty block)."""
    import sys as _sys
    mod = _sys.modules.get("deeplearning4j_tpu.parallel.mesh")
    if mod is None:
        return {}
    try:
        return mod.mesh_block()
    except Exception as e:      # pragma: no cover - defensive scrape path
        log.debug("jitwatch: mesh block failed: %r", e)
        return {}


def _control_block() -> Dict[str, Any]:
    """Control-plane summary (control/plane.py): policy count, active
    cooldowns, total actions, last action. Read through sys.modules like
    the mesh block — a process that never imported the control plane
    pays nothing and reports an honest empty block."""
    import sys as _sys
    mod = _sys.modules.get("deeplearning4j_tpu.control.plane")
    if mod is None:
        return {}
    try:
        return mod.control_block()
    except Exception as e:      # pragma: no cover - defensive scrape path
        log.debug("jitwatch: control block failed: %r", e)
        return {}


#: the trends block's comparison horizons (seconds): "now vs 1m vs 5m"
_TREND_WINDOWS = (60.0, 300.0)


def _trends_block() -> Dict[str, Any]:
    """Now-vs-1m-vs-5m movement of the load-bearing series, read from the
    metric history ring (monitor/history.py). Empty until the history
    sampler has at least two samples — the block answers "is it getting
    WORSE", which a single snapshot cannot. Gauges compare the current
    value against the value at each horizon; counters report the delta
    over each horizon; latency reports the WINDOWED p99 (bucket-count
    deltas — only the samples inside the window); memory peak reports the
    windowed max."""
    from .history import get_history
    hist = get_history()
    if len(hist) < 2:
        return {}

    def tol(w):
        # honesty guard: a value only counts as "w seconds ago" when a
        # sample landed within a quarter-window (or a couple of sampler
        # intervals) of that horizon — a 15s-old ring must answer the
        # 5m question with None, never with a 15s-old value mislabeled
        return max(w * 0.25, 2 * hist.interval_s)

    def covers(w):
        # windowed math only when the window is actually covered (the
        # shared MetricsHistory.covers guard — the alert engine applies
        # the same one to its burn-rate windows)
        return hist.covers(w, tolerance_s=tol(w))

    def ago(metric, w):
        at = hist.at_age(w, tolerance_s=tol(w))
        return hist.value_of(at[1], metric) if at else None

    def gauge_row(metric):
        row = {"now": hist.current(metric)}
        for w in _TREND_WINDOWS:
            row[f"{w:g}s_ago"] = ago(metric, w)
        return row

    def delta_row(metric):
        row = {"total": hist.current(metric)}
        for w in _TREND_WINDOWS:
            row[f"{w:g}s_delta"] = (hist.delta(metric, w)
                                    if covers(w) else None)
        return row

    p99 = {}
    for w in _TREND_WINDOWS:
        p99[f"{w:g}s_p99_ms"] = (hist.quantile_over(
            "serving_request_latency_ms", 0.99, w) if covers(w) else None)
    peak = {"now": hist.current("device_memory_peak_bytes")}
    for w in _TREND_WINDOWS:
        peak[f"{w:g}s_max"] = (hist.max_over("device_memory_peak_bytes", w)
                               if covers(w) else None)
    return {
        "window_s": list(_TREND_WINDOWS),
        "serving_qps": gauge_row("serving_qps"),
        "serving_p99_ms": p99,
        "serving_queue_depth": gauge_row("serving_queue_depth"),
        "jit_compiles": delta_row("jit_compiles_total"),
        "device_memory_peak_bytes": peak,
    }


def _locks_block() -> Dict[str, Any]:
    """Lock-contention table (monitor/lockwatch.py): per instrumented lock
    the acquisition count and exact wait/held mean/max, plus the observed
    inversion count. Empty unless lockwatch is enabled
    (``DL4J_TPU_LOCKWATCH=1``) and instrumented locks actually ran."""
    from .lockwatch import contention_table
    return contention_table()


def _serving_block(snap) -> Dict[str, Any]:
    """Per-model serving anatomy (serving/ tier, docs/SERVING.md): request
    outcomes, latency summary (p50/p95/p99/max — the serving histograms
    are ms-valued, so bucket quantiles are honest here), trailing-window
    QPS, batch-size distribution (mean real examples per flush — how well
    continuous batching is coalescing), and current queue depth. Built
    purely from the registry snapshot, so the block also renders for a
    remote dump. Empty dict until serving traffic flows."""
    per: Dict[str, Dict[str, Any]] = {}

    def row(model):
        return per.setdefault(model, {})

    for r in snap.get("serving_requests_total", []):
        m = r["labels"].get("model", "?")
        row(m).setdefault("requests", {})[
            r["labels"].get("outcome", "?")] = r.get("value")
    for r in snap.get("serving_request_latency_ms", []):
        m = r["labels"].get("model", "?")
        if r.get("summary"):
            row(m)["latency_ms"] = r["summary"]
    for r in snap.get("serving_batch_examples", []):
        m = r["labels"].get("model", "?")
        s = r.get("summary")
        if s:
            # the histogram stores EXAMPLE COUNTS in its value slots, so
            # mean/max/n are exact; its bucket quantiles are not
            # meaningful for counts and are dropped
            row(m)["batch_examples"] = {"mean": round(s["mean_ms"], 2),
                                        "max": s["max_ms"],
                                        "n": int(s["n"])}
    for fam, key in (("serving_queue_depth", "queue_depth"),
                     ("serving_qps", "qps")):
        for r in snap.get(fam, []):
            row(r["labels"].get("model", "?"))[key] = r.get("value")
    for fam, key in (("serving_pad_ms", "pad_ms"),
                     ("serving_transfer_ms", "transfer_ms")):
        # the ISSUE-11 flush-time split: batch assembly vs host<->device
        # movement, per flush — read next to latency_ms to see how much
        # of the tail is data plane rather than compute
        for r in snap.get(fam, []):
            if r.get("summary"):
                row(r["labels"].get("model", "?"))[key] = {
                    "mean": round(r["summary"]["mean_ms"], 4),
                    "p99": r["summary"]["p99_ms"],
                    "n": int(r["summary"]["n"])}
    hits: Dict[str, float] = {}
    misses: Dict[str, float] = {}
    for fam, acc in (("serving_cache_hits_total", hits),
                     ("serving_cache_misses_total", misses)):
        for r in snap.get(fam, []):
            acc[r["labels"].get("model", "?")] = r.get("value") or 0.0
    for m in set(hits) | set(misses):
        h, miss = hits.get(m, 0.0), misses.get(m, 0.0)
        row(m)["cache"] = {
            "hits": int(h), "misses": int(miss),
            "hit_rate": (round(h / (h + miss), 4) if h + miss else None)}
    return per


def _training_block(snap) -> Dict[str, Any]:
    """Paramserver hot-loop phase anatomy (paramserver/training.py +
    overlap.py): per-phase latency summaries (compute / d2h / encode /
    push), the wall step time, and whether the latency-hiding comms
    pipeline is on. ``hidden_ms_total`` is Σ phase totals − wall total —
    positive means comms genuinely ran UNDER the compute (real overlap),
    while the sync loop reads at or below zero (phases stack end to
    end). Empty until a paramserver master has stepped."""
    phases: Dict[str, Any] = {}
    phase_total = 0.0
    for r in snap.get("train_step_phase_ms", []):
        s = r.get("summary")
        if not s:
            continue
        phases[r["labels"].get("phase", "?")] = {
            "mean": round(s["mean_ms"], 3), "p95": s["p95_ms"],
            "max": s["max_ms"], "n": int(s["n"])}
        phase_total += s["mean_ms"] * s["n"]
    if not phases:
        return {}
    out: Dict[str, Any] = {"phase_ms": phases,
                           "phase_ms_total": round(phase_total, 3)}
    wall = _snap_summary(snap, "train_step_wall_ms")
    if wall:
        wall_total = wall["mean_ms"] * wall["n"]
        out["wall_ms"] = {"mean": round(wall["mean_ms"], 3),
                          "p95": wall["p95_ms"], "max": wall["max_ms"],
                          "n": int(wall["n"])}
        out["wall_ms_total"] = round(wall_total, 3)
        out["hidden_ms_total"] = round(phase_total - wall_total, 3)
    ov = _snap_value(snap, "train_overlap_active")
    out["overlap_active"] = bool(ov)
    return out


def _pipeline_block(snap) -> Dict[str, Any]:
    """Input-pipeline anatomy (datasets/prefetch.py): queue depth, the
    residual blocking wait, bytes fed, and the compute/ETL overlap split —
    ``etl_fraction`` near 0 means prefetch+put-ahead hid the ETL behind
    device compute; near 1 means the accelerator starves on input."""
    # input_wait_seconds rides the unit="s" bucket geometry (PR 10), so
    # its p50/p95 are honest bucket quantiles now — the PR-6 exact-only
    # workaround (mean/max) is superseded
    w = _snap_summary(snap, "input_wait_seconds")
    out: Dict[str, Any] = {
        "queue_depth": _snap_value(snap, "input_queue_depth"),
        "batches": _snap_value(snap, "input_batches_total"),
        "bytes_total": _snap_value(snap, "input_bytes_total"),
        "wait_seconds": (None if not w else
                         {"mean_s": round(w["mean_s"], 6),
                          "p50_s": round(w["p50_s"], 6),
                          "p95_s": round(w["p95_s"], 6),
                          "max_s": round(w["max_s"], 6),
                          "n": int(w["n"])}),
    }
    etl = _snap_summary(snap, "training_etl_ms")
    step = _snap_summary(snap, "training_step_ms")
    if etl and step:
        etl_total = etl["mean_ms"] * etl["n"]
        step_total = step["mean_ms"] * step["n"]
        out["etl_ms_total"] = round(etl_total, 3)
        out["step_ms_total"] = round(step_total, 3)
        # training_step_ms is the interval between completions, input
        # wait included: the fraction is etl over it (1 at most)
        if step_total > 0:
            out["etl_fraction"] = round(min(1.0, etl_total / step_total), 4)
    return out


def render_profile_text(report: Dict[str, Any]) -> str:
    """Plain-text rendering of :func:`profile_report` for terminals."""
    lines = ["# jit (per named function)"]
    jit = report.get("jit") or {}
    if jit:
        # disk = persistent_cache_hits (compilecache/): of `compiles`,
        # how many were on-disk cache reads rather than true XLA work
        lines.append(f"{'fn':<28} {'calls':>8} {'compiles':>8} "
                     f"{'disk':>6} {'miss':>7} {'compile_s':>10} "
                     f"{'gflops':>10} {'peak_mb':>8}")
        for name, r in jit.items():
            miss = r.get("cache_miss_ratio")
            flops = r.get("flops")
            peak = r.get("peak_memory_bytes")
            lines.append(
                f"{name:<28} {r['calls']:>8} {r['compiles']:>8} "
                f"{r.get('persistent_cache_hits', 0):>6} "
                f"{miss if miss is not None else '-':>7} "
                f"{r['compile_seconds']:>10} "
                f"{round(flops / 1e9, 3) if flops else '-':>10} "
                f"{round(peak / 1e6, 1) if peak else '-':>8}")
            if r.get("storms"):
                lines.append(f"  !! {r['storms']} retrace storm(s); last "
                             f"delta: {r.get('last_signature_delta')}")
    else:
        lines.append("(no monitored jit activity yet)")
    lines.append("")
    lines.append("# device memory")
    mem = report.get("memory") or {}
    for dev, row in (mem.get("devices") or {}).items():
        lines.append(f"{dev}: in_use={row.get('bytes_in_use')} "
                     f"peak={row.get('peak_bytes_in_use')} "
                     f"limit={row.get('bytes_limit')}")
    if not mem.get("devices"):
        lines.append("(backend reports no memory stats)")
    lines.append(f"live_buffers: {mem.get('live_buffers')}")
    lines.append("")
    lines.append("# steps")
    steps = report.get("steps") or {}
    lines.append(f"iterations={steps.get('iterations')} "
                 f"examples={steps.get('examples')}")
    for k in ("step_ms", "etl_ms"):
        s = steps.get(k)
        if s:
            lines.append(f"{k}: mean={s.get('mean_ms'):.3f} "
                         f"p50={s.get('p50_ms'):.3f} "
                         f"p95={s.get('p95_ms'):.3f} n={int(s.get('n', 0))}")
    pipe = report.get("pipeline") or {}
    if any(v is not None for v in pipe.values()):
        lines.append("")
        lines.append("# pipeline")
        lines.append(f"queue_depth={pipe.get('queue_depth')} "
                     f"batches={pipe.get('batches')} "
                     f"bytes_total={pipe.get('bytes_total')}")
        w = pipe.get("wait_seconds")
        if w:
            lines.append(f"wait_s: mean={w.get('mean_s'):.4f} "
                         f"p50={w.get('p50_s', 0.0):.4f} "
                         f"p95={w.get('p95_s', 0.0):.4f} "
                         f"max={w.get('max_s'):.4f} n={int(w.get('n', 0))}")
        if pipe.get("etl_fraction") is not None:
            lines.append(f"etl_fraction={pipe['etl_fraction']} "
                         f"(etl {pipe.get('etl_ms_total')} ms / step "
                         f"{pipe.get('step_ms_total')} ms)")
    training = report.get("training") or {}
    if training:
        lines.append("")
        lines.append("# training (paramserver hot-loop phases)")
        lines.append(f"overlap_active={training.get('overlap_active')}")
        for p in ("compute", "d2h", "encode", "push"):
            r = (training.get("phase_ms") or {}).get(p)
            if r:
                lines.append(f"{p}: mean={r['mean']:.3f} "
                             f"p95={r['p95']:.3f} max={r['max']:.3f} "
                             f"n={r['n']}")
        w = training.get("wall_ms")
        if w:
            lines.append(f"wall: mean={w['mean']:.3f} p95={w['p95']:.3f} "
                         f"max={w['max']:.3f} n={w['n']}")
        if training.get("hidden_ms_total") is not None:
            lines.append(f"hidden_ms_total={training['hidden_ms_total']} "
                         f"(sum of phases {training.get('phase_ms_total')}"
                         f" ms - wall {training.get('wall_ms_total')} ms)")
    serving = report.get("serving") or {}
    if serving:
        lines.append("")
        lines.append("# serving (per hosted model)")
        lines.append(f"{'model':<20} {'ok':>8} {'rej':>6} {'dl':>5} "
                     f"{'err':>5} {'qps':>7} {'p50_ms':>8} {'p99_ms':>8} "
                     f"{'batch':>6} {'queue':>6} {'cache':>6} "
                     f"{'pad_ms':>7} {'xfer_ms':>8}")
        for name, r in sorted(serving.items()):
            req = r.get("requests", {})
            lat = r.get("latency_ms") or {}
            bat = r.get("batch_examples") or {}
            cache = r.get("cache") or {}
            rate = cache.get("hit_rate")
            lines.append(
                f"{name:<20} {int(req.get('ok', 0)):>8} "
                f"{int(req.get('rejected', 0)):>6} "
                f"{int(req.get('deadline', 0)):>5} "
                f"{int(req.get('error', 0)):>5} "
                f"{round(r.get('qps', 0.0), 1):>7} "
                f"{round(lat.get('p50_ms', 0.0), 2):>8} "
                f"{round(lat.get('p99_ms', 0.0), 2):>8} "
                f"{round(bat.get('mean', 0.0), 1):>6} "
                f"{int(r.get('queue_depth', 0) or 0):>6} "
                f"{rate if rate is not None else '-':>6} "
                f"{(r.get('pad_ms') or {}).get('mean', '-'):>7} "
                f"{(r.get('transfer_ms') or {}).get('mean', '-'):>8}")
    meshes = report.get("mesh") or {}
    if meshes:
        lines.append("")
        lines.append("# mesh (active parallel topologies)")
        lines.append(f"{'style':<28} {'axes':<28} {'devs':>5} "
                     f"{'steps':>6} {'sharded':>8} {'repl':>6} {'zero':>5}")
        for style, r in meshes.items():
            axes = "×".join(f"{a}={n}" for a, n in
                            (r.get("axes") or {}).items()) or "-"
            lines.append(
                f"{style:<28} {axes:<28} {r.get('devices', 0):>5} "
                f"{r.get('steps', 0):>6} {r.get('sharded_leaves', 0):>8} "
                f"{r.get('replicated_leaves', 0):>6} "
                f"{'yes' if r.get('zero') else 'no':>5}")
    locks = report.get("locks") or {}
    if locks:
        lines.append("")
        lines.append("# locks (lockwatch contention)")
        inv = locks.get("_inversions", {}).get("count")
        if inv:
            lines.append(f"  !! {inv} lock-order inversion(s) observed — "
                         f"see the flight recorder")
        lines.append(f"{'lock':<40} {'acq':>8} {'wait_mean_s':>12} "
                     f"{'wait_max_s':>11} {'held_mean_s':>12} "
                     f"{'held_max_s':>11}")
        for name, r in locks.items():
            if name == "_inversions":
                continue
            lines.append(
                f"{name:<40} {r['acquisitions']:>8} "
                f"{r['wait_s_mean']:>12} {r['wait_s_max']:>11} "
                f"{r['held_s_mean']:>12} {r['held_s_max']:>11}")
    control = report.get("control") or {}
    if control:
        lines.append("")
        lines.append("# control (closed-loop control plane)")
        lines.append(f"policies={control.get('policies', 0)} "
                     f"running={'yes' if control.get('running') else 'no'} "
                     f"cooldowns_active={control.get('cooldowns_active', 0)} "
                     f"pending={control.get('pending', 0)} "
                     f"actions_total={control.get('actions_total', 0)}")
        last = control.get("last_action")
        if last:
            lines.append(f"last_action: policy={last.get('policy')} "
                         f"action={last.get('action')} "
                         f"outcome={last.get('outcome')} "
                         f"rule={last.get('rule')} "
                         f"exemplar={last.get('exemplar_trace_id')}")
    startup = report.get("startup") or {}
    if startup:
        lines.append("")
        lines.append("# startup (kept set-up spans — monitor/tracer.py)")
        for r in startup.get("inits", ()):
            lines.append(
                f"init {r.get('network')}: {r['seconds']}s for "
                f"{r.get('parameters')} parameters in {r.get('leaves')} "
                f"leaves ({r.get('bytes')} bytes): drawn "
                f"{r.get('draw_s', '-')}s, placed {r.get('place_s', '-')}s, "
                f"updater state {r.get('updater_state_s', '-')}s")
        ph = startup.get("compile_phase_s") or {}
        lines.append(
            f"compiles={startup.get('compiles', 0)} "
            + " ".join(f"{k}={v}s" for k, v in ph.items()))
        lines.append(
            f"cost_capture={startup.get('cost_capture_s')}s "
            f"place_model={startup.get('place_model_s')}s "
            f"kept={startup.get('kept')} "
            f"kept_dropped={startup.get('kept_dropped')}")
        if "init_to_first_step_compiled_s" in startup:
            lines.append("first init to first step compiled: "
                         f"{startup['init_to_first_step_compiled_s']}s")
    trends = report.get("trends") or {}
    if trends:
        lines.append("")
        lines.append("# trends (now vs 1m/5m — monitor/history.py)")
        for key, row in trends.items():
            if key == "window_s":
                continue
            cells = " ".join(
                f"{k}={round(v, 3) if isinstance(v, float) else v}"
                for k, v in row.items())
            lines.append(f"{key}: {cells}")
    return "\n".join(lines) + "\n"

"""Continuous-batching scheduler: many small requests, one jitted forward.

The serving tier's core loop (docs/SERVING.md). Concurrent callers
``submit()`` single-example (or small-batch) requests; a dedicated
scheduler thread coalesces compatible requests into ONE padded batch,
runs ONE forward per flush, and demultiplexes per-request result rows
back onto each caller's :class:`~concurrent.futures.Future`. This is the
reference ``ParallelInference.java`` observer/``BatchedInferenceObservable``
design rebuilt for an XLA device, with the two production constraints the
reference never had:

- **closed jit signature set.** ``jax.jit`` specializes per input shape,
  so naive coalescing (flush whatever accumulated) feeds the jit cache an
  open set of batch sizes — the retrace-storm failure jitwatch detects
  (docs/OBSERVABILITY.md "Compilation & memory"). Every flush therefore
  pads its batch dim up to a configured **bucket**
  (``datasets/bucketing.py`` rules: smallest admitting bucket, zero-pad
  rows, oversize rejected loudly), and sequence inputs optionally pad
  their time dim up to a time bucket with a zero ``features_mask`` for
  the padding (the records.py/bucketing.py masking convention — mask
  presence is part of the jit signature, so time-bucketed groups ALWAYS
  carry a mask). Steady state compiles exactly
  ``len(batch_buckets) × len(time_buckets)`` variants, no matter how
  request sizes churn.
- **admission control.** The queue is bounded (``max_queue_examples`` /
  ``max_queue_requests``); an over-cap ``submit`` raises the typed
  :class:`OverloadedError` (HTTP 429 at the front door) instead of
  letting latency grow without bound, and every request carries a
  deadline — a request whose deadline expires while queued completes
  with :class:`DeadlineExceededError` (HTTP 504) rather than wasting a
  flush slot. ``close(drain=True)`` stops admission and drains: every
  accepted request still gets its answer.

A lone request is never stranded: the scheduler flushes a partial batch
once the oldest queued request has lingered ``linger_ms`` (the max-linger
bound ``parallel/inference.py`` previously approximated with ad-hoc
``threading.Timer`` threads — ``ParallelInference`` now delegates its
BATCHED path here).

The flush data plane is built for raw speed (ISSUE 11, docs/SERVING.md
"Data-plane tuning"):

- **device residency + donation.** The host only ever moves the REAL
  examples: requests are coalesced into one ``[total, ...]`` host view
  (a lone request ships zero-copy), ``jax.device_put`` once, and the
  padding up to the bucket happens ON DEVICE into a bucket-shaped buffer
  recycled flush-over-flush via XLA buffer donation — the donated buffer
  is only ever overwritten, never read, so stale contents cannot leak
  into padding rows. The forward's output is sliced back to the real
  rows on device and crosses device→host in ONE transfer. The split is
  observable: ``serving/pad`` and ``serving/transfer`` spans nest under
  ``serving/flush``, and ``serving_pad_ms``/``serving_transfer_ms``
  histograms carry the same numbers for /profile.
- **precision.** ``precision="bf16"`` casts inputs to bfloat16 at submit
  (halving host→device bytes) and serves the forward in bf16; responses
  are cast back to float32 on the host side of the single transfer.
  Dtype is part of the jit signature, so each served precision owns its
  own closed ``len(buckets)`` compile set — jitwatch-provable.
- **response cache.** ``cache_size=`` (capacity in EXAMPLES) enables a
  per-model content-addressed LRU checked at ``submit()``: a hit
  resolves the future immediately with a bit-identical copy of the
  cached rows — no queue, no ``serving/queue_wait`` span, no flush —
  counted by ``serving_cache_hits_total``/``serving_cache_misses_total``.

Locking: ONE condition variable (``ContinuousBatcher._cond`` through the
lockwatch factory, so THR003/THR004 and the runtime sanitizer cover it)
guards the queue; the forward always runs OUTSIDE the lock on the
scheduler thread, so submitters never block behind device compute. The
response cache has its own lock (``ContinuousBatcher._cache_lock``),
never held while acquiring the condition (and vice versa) — the serving
lock graph stays edge-free.
"""
from __future__ import annotations

import contextlib
import hashlib
import logging
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future, InvalidStateError
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import numpy as np

from ..datasets.bucketing import bucket_for, validate_buckets
from ..monitor.lockwatch import make_condition, make_lock

log = logging.getLogger(__name__)

#: serving precisions → the numpy dtype submitted floats are cast to.
#: bfloat16 comes from ml_dtypes (a jax dependency), so host buffers can
#: hold it natively and the host→device transfer ships half the bytes.
PRECISIONS = ("f32", "bf16")


def serving_dtype(precision: str) -> np.dtype:
    """The input dtype a serving precision casts float features to."""
    if precision == "bf16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(np.float32)


def _floatish(dtype) -> bool:
    # bfloat16 registers as kind "V" (ml_dtypes extension type), so the
    # float-family test must name it explicitly
    return dtype.kind == "f" or dtype.name == "bfloat16"


#: warm_pads budget: at most this many pad-program pre-compiles per
#: bucket (the default bucket set needs far fewer; see warm_pads)
_WARM_PADS_PER_BUCKET = 64

_PAD_JIT = None


def _pad_jit():
    """The device-side pad: write the coalesced rows into a bucket-shaped
    zero buffer, DONATING the previous flush's buffer so XLA reuses its
    memory for the output instead of allocating fresh. The donated buffer
    is write-only to this op (``zeros_like`` then ``set`` — its VALUES are
    never read), which is what makes recycling safe: stale rows from the
    previous flush can never survive into padding rows. Shared across
    batchers — jax's own cache specializes per shape/dtype, and the set of
    shapes is closed by the bucket set."""
    global _PAD_JIT
    if _PAD_JIT is None:
        import jax
        import jax.numpy as jnp
        # deliberately a bare jax.jit, NOT monitored_jit: the pad program
        # legitimately specializes per (total, bucket) pair — a set
        # bounded by the bucket config — and the per-instance storm
        # detector would report that bounded warm-in as retrace churn,
        # poisoning the zero-storm invariant the MODEL forward must keep
        _PAD_JIT = jax.jit(  # tpulint: disable=JAX003
            lambda buf, rows: jnp.zeros_like(buf).at[:rows.shape[0]]
            .set(rows), donate_argnums=(0,))
    return _PAD_JIT


def _content_key(x: np.ndarray) -> Tuple:
    """The response-cache content address: shape + dtype (which carries
    the precision) + sha256 of the bytes. Hashes the buffer IN PLACE
    when possible — a tobytes() copy of every submitted payload on the
    latency-critical caller thread would undo the submit no-copy work.
    Extension dtypes (ml_dtypes bfloat16) refuse buffer export entirely
    ("cannot include dtype 'E'"), so they take the copy."""
    try:
        buf = x.data if x.flags.c_contiguous else x.tobytes()
    except ValueError:
        buf = x.tobytes()
    return (x.shape, str(x.dtype), hashlib.sha256(buf).digest())


def _complete(fut: Future, value=None, exc: Optional[Exception] = None):
    """Resolve a request future, tolerating caller-side ``cancel()``: a
    cancelled future refuses ``set_result``/``set_exception`` with
    InvalidStateError, and that must never escape into the scheduler
    thread (the caller explicitly said they no longer want the answer).
    Returns True when the future actually took the completion."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(value)
        return True
    except InvalidStateError:
        return False

__all__ = ["ContinuousBatcher", "OverloadedError", "DeadlineExceededError",
           "ModelNotFoundError"]


class OverloadedError(RuntimeError):
    """Admission refused: queue at capacity or the batcher is shutting
    down. The HTTP front door maps this to 429 (with Retry-After) — the
    caller should back off or hit another replica."""


class DeadlineExceededError(RuntimeError):
    """The request's deadline expired before a flush could serve it.
    Mapped to HTTP 504 — the work was shed, not half-done."""


class ModelNotFoundError(KeyError):
    """No model registered under that name (HTTP 404). Lives here so the
    whole typed-error surface of the serving tier imports from one
    module."""


class _Request:
    __slots__ = ("x", "mask", "fut", "key", "n", "t_enq", "t_perf",
                 "deadline", "orig_t", "padded_t", "ctx", "ckey")

    def __init__(self, x, mask, key, t_enq, deadline, orig_t, padded_t,
                 ctx=None, ckey=None):
        self.x = x
        self.mask = mask
        self.fut: Future = Future()
        self.key = key
        self.n = int(x.shape[0])
        self.t_enq = t_enq
        self.t_perf = time.perf_counter()   # tracer timebase for spans
        self.deadline = deadline      # monotonic seconds, or None
        self.orig_t = orig_t          # pre-padding time steps, or None
        self.padded_t = padded_t      # time bucket the input was padded to
        self.ctx = ctx                # SpanContext (serving mode), or None
        self.ckey = ckey              # response-cache key, or None


class ContinuousBatcher:
    """Iteration-level request coalescing behind one forward callable.

    ``forward_fn(xs)`` (or ``forward_fn(xs, mask)`` when a features mask
    is present) receives the assembled ``[bucket, ...]`` batch and returns
    an array whose leading dim matches; result rows are sliced back per
    request. Requests with different trailing shapes/dtypes never mix in
    one flush (each trailing shape is its own jit signature anyway).

    ``queue_policy``: ``"reject"`` (serving default) raises
    :class:`OverloadedError` at the cap; ``"flush"`` (the
    ``ParallelInference`` semantics) instead forces an immediate flush
    and keeps accepting.

    ``precision``: ``"f32"`` (default) or ``"bf16"`` — the dtype float
    inputs are cast to at submit and served in (module docstring).
    ``cache_size``: response-cache capacity in EXAMPLES (None = off).
    ``device_path``: pad/slice on device with donated buffers. Default
    OFF for a directly-constructed batcher — the forward keeps receiving
    host ndarrays, the pre-ISSUE-11 contract (a host-numpy forward must
    not silently start seeing immutable jax.Arrays, nor pay an h2d+d2h
    round trip it never asked for). :class:`ServedModel` turns it on for
    framework nets, whose forwards are jax-backed; device-computing
    custom forwards opt in with ``device_path=True``.
    """

    def __init__(self, forward_fn: Callable, *, name: str = "model",
                 batch_buckets: Optional[Sequence[int]] = None,
                 time_buckets: Optional[Sequence[int]] = None,
                 max_batch: int = 64,
                 max_queue_examples: Optional[int] = 256,
                 max_queue_requests: Optional[int] = None,
                 linger_ms: float = 5.0,
                 default_deadline_ms: Optional[float] = None,
                 queue_policy: str = "reject",
                 in_flight: Optional[threading.Semaphore] = None,
                 metrics_label: Optional[str] = None,
                 qps_window_s: float = 10.0,
                 precision: str = "f32",
                 cache_size: Optional[int] = None,
                 device_path: Optional[bool] = None):
        if queue_policy not in ("reject", "flush"):
            raise ValueError(f"queue_policy must be 'reject' or 'flush', "
                             f"got {queue_policy!r}")
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, "
                             f"got {precision!r}")
        self.name = str(name)
        self._forward = forward_fn
        self.precision = precision
        self._in_dtype = serving_dtype(precision)
        if cache_size is not None and int(cache_size) < 1:
            # 0 raises like -1 does — a miscomputed capacity must not
            # silently serve uncached (None is the one off spelling)
            raise ValueError(f"cache_size must be >= 1 examples, got "
                             f"{cache_size}")
        self.cache_size = (int(cache_size) if cache_size is not None
                           else None)
        # content-addressed LRU: ckey -> READ-ONLY result rows (hits hand
        # out writable copies, so no caller can corrupt the cached master)
        self._cache: Optional[OrderedDict] = (
            OrderedDict() if self.cache_size is not None else None)
        self._cache_examples = 0
        self._cache_lock = (make_lock("ContinuousBatcher._cache_lock")
                            if self._cache is not None else None)
        self._device_path = bool(device_path)
        # per-(key, bucket) device-resident pad buffer, recycled via
        # donation each flush; scheduler-thread-only, dropped on close
        self._dev_bufs: Dict[Tuple, object] = {}
        self._bb = (validate_buckets(batch_buckets, "batch")
                    if batch_buckets else None)
        self._tb = (validate_buckets(time_buckets, "time")
                    if time_buckets else None)
        self.max_batch = self._bb[-1] if self._bb else int(max_batch)
        self.max_queue_examples = max_queue_examples
        self.max_queue_requests = max_queue_requests
        self.linger_ms = float(linger_ms)
        self.default_deadline_ms = default_deadline_ms
        self.queue_policy = queue_policy
        self._in_flight = in_flight
        self._label = metrics_label
        self._qps_window = float(qps_window_s)

        self._cond = make_condition("ContinuousBatcher._cond")
        self._queue: List[_Request] = []
        self._queued_examples = 0
        self._key_examples: Dict[Tuple, int] = {}
        self._force = False
        self._closed = False
        self._running = False          # a flush is executing forward_fn
        # completion stamps for the qps gauge: deque so the window trim
        # is O(1) popleft per aged-out stamp — a plain list's pop(0)
        # memmove would grow per-completion cost linearly with sustained
        # QPS, under the shared condition, on the cache-hit fast path
        self._done_times: Deque[float] = deque()
        self._handles = None
        self._thread = threading.Thread(
            target=self._loop, name=f"serving-batcher-{self.name}",
            daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- metrics
    def _metric_handles(self):
        # lazy, like MonitoredJit: constructing a batcher must not
        # populate /metrics until traffic actually flows
        if self._label is None:
            return None
        if self._handles is None:
            from ..monitor.registry import get_registry
            reg = get_registry()
            handles = {
                "req_ok": reg.counter(
                    "serving_requests_total",
                    "inference requests by outcome "
                    "(ok/rejected/deadline/error)",
                    model=self._label, outcome="ok"),
                "latency": reg.histogram(
                    "serving_request_latency_ms",
                    "request latency, submit to result (queue + batch "
                    "assembly + forward)", model=self._label),
                "batch": reg.histogram(
                    "serving_batch_examples",
                    "real (pre-padding) examples per flushed batch",
                    model=self._label),
                "depth": reg.gauge(
                    "serving_queue_depth",
                    "requests currently queued for batching",
                    model=self._label),
                "depth_ex": reg.gauge(
                    "serving_queue_examples",
                    "examples currently queued for batching — the unit "
                    "the admission cap (max_queue_examples) is in, so "
                    "saturation alerts compare like with like",
                    model=self._label),
                "qps": reg.gauge(
                    "serving_qps",
                    "completed requests per second over the trailing "
                    "window", model=self._label),
                "pad": reg.histogram(
                    "serving_pad_ms",
                    "per-flush batch-assembly time: host coalesce + mask "
                    "pad + on-device pad to the bucket shape",
                    model=self._label),
                "xfer": reg.histogram(
                    "serving_transfer_ms",
                    "per-flush host<->device movement: one device_put of "
                    "the real examples in, one sliced fetch out",
                    model=self._label),
            }
            if self._cache is not None:
                handles["c_hit"] = reg.counter(
                    "serving_cache_hits_total",
                    "response-cache hits — requests answered without "
                    "queueing or a flush", model=self._label)
                handles["c_miss"] = reg.counter(
                    "serving_cache_misses_total",
                    "response-cache misses — requests that paid the full "
                    "queue + flush path", model=self._label)
            # publish COMPLETE: concurrent submitters read this dict
            # lock-free (_cache_count), so the assignment must be the
            # last step — a partially-built dict must never be visible
            self._handles = handles
        return self._handles

    def _cache_count(self, hit: bool):
        # cached handles: the hit path runs on the latency-critical
        # caller thread — no per-submit registry-lock lookup
        h = self._metric_handles()
        if h is not None:
            (h["c_hit"] if hit else h["c_miss"]).inc()

    def _count(self, outcome: str, n: int = 1):
        if self._label is None:
            return
        if outcome == "ok" and self._handles is not None:
            # the hot completion path (every cache hit, every flushed
            # request) rides the cached handle — no registry-lock lookup
            self._handles["req_ok"].inc(n)
            return
        from ..monitor.registry import get_registry
        get_registry().counter(
            "serving_requests_total",
            "inference requests by outcome (ok/rejected/deadline/error)",
            model=self._label, outcome=outcome).inc(n)

    def _note_done(self, outcome: str, latency_ms: Optional[float] = None,
                   exemplar: Optional[str] = None):
        h = self._metric_handles()
        self._count(outcome)
        if h is None:
            return
        if latency_ms is not None:
            # the exemplar (the request's trace id) rides the worst-bucket
            # latch, so a firing p99 alert can name a concrete trace
            h["latency"].observe(latency_ms, exemplar=exemplar)
        now = time.monotonic()
        # trailing-window QPS under the condition (cache hits complete on
        # SUBMITTER threads since ISSUE 11, so the window is no longer
        # scheduler-thread-only; _set_depth already writes gauges under
        # the cond, same registry-lock ordering)
        with self._cond:
            if self._closed and not self._thread.is_alive():
                # a late cache hit completing after close: the scheduler
                # (the only decay driver) is gone and has already zeroed
                # the gauge — re-latching a nonzero qps here would freeze
                # a dead model at that value forever
                return
            was_empty = not self._done_times
            self._done_times.append(now)
            self._trim_done(now, h)
            if was_empty:
                # wake a scheduler parked with wait(None) — it only parks
                # unbounded when the window is empty; with completions
                # already in the window a decay timeout is armed, so the
                # common per-request completion skips the wakeup. The
                # empty→nonempty edge re-arms idle decay when ONLY cache
                # hits (submitter threads) have been completing
                self._cond.notify_all()

    def _trim_done(self, now: float, h) -> bool:
        """Drop completions older than the window and refresh the qps
        gauge — the ONE implementation behind both the completion path
        and the idle decay (they must never disagree on the gauge).
        Returns True when anything aged out."""
        cut = now - self._qps_window
        changed = False
        while self._done_times and self._done_times[0] < cut:
            self._done_times.popleft()
            changed = True
        if h is not None:
            h["qps"].set(len(self._done_times) / self._qps_window)
        return changed

    def _decay_qps(self, now: float):
        """Scheduler-driven staleness fix: the trailing-window gauge is
        otherwise only written by completion bookkeeping, so after traffic
        stops it would report the last value FOREVER. The idle scheduler
        wakes as completions age out of the window (see
        ``_wait_timeout_locked``) and walks the gauge down to zero."""
        if not self._done_times:
            return
        self._trim_done(now, self._metric_handles())

    def _set_depth(self):
        h = self._metric_handles()
        if h is not None:
            h["depth"].set(len(self._queue))
            h["depth_ex"].set(self._queued_examples)

    # -------------------------------------------------------- response cache
    def _cache_lookup(self, ckey):
        """LRU get (submitter threads). The cache lock is never held while
        taking the batcher condition — no lock-graph edge."""
        with self._cache_lock:
            got = self._cache.get(ckey)
            if got is not None:
                self._cache.move_to_end(ckey)
            return got

    def _cache_store(self, ckey, rows: np.ndarray):
        """Insert freshly-computed result rows (scheduler thread). The
        stored master is an owned, read-only copy — decoupled from the
        flush's big output buffer, immune to caller mutation — and hits
        are byte-for-byte what the flush computed."""
        if self._closed:
            # a drain-window flush after close() started: storing would
            # repopulate the cache BEHIND close's clear (the join may
            # have timed out) — the drained futures still resolve, the
            # result just isn't cached for a model being torn down
            return
        master = np.array(rows)
        master.flags.writeable = False
        n = int(rows.shape[0]) if rows.ndim >= 1 else 1
        with self._cache_lock:
            old = self._cache.pop(ckey, None)
            if old is not None:
                self._cache_examples -= (int(old.shape[0])
                                         if old.ndim >= 1 else 1)
            self._cache[ckey] = master
            self._cache_examples += n
            while self._cache_examples > self.cache_size and self._cache:
                _, evicted = self._cache.popitem(last=False)
                self._cache_examples -= (int(evicted.shape[0])
                                         if evicted.ndim >= 1 else 1)

    def cache_stats(self) -> Dict[str, int]:
        """Live cache occupancy (entries, examples) for stats()/tests."""
        if self._cache is None:
            return {"entries": 0, "examples": 0}
        with self._cache_lock:
            return {"entries": len(self._cache),
                    "examples": self._cache_examples}

    # -------------------------------------------------------------- submit
    def submit(self, x, deadline_ms: Optional[float] = None,
               trace_ctx=None, cache_bypass: bool = False) -> Future:
        """Queue a request; returns a Future resolving to the result rows
        for exactly the submitted examples (padding never leaks out).

        ``x``: ``[b, ...]`` features (``b >= 1``). Raises
        :class:`OverloadedError` when the queue is at capacity (policy
        ``"reject"``) or the batcher is closed; ``ValueError`` when ``b``
        exceeds the largest bucket (configure a bucket that fits).

        ``cache_bypass``: skip the response cache ENTIRELY for this
        request — no lookup, and the computed result is never stored
        (the request keeps ``ckey=None`` end to end). The probe plane
        sets this (via the ``X-DL4J-Probe`` header): a synthetic probe
        answered from the LRU would prove nothing about the live model
        path, and a probe must not evict real traffic's entries either.

        ``trace_ctx``: the request's :class:`SpanContext` (the HTTP front
        door forwards the caller's ``X-DL4J-Trace`` header, or its own
        ``http/predict`` span). Serving-labeled batchers mint a fresh
        context when none is given, so EVERY request owns a trace id —
        the scheduler records a ``serving/queue_wait`` span under it
        (linked to the shared ``serving/flush`` span) and latches it as
        the latency histogram's exemplar.

        **No-copy / no-mutation contract**: an ndarray whose float dtype
        already matches the serving precision is enqueued AS-IS — no
        ``asarray`` copy, no cast (the old path re-copied every submit).
        The batcher never mutates a submitted array; in return the caller
        must not mutate it until the returned future resolves (the flush
        reads it exactly once, to coalesce the device batch). The
        contract extends to the FORWARD: a lone conforming request may
        be handed to ``forward_fn`` as-is (zero-copy end to end), so a
        custom forward must not mutate its input batch in place — it may
        be the caller's own memory. Exception:
        a CACHE-enabled model copies on a miss — the content address must
        name immutable bytes, or a contract-violating caller could plant
        a poisoned entry that other callers of those bytes would hit."""
        owned = not isinstance(x, np.ndarray)
        if owned:
            x = np.asarray(x)
        if _floatish(x.dtype) and x.dtype != self._in_dtype:
            # the ONLY submit-path copy, and only for non-conforming
            # dtypes (f64 callers, or any float feeding a bf16 model)
            x = x.astype(self._in_dtype)
            owned = True
        if x.ndim < 1 or x.shape[0] < 1:
            raise ValueError(f"request must be [b, ...] with b >= 1, "
                             f"got shape {x.shape}")
        b = int(x.shape[0])
        if self._bb is not None and b > self.max_batch:
            # only a HARD limit when buckets are configured (no bucket can
            # pad it); unbucketed mode treats max_batch as the flush
            # trigger and serves an oversize request as its own batch —
            # the original ParallelInference accept-and-flush semantics
            raise ValueError(
                f"request of {b} examples exceeds the largest batch "
                f"bucket {self.max_batch} — split the request or "
                f"configure a bigger bucket")
        ckey = None
        if self._cache is not None and not self._closed and not cache_bypass:
            # a closed (draining) batcher must not keep answering cached
            # inputs while rejecting uncached ones — admission after
            # close() is uniform: skip the fast path, let the cond-
            # guarded admission below raise OverloadedError (the
            # unlocked _closed read races close() at most as much as the
            # submit itself would)
            # content address = the submitted bytes (pre-padding) + shape
            # + dtype; dtype carries the precision, the per-model cache
            # carries the model — together the full ISSUE-11 cache key
            ckey = _content_key(x)
            hit = self._cache_lookup(ckey)
            if hit is not None:
                # a hit skips the queue ENTIRELY: no queue_wait span, no
                # flush — the future resolves here, on the caller's
                # thread, with a writable bit-identical copy. It still
                # counts as a completion everywhere (ok outcome, ~0ms
                # latency sample, the trailing-QPS window), so the qps
                # gauge stays honest for cache-heavy workloads
                self._cache_count(True)
                self._note_done(
                    "ok", 0.0,
                    exemplar=(f"{trace_ctx.trace_id:x}"
                              if trace_ctx is not None else None))
                fut: Future = Future()
                fut.set_result(hit.copy())
                return fut
            if not owned:
                # a MISS will be stored under sha256(these bytes) at
                # flush time — own them now, so a caller mutating its
                # array in the linger window (violating the no-mutation
                # contract) can only corrupt its own answer, never plant
                # a poisoned entry other callers would hit. The no-copy
                # fast path is therefore an uncached-model guarantee; a
                # content address must name immutable bytes.
                x = np.array(x)
                # ... and re-derive the address from the OWNED bytes: a
                # racing mutation in the hash→copy window above would
                # otherwise file f(mutated) under the ORIGINAL bytes'
                # hash — the exact cross-caller poisoning the copy
                # exists to prevent. Costs one extra hash per miss; the
                # hit path stays copy-free
                ckey = _content_key(x)
        mask = orig_t = padded_t = None
        if self._tb is not None and x.ndim >= 3:
            # sequence request [b, T, f]: pad T up to its time bucket and
            # carry a features mask (ALWAYS, even when T already fits — a
            # sometimes-present mask would double the signature set)
            orig_t = int(x.shape[1])
            padded_t = bucket_for(self._tb, orig_t, "time")
            mask = np.zeros((b, padded_t), np.float32)
            mask[:, :orig_t] = 1.0
            if padded_t != orig_t:
                pad = np.zeros((b, padded_t - orig_t) + x.shape[2:],
                               x.dtype)
                x = np.concatenate([x, pad], axis=1)
        key = (x.shape[1:], str(x.dtype), mask is not None)
        now = time.monotonic()
        dl_ms = deadline_ms if deadline_ms is not None \
            else self.default_deadline_ms
        ctx = trace_ctx
        if ctx is None and self._label is not None:
            # serving mode: every request gets a trace identity even when
            # the caller brought none (direct registry.submit callers)
            from ..monitor.tracer import new_context
            ctx = new_context()
        req = _Request(x, mask, key, now,
                       now + dl_ms / 1e3 if dl_ms is not None else None,
                       orig_t, padded_t, ctx=ctx, ckey=ckey)
        with self._cond:
            if self._closed:
                self._count("rejected")
                raise OverloadedError(
                    f"model {self.name!r} is shutting down")
            over = ((self.max_queue_examples is not None
                     and self._queued_examples + b > self.max_queue_examples)
                    or (self.max_queue_requests is not None
                        and len(self._queue) + 1 > self.max_queue_requests))
            if over and self.queue_policy == "reject":
                self._count("rejected")
                raise OverloadedError(
                    f"model {self.name!r} overloaded: "
                    f"{self._queued_examples} examples / "
                    f"{len(self._queue)} requests queued (caps: "
                    f"{self.max_queue_examples} examples, "
                    f"{self.max_queue_requests} requests)")
            self._queue.append(req)
            self._queued_examples += b
            self._key_examples[key] = self._key_examples.get(key, 0) + b
            if over:                      # policy "flush": drain, keep going
                self._force = True
            self._set_depth()
            self._cond.notify_all()
        if ckey is not None:
            # counted only for ADMITTED requests — a 429'd submit neither
            # hit nor missed, and must not depress the hit rate
            self._cache_count(False)
        return req.fut

    # ----------------------------------------------------------- scheduler
    def _ripe_locked(self, now: float) -> bool:
        if not self._queue:
            return False
        if self._force or self._closed:
            return True
        if any(n >= self.max_batch for n in self._key_examples.values()):
            return True
        if (self.max_queue_requests is not None
                and len(self._queue) >= self.max_queue_requests):
            return True
        # an expired deadline is ripe too: the request must complete with
        # DeadlineExceededError NOW, not spin-wait until the linger bound
        if any(r.deadline is not None and now > r.deadline
               for r in self._queue):
            return True
        return (now - self._queue[0].t_enq) * 1e3 >= self.linger_ms

    def _wait_timeout_locked(self, now: float) -> Optional[float]:
        """Sleep until the oldest request's linger expires or the nearest
        deadline passes, whichever is sooner. With an empty queue but a
        non-empty qps window, wake when the oldest completion ages out so
        ``_decay_qps`` can walk the gauge down (None = park until
        notified)."""
        if not self._queue:
            if self._done_times:
                return max(self._done_times[0] + self._qps_window - now,
                           0.0) + 0.05
            return None
        t = self._queue[0].t_enq + self.linger_ms / 1e3
        for r in self._queue:
            if r.deadline is not None:
                t = min(t, r.deadline)
        return max(t - now, 0.0)

    def _take_locked(self, now: float):
        """Pop expired requests plus one same-key batch (FIFO head's key,
        up to the bucket cap). Futures complete OUTSIDE the lock."""
        expired, batch = [], []
        keep = []
        for r in self._queue:
            if r.deadline is not None and now > r.deadline:
                expired.append(r)
                self._queued_examples -= r.n
                self._key_examples[r.key] -= r.n
            else:
                keep.append(r)
        self._queue = keep
        if self._queue:
            key = self._queue[0].key
            taken = 0
            keep = []
            for r in self._queue:
                # the head is ALWAYS taken (an unbucketed oversize request
                # must flush as its own batch, never starve); others join
                # while the cap holds
                if r.key == key and (not batch
                                     or taken + r.n <= self.max_batch):
                    batch.append(r)
                    taken += r.n
                else:
                    keep.append(r)
            self._queue = keep
            self._queued_examples -= taken
            self._key_examples[key] -= taken
        for k in [k for k, n in self._key_examples.items() if n <= 0]:
            del self._key_examples[k]
        if not self._queue:
            self._force = False
        self._set_depth()
        return expired, batch

    def _loop(self):
        try:
            self._loop_inner()
        finally:
            # the scheduler OWNS _dev_bufs (scheduler-thread-only): it
            # releases device residency on ITS way out, so even a close()
            # whose join timed out mid-drain sees the buffers dropped
            # when the drain actually finishes — close() only clears
            # them itself once the thread is provably dead
            self._dev_bufs.clear()

    def _loop_inner(self):
        while True:
            with self._cond:
                now = time.monotonic()
                while not self._ripe_locked(now):
                    if self._closed and not self._queue:
                        # the gauge must not outlive the scheduler: a
                        # closed model frozen at its last nonzero qps
                        # would report a dead model as serving forever
                        self._done_times.clear()
                        h = self._metric_handles()
                        if h is not None:
                            h["qps"].set(0.0)
                        return
                    if self._force and not self._queue:
                        self._force = False    # stale flush() of an idle
                                               # queue must not bypass the
                                               # next request's linger
                    self._cond.wait(self._wait_timeout_locked(now))
                    now = time.monotonic()
                    # idle ticks double as the qps-gauge decay driver
                    # (_done_times is cond-guarded: cache hits append
                    # from submitter threads and notify, so a park with
                    # wait(None) re-arms against the refreshed window)
                    self._decay_qps(now)
                expired, batch = self._take_locked(now)
                self._running = bool(batch)
            try:
                for r in expired:
                    if _complete(r.fut, exc=DeadlineExceededError(
                            f"deadline expired after "
                            f"{(now - r.t_enq) * 1e3:.1f}ms in queue "
                            f"(model {self.name!r})")):
                        self._note_done("deadline")
                if batch:
                    self._run_batch(batch)
            except Exception:
                # the scheduler thread must survive anything — a dead
                # scheduler turns every future submit into a silent hang
                # (_run_batch resolves per-request errors itself; this is
                # the last-resort belt)
                log.exception("serving batcher %s: scheduler iteration "
                              "failed", self.name)
            finally:
                with self._cond:
                    self._running = False
                    self._cond.notify_all()

    def _use_device(self) -> bool:
        return self._device_path

    def _span(self, name: str, **args):
        if self._label is None:
            return contextlib.nullcontext()
        from ..monitor.tracer import get_tracer
        return get_tracer().span(name, cat="serving", model=self.name,
                                 **args)

    def _coalesce(self, batch: List[_Request], padded: int):
        """Host-side coalesce of the REAL examples only — ``[total, ...]``
        — plus the bucket-shaped mask. A lone request IS the coalesced
        batch (zero host copies: the submit no-copy contract holds end to
        end; the one read happens here). Padding rows are NOT materialized
        on host — they are the device pad's job."""
        xs = batch[0].x if len(batch) == 1 else np.concatenate(
            [r.x for r in batch], axis=0)
        mask = None
        if batch[0].mask is not None:
            # masks are tiny [b, T] f32: pad rows to the bucket here; zero
            # rows contribute nothing to mask-aware layers (bucketing.py
            # convention)
            mask = np.zeros((padded,) + batch[0].mask.shape[1:], np.float32)
            pos = 0
            for r in batch:
                mask[pos:pos + r.n] = r.mask
                pos += r.n
        return xs, mask

    def _pad_device(self, xs_dev, padded: int, key):
        """Pad to the bucket ON DEVICE, recycling the previous flush's
        bucket-shaped buffer via donation (module docstring). The donated
        handle is dead after the call — only the new buffer is kept, as
        the forward's input and then as the NEXT flush's donation."""
        import jax.numpy as jnp
        shape = (padded,) + tuple(xs_dev.shape[1:])
        buf = self._dev_bufs.pop((key, padded), None)
        if buf is None or buf.shape != shape or buf.dtype != xs_dev.dtype:
            buf = jnp.zeros(shape, xs_dev.dtype)
        out = _pad_jit()(buf, xs_dev)
        self._dev_bufs[(key, padded)] = out
        return out

    def compile_signatures(self, input_shape: Sequence[int]
                           ) -> List[Tuple[Tuple[int, ...], str, bool]]:
        """The CLOSED forward compile set this batcher will ever request
        for a model with per-example trailing shape ``input_shape``:
        ``[(batch_shape, dtype, masked), ...]`` — one entry per batch
        bucket (× time bucket for sequence models), in the serving
        dtype. This enumeration is the single source of truth shared by
        ``ServedModel.warm()`` (pre-compile each signature live) and the
        AOT warmup-artifact exporter (``compilecache/artifacts.py`` —
        serialize each signature's compiled executable), so an artifact
        can never silently cover a different set than warm() compiles."""
        shape = tuple(int(d) for d in input_shape)
        dt = str(np.dtype(self._in_dtype))
        out: List[Tuple[Tuple[int, ...], str, bool]] = []
        for n in (self._bb or [self.max_batch]):
            if self._tb is not None and len(shape) >= 2:
                # one variant per (batch, time) bucket, masked — mask
                # presence is part of the jit signature (module docstring)
                for tt in self._tb:
                    out.append(((n, tt) + shape[1:], dt, True))
            else:
                out.append(((n,) + shape, dt, False))
        return out

    def warm_pads(self, trailing: Sequence[int], masked: bool = False):
        """Pre-compile the device-pad programs for every (real rows,
        bucket) pair with this trailing shape — warm()'s cold-start-
        paid-once contract extended to the data plane: the pad jit
        legitimately specializes per pair (``_pad_jit``), and without
        this the first live flush at each partial batch size pays that
        (trivial) compile inside a request's ``serving/flush``, spiking
        warm-in p99 and skewing ``serving_pad_ms``. Pre-traffic only
        (same convention as warm()'s direct forward calls: ``_dev_bufs``
        is scheduler-thread-only once requests flow)."""
        if not self._bb or not self._use_device():
            return
        import jax
        key = (tuple(int(d) for d in trailing),
               str(np.dtype(self._in_dtype)), masked)
        lo = 0
        for bucket in self._bb:
            gap = range(lo + 1, bucket)
            if len(gap) > _WARM_PADS_PER_BUCKET:
                # coarse bucket sets (e.g. (64, 512)) would otherwise pay
                # one compile per admissible row count — hundreds of
                # trivial programs before registration returns. Warm an
                # evenly-spaced subset; uncovered sizes warm in their
                # first live flush (the pre-warmup behavior), bounded by
                # the same closed set either way
                step = max(1, len(gap) // _WARM_PADS_PER_BUCKET)
                gap = list(gap)[::step]
            for total in gap:
                rows = jax.device_put(
                    np.zeros((total,) + key[0], self._in_dtype))
                self._pad_device(rows, bucket, key)
            lo = bucket

    def _stage_in(self, batch: List[_Request], total: int, padded: int):
        """Assemble the padded device batch: coalesce (host), ONE h2d
        transfer of the real examples, pad on device. Returns
        ``(xs, mask, pad_seconds, h2d_seconds)``; falls back to host
        padding when the device path is off (the direct-construction
        default; :class:`ServedModel` enables it for framework nets)."""
        t0 = time.perf_counter()
        with self._span("serving/pad", examples=int(total),
                        padded=int(padded)):
            xs, mask = self._coalesce(batch, padded)
        t1 = time.perf_counter()
        if self._use_device():
            import jax
            with self._span("serving/transfer", direction="h2d"):
                xs = jax.device_put(xs).block_until_ready()
                if mask is not None:
                    mask = jax.device_put(mask)
            t2 = time.perf_counter()
            if int(xs.shape[0]) != padded:
                with self._span("serving/pad", padded=int(padded)):
                    xs = self._pad_device(
                        xs, padded, batch[0].key).block_until_ready()
            return xs, mask, (t1 - t0) + (time.perf_counter() - t2), t2 - t1
        if int(xs.shape[0]) != padded:
            with self._span("serving/pad", padded=int(padded)):
                out = np.zeros((padded,) + xs.shape[1:], xs.dtype)
                out[:xs.shape[0]] = xs
                xs = out
        return xs, mask, time.perf_counter() - t0, 0.0

    def _stage_out(self, ys, total: int):
        """Slice the padding off (on device, when the forward's output
        lives there) and cross device→host ONCE; bf16 outputs are cast to
        f32 on the host side of the transfer — half the wire bytes."""
        if getattr(ys, "ndim", 0) >= 1 and ys.shape[0] >= total:
            ys = ys[:total]
        with self._span("serving/transfer", direction="d2h",
                        examples=int(total)):
            out = np.asarray(ys)
        if out.dtype.name == "bfloat16":
            out = out.astype(np.float32)
        return out

    def _forward_batch(self, xs, mask):
        if self._in_flight is not None:
            self._in_flight.acquire()
        try:
            return self._forward(xs) if mask is None \
                else self._forward(xs, mask)
        finally:
            if self._in_flight is not None:
                self._in_flight.release()

    def _flush_once(self, batch: List[_Request], total: int, padded: int):
        """stage-in → forward → stage-out, returning the host result rows
        plus the pad/transfer timing split."""
        xs, mask, t_pad, t_h2d = self._stage_in(batch, total, padded)
        ys = self._forward_batch(xs, mask)
        if self._use_device():
            # jit dispatch is async: synchronize HERE so the compute tail
            # lands in the forward's share of serving/flush, not in the
            # d2h transfer span below
            import jax
            ys = jax.block_until_ready(ys)
        t0 = time.perf_counter()
        out = self._stage_out(ys, total)
        return out, t_pad, t_h2d + (time.perf_counter() - t0)

    def _run_batch(self, batch: List[_Request]):
        try:
            total = sum(r.n for r in batch)
            padded = (bucket_for(self._bb, total, "batch")
                      if self._bb else total)
            flush_start = time.perf_counter()
            if self._label is not None:
                # request-scoped tracing (docs/OBSERVABILITY.md): ONE
                # shared serving/flush span on the scheduler thread — the
                # serving/pad + serving/transfer stage spans and compiles
                # inside the forward nest under it — and each request's
                # queue-wait span below links to it, so p99 decomposes
                # into queue vs pad vs transfer vs compute per trace
                from ..monitor.tracer import get_tracer
                with get_tracer().span(
                        "serving/flush", cat="serving", model=self.name,
                        examples=int(total), padded=int(padded),
                        requests=len(batch)) as flush_ctx:
                    ys, t_pad, t_xfer = self._flush_once(batch, total,
                                                         padded)
            else:
                flush_ctx = None
                ys, t_pad, t_xfer = self._flush_once(batch, total, padded)
            h = self._metric_handles()
            if h is not None:
                h["batch"].observe(float(total))
                h["pad"].observe(t_pad * 1e3)
                h["xfer"].observe(t_xfer * 1e3)
            done = time.monotonic()
            if flush_ctx is not None:
                from ..monitor.tracer import get_tracer
                tracer = get_tracer()
                for r in batch:
                    if r.ctx is None:
                        continue
                    tracer.record_complete(
                        "serving/queue_wait", r.t_perf,
                        max(flush_start - r.t_perf, 0.0), cat="serving",
                        parent=r.ctx, model=self.name,
                        flush_span_id=f"{flush_ctx.span_id:x}")
            pos = 0
            for r in batch:
                yr = ys[pos:pos + r.n]
                pos += r.n
                if (r.padded_t is not None and r.padded_t != r.orig_t
                        and yr.ndim >= 2 and yr.shape[1] == r.padded_t):
                    # per-timestep output ([b, T', ...] tracking the padded
                    # time dim): strip the time padding from the result too
                    yr = yr[:, :r.orig_t]
                if self._cache is not None and r.ckey is not None:
                    self._cache_store(r.ckey, yr)
                if _complete(r.fut, yr):
                    self._note_done(
                        "ok", (done - r.t_enq) * 1e3,
                        exemplar=(f"{r.ctx.trace_id:x}" if r.ctx is not None
                                  else None))
        except Exception as e:
            for r in batch:
                if not r.fut.done() and _complete(r.fut, exc=e):
                    self._note_done("error")

    # ------------------------------------------------------------ lifecycle
    def flush(self, wait: bool = True, timeout: float = 30.0) -> bool:
        """Force everything queued to flush now (ignoring linger).
        ``wait=True`` blocks until the queue is empty and no flush is
        executing; returns False on timeout."""
        deadline = time.monotonic() + timeout
        with self._cond:
            if not self._queue and not self._running:
                return True       # idle: nothing to flush, and leaving
                                  # _force armed would rob the NEXT lone
                                  # request of its linger coalescing
            self._force = True
            self._cond.notify_all()
            if not wait:
                return True
            while self._queue or self._running:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
        return True

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    def set_admission(self, max_queue_examples: Optional[int] = None,
                      linger_ms: Optional[float] = None
                      ) -> Dict[str, Any]:
        """Mutate the admission knobs of a LIVE batcher — the control
        plane's serving actuator. A lowered ``max_queue_examples`` only
        tightens the gate for FUTURE submits (already-queued examples are
        served, never evicted — admission was a promise); a lowered
        ``linger_ms`` wakes the scheduler so a queue that was sitting out
        a long linger re-arms on the new deadline immediately. Returns
        the previous values so a resolve-edge can restore them."""
        with self._cond:
            prev = {"max_queue_examples": self.max_queue_examples,
                    "linger_ms": self.linger_ms}
            if max_queue_examples is not None:
                cap = int(max_queue_examples)
                if cap < 1:
                    raise ValueError(
                        f"max_queue_examples must be >= 1, got {cap}")
                self.max_queue_examples = cap
            if linger_ms is not None:
                lg = float(linger_ms)
                if lg < 0:
                    raise ValueError(f"linger_ms must be >= 0, got {lg}")
                self.linger_ms = lg
            self._cond.notify_all()
        return prev

    def close(self, drain: bool = True, timeout: float = 30.0):
        """Stop admission, then either serve (``drain=True`` — no accepted
        request is dropped) or fail (``drain=False`` → OverloadedError)
        everything still queued, and join the scheduler thread."""
        with self._cond:
            self._closed = True
            dropped: List[_Request] = []
            if not drain:
                dropped, self._queue = self._queue, []
                self._queued_examples = 0
                self._key_examples.clear()
            self._cond.notify_all()
        for r in dropped:
            if _complete(r.fut, exc=OverloadedError(
                    f"model {self.name!r} shut down without drain")):
                # counter only — _note_done's qps window belongs to the
                # scheduler thread, which may still be draining a batch
                self._count("rejected")
        self._thread.join(timeout)
        # release device residency: the recycled pad buffers (and the
        # response cache) must not outlive the model they served —
        # device_memory_in_use_bytes drops back after unregister. A join
        # that TIMED OUT leaves the scheduler draining: _dev_bufs is its
        # data structure (mutating it here would race), so only clear
        # when the thread is provably dead — the scheduler's own _loop
        # finally releases the buffers when the drain actually ends
        if not self._thread.is_alive():
            self._dev_bufs.clear()
        if self._cache is not None:
            with self._cache_lock:
                self._cache.clear()
                self._cache_examples = 0
            # belt for the drain-window race: a hit that appended between
            # the scheduler's own exit-zeroing and the join lands here;
            # anything later is refused by _note_done's closed-and-dead
            # guard — between the two, a dead model always reads qps 0
            h = self._metric_handles()
            if h is not None:
                with self._cond:
                    self._done_times.clear()
                    h["qps"].set(0.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

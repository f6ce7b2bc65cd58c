"""Multi-worker prefetch with device-put-ahead double buffering.

The step-anatomy report (``GET /profile``) splits ``step_ms`` from
``etl_ms``, and on input-bound workloads it shows the fit loops paying the
full host ETL latency on the training thread, then the host→device
transfer inside the step. This module is the production generalization of
:class:`~deeplearning4j_tpu.datasets.iterators.AsyncDataSetIterator`
(reference ``AsyncDataSetIterator.java``'s single prefetch thread):

- :class:`PrefetchIterator` — N worker threads pull from the base
  iterator. Pulls are serialized (python iterators are not thread-safe)
  and sequence-numbered, so the per-batch *processing* (decode, augment,
  padding, host cast, device transfer) runs in parallel while **batch
  order is preserved exactly**. Worker exceptions re-raise on the
  consumer thread at the position they occurred — a dead worker can
  never silently hang the training loop (bounded-timeout waits plus a
  liveness check).
- :class:`PrefetchDataSetIterator` — the DataSetIterator seam with
  **device-put-ahead**: while step *k* computes, batch *k+1* is already
  ``jax.device_put`` (optionally under the model's input
  ``Sharding`` when driving a ``parallel/`` mesh step), so the fit
  loops' ``etl_ms`` measures only a queue pop and the H2D transfer
  overlaps device compute instead of extending the step.
- :func:`wrap_for_training` — the containers' auto-wrap policy
  (``DL4J_TPU_PREFETCH_WORKERS``, default 2; ``0`` restores the fully
  synchronous path; ``DL4J_TPU_PUT_AHEAD=0`` keeps prefetch but moves
  the transfer back into the step; ``DL4J_TPU_PREFETCH_QUEUE`` bounds
  the ready-batch window — default 2 with put-ahead, so at most two
  batches pin device memory (double buffering), ``2 × workers``
  otherwise).

Monitor series (docs/OBSERVABILITY.md; all ride ``OP_TELEMETRY`` into
``GET /fleet`` and fold into the ``pipeline`` block of ``GET /profile``):

- ``input_queue_depth`` gauge — ready batches buffered ahead of the
  consumer (0 sustained = ETL-bound, full = compute-bound: healthy).
- ``input_wait_seconds`` histogram — how long ``next()`` actually
  blocked (the residual ETL the pipeline failed to hide).
- ``input_bytes_total`` / ``input_batches_total`` counters — host bytes
  and batches fed through the pipeline.

Spans, on the worker threads (``monitor/tracer.py``; host time):
``input/transform`` (the user transform and the byte count) and
``input/put_ahead`` (handing the batch to the runtime for transfer).
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Optional

import numpy as np

from .dataset import DataSet, DataSetIterator, MultiDataSet
from .iterators import AsyncDataSetIterator
from ..monitor import get_registry, get_tracer
from ..monitor.lockwatch import make_condition, make_lock

log = logging.getLogger(__name__)

__all__ = ["PrefetchIterator", "PrefetchDataSetIterator",
           "wrap_for_training"]

#: consumer/worker poll granularity (seconds): every blocking wait in this
#: module is bounded by this and re-checks stop/liveness, so no thread can
#: park forever on a condition a dead peer will never signal
_POLL_S = 0.2



class _Raise:
    """A worker-side error travelling the reorder buffer in batch order:
    batches produced BEFORE the failure are still delivered, then the
    exception re-raises on the consumer thread at its true position."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class _Epoch:
    """One epoch's worth of pipeline state. Workers only ever touch the
    epoch object they were born with (same ownership rule as
    ``AsyncDataSetIterator._worker``), so a ``reset()`` mid-epoch cannot
    leak stale batches into the next epoch. The PULL lock lives on the
    iterator, not here — a stale worker still blocked inside
    ``next(source)`` after a timed-out join must keep excluding the next
    epoch's workers from the shared (non-thread-safe) base."""

    __slots__ = ("source", "cond", "buf", "next_seq", "emit_seq",
                 "end_seq", "exc", "ended", "pulling", "source_done",
                 "stop", "threads")

    def __init__(self, source):
        self.source = source
        self.cond = make_condition("_Epoch.cond")  # guards buf/emit_seq/end_seq
        self.buf = {}                       # seq -> item | _Raise
        self.next_seq = 0
        self.emit_seq = 0
        self.end_seq = None                 # first seq past the stream end
        self.exc = None                     # pull-side error (raised at end_seq)
        self.ended = False                  # no further pulls
        self.pulling = 0                    # concurrent mode: in-flight pulls
        self.source_done = False            # concurrent mode: saw exhaustion
        self.stop = threading.Event()
        self.threads = []


class PrefetchIterator:
    """Order-preserving multi-worker prefetch over any iterator.

    ``transform`` runs on the worker threads — that is the parallel part.
    The pull itself is serialized under a lock by default (python
    iterators are not thread-safe); ``concurrent_pull=True`` lets the N
    workers call ``next(base)`` concurrently — REQUIRED for a slow
    *source* (disk decode, network fetch) to actually parallelize, and
    only sound when the base iterator is safe to call from multiple
    threads (``DataSetIterator.concurrent_pull_supported()`` is the
    opt-in; sequence numbers are still assigned under the lock, so
    delivery order is the pull-start order). ``queue_size`` bounds how
    many batches may sit ready ahead of the consumer (plus up to
    ``workers`` in-flight transforms), so a fast producer cannot balloon
    host/device memory.
    """

    def __init__(self, base, workers: int = 2, queue_size: Optional[int] = None,
                 transform: Optional[Callable] = None,
                 concurrent_pull: bool = False, finalize: Optional[Callable] = None,
                 name: str = "prefetch"):
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        self._base = base
        self._workers = int(workers)
        self._qsize = int(queue_size) if queue_size else max(2, 2 * workers)
        self._transform = transform
        #: runs AFTER admission into the bounded window (still on the
        #: worker thread) — the seam for work whose RESULT must stay
        #: bounded, e.g. the device put: at most ``queue_size`` finalized
        #: batches exist at once, while cheap pre-finalize batches may
        #: additionally sit with parked workers
        self._finalize = finalize
        self._concurrent = bool(concurrent_pull)
        self._name = name
        # iterator-level, NOT per-epoch: a stale worker still blocked
        # inside next(source) after a timed-out join keeps excluding the
        # next epoch's workers from the shared non-thread-safe base
        self._pull_lock = make_lock("PrefetchIterator._pull_lock")
        self._ep: Optional[_Epoch] = None
        self._handles = None

    # ------------------------------------------------------------ metrics
    def _metric_handles(self):
        if self._handles is None:
            reg = get_registry()
            self._handles = (
                reg.gauge("input_queue_depth",
                          "prefetched batches buffered ahead of the "
                          "training loop"),
                reg.histogram("input_wait_seconds",
                              "blocking wait for the next batch in the "
                              "input pipeline (seconds)", unit="s"),
                reg.counter("input_batches_total",
                            "batches served by the input pipeline"),
            )
        return self._handles

    # ------------------------------------------------------------- workers
    def _mark_end(self, ep: _Epoch, seq: int, exc=None):
        """Record the stream end (or the position of a failure): the
        smallest ending seq wins, and the exception travelling with it (if
        any) re-raises after every earlier batch has been delivered."""
        with ep.cond:
            if ep.end_seq is None or ep.end_seq > seq:
                ep.end_seq = seq
                ep.exc = exc
            ep.cond.notify_all()

    def _pull(self, ep: _Epoch):
        """One pull: returns ``(seq, item)``, or None when the stream (or
        this worker's reason to continue) ended.

        Serial mode: ``next(source)`` and the seq assignment both happen
        under the pull lock — order is exact, the first failure ends the
        stream at its true position.

        Concurrent mode: pulls run in parallel (the base declared itself
        pull-thread-safe) and seqs are assigned in pull-COMPLETION order,
        so no seq can ever map to a lost item. Exhaustion is only final
        once every in-flight pull has resolved (``ep.pulling`` drains to
        0) — the worker that raced past a sibling's StopIteration with
        the true last item still delivers it."""
        if not self._concurrent:
            with self._pull_lock:
                if ep.ended or ep.stop.is_set():
                    return None
                seq = ep.next_seq
                try:
                    item = next(ep.source)
                except StopIteration:
                    ep.ended = True
                    self._mark_end(ep, seq)
                    return None
                except Exception as e:
                    # pull failure: deliver the batches already produced,
                    # then re-raise at this position
                    ep.ended = True
                    self._mark_end(ep, seq, e)
                    return None
                ep.next_seq = seq + 1
            return seq, item
        with ep.cond:
            if ep.ended or ep.source_done:
                return None
            ep.pulling += 1
        try:
            item = next(ep.source)
        except StopIteration:
            self._concurrent_pull_resolved(ep, done=True)
            return None
        except Exception as e:
            self._concurrent_pull_resolved(ep, done=True, exc=e)
            return None
        with ep.cond:
            seq = ep.next_seq
            ep.next_seq = seq + 1
        self._concurrent_pull_resolved(ep, done=False)
        return seq, item

    @staticmethod
    def _concurrent_pull_resolved(ep: _Epoch, done: bool, exc=None):
        with ep.cond:
            ep.pulling -= 1
            if done:
                ep.source_done = True
                if exc is not None and ep.exc is None:
                    ep.exc = exc
            if ep.source_done and ep.pulling == 0 and ep.end_seq is None:
                # last in-flight pull resolved: every assigned seq has an
                # item, so the end is exactly the seq count — no drops
                ep.end_seq = ep.next_seq
            ep.cond.notify_all()

    def _worker_loop(self, ep: _Epoch):
        depth_g = self._metric_handles()[0]
        while not ep.stop.is_set():
            pulled = self._pull(ep)
            if pulled is None:
                return
            seq, item = pulled
            try:
                out = item if self._transform is None else self._transform(item)
            except Exception as e:
                out = _Raise(e)
                with ep.cond:
                    ep.ended = True     # no point producing past the error
                # the error IS the stream end at seq+1: the _Raise item
                # delivers (and re-raises) in order, later nexts stop
                self._mark_end(ep, seq + 1)
            # bounded put-ahead: wait for admission into the window, THEN
            # finalize (the device put) — at most queue_size finalized
            # batches hold device memory at once
            with ep.cond:
                while (not ep.stop.is_set()
                       and seq - ep.emit_seq >= self._qsize
                       and (ep.end_seq is None or seq < ep.end_seq)):
                    ep.cond.wait(_POLL_S)
                if ep.stop.is_set():
                    return
                if ep.end_seq is not None and seq >= ep.end_seq:
                    continue   # past the recorded end — drop, never deliver
            if self._finalize is not None and not isinstance(out, _Raise):
                try:
                    out = self._finalize(out)
                except Exception as e:
                    out = _Raise(e)
                    with ep.cond:
                        ep.ended = True
                    self._mark_end(ep, seq + 1)
            with ep.cond:
                if ep.stop.is_set():
                    return
                ep.buf[seq] = out
                depth_g.set(len(ep.buf))
                ep.cond.notify_all()

    # ------------------------------------------------------------ protocol
    def __iter__(self):
        self.reset()
        return self

    def reset(self):
        stale = self._stop_epoch()
        # base reset under the pull lock: a stale SERIAL-mode worker still
        # blocked inside next(source) (its join timed out) holds this lock,
        # so it cannot race the rewind. Bounded acquire: a source stuck
        # forever degrades to a loud warning, not a hang. Concurrent-mode
        # pulls run lock-free by contract — a stale one surviving the join
        # can still consume a post-rewind batch, so that degraded state is
        # warned about explicitly below instead of silently losing data.
        if self._pull_lock.acquire(timeout=5):
            try:
                source = iter(self._base)
            finally:
                self._pull_lock.release()
        else:
            log.warning(
                "%s: a previous epoch's worker is still blocked inside "
                "next(base) after 5s; resetting the base anyway", self._name)
            source = iter(self._base)
        if stale:
            log.warning(
                "%s: %d worker(s) from the previous epoch outlived their "
                "join; their in-flight pull may consume (and discard) a "
                "batch from the reset stream", self._name, stale)
        ep = _Epoch(source)
        for i in range(self._workers):
            t = threading.Thread(target=self._worker_loop, args=(ep,),
                                 name=f"{self._name}-{i}", daemon=True)
            ep.threads.append(t)
            t.start()
        self._ep = ep

    def _stop_epoch(self) -> int:
        """Stop and join the current epoch's workers; returns how many
        survived the bounded join (0 on the normal path)."""
        ep, self._ep = self._ep, None
        if ep is None:
            return 0
        ep.stop.set()
        with ep.cond:
            ep.cond.notify_all()
        for t in ep.threads:
            t.join(timeout=5)
        return sum(1 for t in ep.threads if t.is_alive())

    def shutdown(self):
        """Stop and join the current epoch's workers (reset-mid-epoch /
        end-of-fit cleanliness: no leaked threads)."""
        self._stop_epoch()

    def __next__(self):
        if self._ep is None:
            self.reset()
        ep = self._ep
        depth_g, wait_h, batches_c = self._metric_handles()
        t0 = time.perf_counter()
        with ep.cond:
            while True:
                if ep.emit_seq in ep.buf:
                    item = ep.buf.pop(ep.emit_seq)
                    ep.emit_seq += 1
                    depth_g.set(len(ep.buf))
                    ep.cond.notify_all()     # space freed for producers
                    break
                if ep.end_seq is not None and ep.emit_seq >= ep.end_seq:
                    if ep.exc is not None:
                        raise ep.exc
                    raise StopIteration
                if not any(t.is_alive() for t in ep.threads):
                    # liveness: every worker died without delivering the
                    # batch we are waiting for — never hang, raise the
                    # cause (or a loud stand-in for a hard thread death)
                    if ep.exc is not None:
                        raise ep.exc
                    raise RuntimeError(
                        f"{self._name}: all {self._workers} prefetch "
                        f"workers died without delivering batch "
                        f"{ep.emit_seq} or an end-of-stream marker")
                ep.cond.wait(_POLL_S)
        wait_h.observe(time.perf_counter() - t0)
        if isinstance(item, _Raise):
            raise item.exc
        batches_c.inc()
        return item


# ------------------------------------------------------- device-put-ahead
def _host_nbytes(ds) -> int:
    """Host bytes of a DataSet/MultiDataSet's arrays (pre-transfer)."""
    def nb(a):
        return int(getattr(a, "nbytes", 0) or 0) if a is not None else 0
    if isinstance(ds, MultiDataSet):
        total = sum(nb(a) for a in ds.features) + sum(nb(a) for a in ds.labels)
        for masks in (ds.features_masks, ds.labels_masks):
            if masks is not None:
                total += sum(nb(a) for a in masks)
        return total
    if isinstance(ds, DataSet):
        return (nb(ds.features) + nb(ds.labels) + nb(ds.features_mask)
                + nb(ds.labels_mask))
    return 0


def _device_view(ds, put):
    """A shallow DataSet/MultiDataSet whose arrays are device-resident.
    Built via ``__new__`` — the constructors call ``np.asarray``, which
    would pull a ``jax.Array`` straight back to the host. The caller's
    DataSet is never mutated, so the device buffers die with the view
    (one step), not with the user's dataset."""
    if isinstance(ds, MultiDataSet):
        view = MultiDataSet.__new__(MultiDataSet)
        view.features = [put(a) for a in ds.features]
        view.labels = [put(a) for a in ds.labels]
        view.features_masks = (None if ds.features_masks is None
                               else [put(a) for a in ds.features_masks])
        view.labels_masks = (None if ds.labels_masks is None
                             else [put(a) for a in ds.labels_masks])
        return view
    view = DataSet.__new__(DataSet)
    view.features = put(ds.features)
    view.labels = put(ds.labels)
    view.features_mask = put(ds.features_mask)
    view.labels_mask = put(ds.labels_mask)
    view.synthetic = getattr(ds, "synthetic", False)
    return view


class PrefetchDataSetIterator(PrefetchIterator, DataSetIterator):
    """Multi-worker prefetch over a ``DataSetIterator`` with optional
    device-put-ahead.

    ``device_put=True`` transfers each batch to the device ON THE WORKER
    THREAD, so the training loop receives device-resident arrays and its
    ``jnp.asarray`` is an identity — H2D overlaps the previous step's
    compute (double buffering, bounded by ``queue_size``).

    ``sharding`` (a ``jax.sharding.Sharding``) places batches under the
    model's input sharding — the seam for feeding
    ``parallel.sharding.data_parallel_step`` style mesh steps without a
    host re-placement inside the step.

    ``cache_device=True`` (``CacheMode.DEVICE`` models): instead of a
    fresh transfer per epoch, the worker warms
    :meth:`DataSet.device_arrays` on the BASE dataset, preserving the
    one-transfer-per-dataset cache semantics across fits.

    ``transform`` (host-side, runs before the device put) is where
    decode/augment/padding work parallelizes across workers.
    """

    def __init__(self, base: DataSetIterator, workers: int = 2,
                 queue_size: Optional[int] = None, device_put: bool = False,
                 sharding=None, cache_device: bool = False,
                 transform: Optional[Callable] = None,
                 concurrent_pull: Optional[bool] = None):
        self._user_transform = transform
        self._device_put = bool(device_put) or sharding is not None
        self._sharding = sharding
        self._cache_device = bool(cache_device)
        if concurrent_pull is None:
            # the base iterator's own declaration (DataSetIterator
            # protocol; default False — python iterators are not
            # thread-safe unless they say so)
            concurrent_pull = bool(getattr(base, "concurrent_pull_supported",
                                           lambda: False)())
        self._bytes_counter = get_registry().counter(
            "input_bytes_total",
            "host bytes fed through the input pipeline")
        # the device put is the FINALIZE stage: it runs only after
        # admission into the bounded window, so at most queue_size batches
        # hold device memory at once (workers parked for admission hold
        # cheap host batches, not HBM)
        super().__init__(base, workers=workers, queue_size=queue_size,
                         transform=self._prepare,
                         finalize=self._put_ahead if self._device_put
                         else None,
                         concurrent_pull=concurrent_pull,
                         name="input-prefetch")

    def _put(self, x):
        if x is None:
            return None
        import jax
        if self._sharding is not None:
            return jax.device_put(x, self._sharding)
        import jax.numpy as jnp
        return jnp.asarray(x)

    def _prepare(self, ds):
        with get_tracer().span("input/transform", cat="input"):
            if self._user_transform is not None:
                ds = self._user_transform(ds)
            self._bytes_counter.inc(_host_nbytes(ds))
            return ds

    def _put_ahead(self, ds):
        # host time to hand the batch to the runtime, on the worker thread;
        # the transfer itself is the runtime's and shows in the trace
        with get_tracer().span("input/put_ahead", cat="input"):
            if self._cache_device and hasattr(ds, "device_arrays"):
                # warm the base dataset's CacheMode.DEVICE cache ahead of
                # the step; the fit loop's own device_arrays() call then
                # hits it
                ds.device_arrays()
                return ds
            if isinstance(ds, (DataSet, MultiDataSet)):
                return _device_view(ds, self._put)
            return ds

    def batch(self):
        return self._base.batch()

    def async_supported(self):
        return False    # already asynchronous — never wrap again


def wrap_for_training(it, cache_device: bool = False):
    """The containers' fit-loop auto-wrap: returns ``(iterator, owned)``.
    ``owned`` is True when a new pipeline was created here — the caller
    must ``shutdown()`` it when fit ends (normally or by halt) so no
    worker threads outlive the loop.

    Dials (read per call, so benchmarks can A/B without re-imports):
    ``DL4J_TPU_PREFETCH_WORKERS`` (default 2; ``0`` → no wrap, fully
    synchronous), ``DL4J_TPU_PREFETCH_QUEUE`` (default 2 with put-ahead —
    true double buffering, so at most 2 batches pin device memory, the
    same residency the old transfer-in-step path peaked at; default
    ``2 × workers`` host batches otherwise), ``DL4J_TPU_PUT_AHEAD``
    (default on).
    """
    if not isinstance(it, DataSetIterator):
        return it, False
    if isinstance(it, (AsyncDataSetIterator, PrefetchDataSetIterator)):
        return it, False
    if not it.async_supported():
        return it, False
    try:
        workers = int(os.environ.get("DL4J_TPU_PREFETCH_WORKERS", "2"))
    except ValueError:
        workers = 2
    if workers <= 0:
        return it, False
    put_ahead = os.environ.get("DL4J_TPU_PUT_AHEAD", "1") \
        not in ("0", "false", "")
    qs = os.environ.get("DL4J_TPU_PREFETCH_QUEUE", "")
    if qs.isdigit() and int(qs) > 0:
        queue_size = int(qs)
    else:
        queue_size = 2 if put_ahead else None
    return PrefetchDataSetIterator(it, workers=workers,
                                   queue_size=queue_size,
                                   device_put=put_ahead,
                                   cache_device=cache_device), True

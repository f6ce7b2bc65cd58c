"""The one traffic generator: an iterator that hands the cell's batches to
``fit`` until a deadline or a count, and keeps the host from running far
ahead of the device.

Dispatch is asynchronous, so without a brake the host would queue steps for
the whole window and the barrier that closes it would land long after
``--seconds``. The brake is a *lagged* barrier inside ``__next__``: before
handing out batch ``n`` it waits for the loss the network held when batch
``n - run_ahead`` was handed out, never the newest one, so the device always
has work queued behind the step being waited for. No listener is involved
and nothing is fetched to the host. ``__next__`` is called from a prefetch
worker thread when the program wraps the iterator, hence the lock.
"""
from __future__ import annotations

import collections
import threading
import time

import jax

from deeplearning4j_tpu.datasets.dataset import DataSetIterator


class Feed(DataSetIterator):
    def __init__(self, pool, newest_loss, run_ahead, group=1):
        """``pool``: the DataSets to cycle through. ``newest_loss``: returns
        the handle of the loss of the last step dispatched (``net.score_``).
        ``run_ahead``: batches the host may lead the device by. ``group``:
        batches the program merges into one step (one per chip under
        ``ParallelWrapper``); the stream ends on a whole group only, so that
        no smaller last step brings a shape of its own."""
        self._pool = list(pool)
        self._newest_loss = newest_loss
        self._run_ahead = int(run_ahead)
        self._group = int(group)
        self._lock = threading.Lock()
        self._handles = collections.deque()
        self._deadline = None
        self._limit = None
        self._armed_at = 0
        self.handed = 0              # batches handed out since construction
        self.barrier_seconds = 0.0   # time spent waiting in the brake

    def arm(self, seconds=None, batches=None):
        """Hand out batches for ``seconds`` from now, or ``batches`` of them
        (whichever is given), then stop."""
        with self._lock:
            self._deadline = (None if seconds is None
                              else time.perf_counter() + float(seconds))
            self._limit = None if batches is None else int(batches)
            self._armed_at = self.handed
            self._handles.clear()

    def reset(self):
        pass                         # a stream, not an epoch: arm() restarts it

    def batch(self):
        return self._pool[0].num_examples()

    def __next__(self):
        with jax.profiler.TraceAnnotation("bench/input_next"):
            with self._lock:
                done = self.handed - self._armed_at
                if self._limit is not None and done >= self._limit:
                    raise StopIteration
                if (self._deadline is not None and done % self._group == 0
                        and time.perf_counter() >= self._deadline):
                    raise StopIteration
                n = self.handed
                self.handed += 1
                self._handles.append(self._newest_loss())
                lagged = (self._handles.popleft()
                          if len(self._handles) > self._run_ahead else None)
            if lagged is not None:
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench/run_ahead_barrier"):
                    jax.block_until_ready(lagged)
                with self._lock:
                    self.barrier_seconds += time.perf_counter() - t0
            return self._pool[n % len(self._pool)]

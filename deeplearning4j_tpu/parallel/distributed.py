"""Distributed training: TrainingMaster SPI + multi-host collective design.

TPU-native equivalent of the reference's Spark layer (SURVEY.md §2.4):
``TrainingMaster`` SPI (``spark/dl4j-spark/.../spark/api/TrainingMaster.java:28``),
``ParameterAveragingTrainingMaster`` (sync DP, ``impl/paramavg/...:308``),
``SharedTrainingMaster`` (async quantized gradient sharing over Aeron,
``dl4j-spark-parameterserver/.../SharedTrainingMaster.java:55``) and the
user-facing ``SparkDl4jMultiLayer`` facade (``impl/multilayer/...:214``).

Architecture shift: the reference's control plane (driver serializes the model
to executors each averaging round; Aeron UDP data plane for encoded updates)
collapses into JAX's multi-controller SPMD model — every host runs the SAME
program, ``jax.distributed.initialize`` forms the cluster, the global mesh
spans hosts, and the gradient ``psum`` rides ICI within a slice and DCN across
slices. There is no parameter broadcast step: compiled-once params live
sharded/replicated on device. The TrainingMaster seam is retained so user code
written against the reference's API maps 1:1.

Multi-host bring-up (real cluster):
    jax.distributed.initialize(coordinator_address, num_processes, process_id)
    master = ParameterAveragingTrainingMaster(batch_size_per_worker=...,
                                              averaging_frequency=1)
    DistributedMultiLayerNetwork(net, master).fit(iterator)
Single-process testing uses the same code on a virtual device mesh.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax

from .sharding import DATA_AXIS, make_mesh
from ..monitor.jitwatch import monitored_jit
from .wrapper import ParallelWrapper, TrainingMode
from .accumulation import EncodedGradientsAccumulator


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None,
                           heartbeat_timeout_s: Optional[int] = None,
                           initialization_timeout_s: Optional[int] = None):
    """Form the multi-host cluster (replaces the reference's
    ``VoidParameterServer.init`` Aeron mesh handshake,
    ``SharedTrainingMaster.java:469``). No-op when single-process.

    On the CPU backend (tests / virtual clusters) cross-process collectives
    ride jax's default gloo transport.

    FAILURE SEMANTICS: the cluster is fate-shared, like the reference's
    Spark stage — there is no in-framework elastic recovery (SURVEY.md §5:
    the reference's only failure handling is RDD-lineage retry OUTSIDE the
    training step). What the framework guarantees is DETECTION, not
    resurrection: when a peer dies, the coordination service notices within
    ``heartbeat_timeout_s`` (the barrier/collective path raises a
    distributed-runtime error naming the dead/timed-out peer) and survivors
    FAIL CLEANLY instead of hanging — catch the error, checkpoint if
    appropriate, and let the job scheduler relaunch the whole cluster
    (resume via ``ModelSerializer`` exact-restore). Lower
    ``heartbeat_timeout_s`` (default 100 s upstream) to shrink
    detection latency; see ``tests/test_multiprocess.py``
    ``test_killed_worker_fails_cleanly`` for the pinned behavior."""
    if coordinator_address is None:
        return False
    kw = {}
    if heartbeat_timeout_s is not None:
        kw["heartbeat_timeout_seconds"] = int(heartbeat_timeout_s)
    if initialization_timeout_s is not None:
        kw["initialization_timeout"] = int(initialization_timeout_s)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kw)
    return True


def is_chief() -> bool:
    """True on the coordinator process (host 0) — checkpointing, listener
    output and UI posting are gated on this so N hosts don't write N copies
    (the reference's Spark driver/executor role split)."""
    return jax.process_index() == 0


class ProcessLocalIterator:
    """Round-robins a shared data stream across processes: process ``p`` of
    ``P`` keeps batches ``p, p+P, p+2P, ...`` — the multi-controller
    equivalent of the reference's per-executor RDD partition feeding
    (``VirtualDataSetIterator``; fixes the naive every-host-feeds-everything
    double-feed). The stream is truncated to a multiple of ``P`` batches so
    every process sees the same number of steps (collective schedules must
    match)."""

    def __init__(self, iterator, process_index: Optional[int] = None,
                 process_count: Optional[int] = None,
                 drop_remainder: bool = True):
        self.it = iterator
        self.p = jax.process_index() if process_index is None else process_index
        self.P = jax.process_count() if process_count is None else process_count
        # training needs equal step counts on every process (collective
        # schedules must match) → drop the final partial window; evaluation/
        # scoring has no per-batch collective, so the tail is kept and
        # assigned to the low-indexed processes (full-stream metrics)
        self.drop_remainder = drop_remainder

    def __iter__(self):
        # rolling window of P batches — never materializes the stream
        chunk = []
        for b in self.it:
            chunk.append(b)
            if len(chunk) == self.P:
                yield chunk[self.p]
                chunk = []
        if chunk and not self.drop_remainder and self.p < len(chunk):
            yield chunk[self.p]

    def reset(self):
        if hasattr(self.it, "reset"):
            self.it.reset()

    def async_supported(self):
        return False


class TrainingMaster:
    """SPI (reference ``TrainingMaster.java:28``): how distributed fitting is
    executed. Implementations configure mesh + step strategy.

    Implementations: :class:`ParameterAveragingTrainingMaster` (fused sync
    all-reduce), :class:`SharedTrainingMaster` (async quantized sharing —
    full-mesh ``UpdateChannel`` across hosts), and
    ``deeplearning4j_tpu.paramserver.ParameterServerTrainingMaster``
    (server-mediated async push/pull with bounded staleness — the mode where
    a worker can die and rejoin without taking down training)."""

    def execute_training(self, net, iterator):
        raise NotImplementedError

    executeTraining = execute_training


class ParameterAveragingTrainingMaster(TrainingMaster):
    """Sync DP (reference ``ParameterAveragingTrainingMaster``): averaging
    every iteration == fused gradient all-reduce; ``averaging_frequency > 1``
    == local SGD with periodic param+updater averaging. ``aggregation_depth``
    (the reference's tree-aggregation knob) is obsolete — XLA picks the
    reduction topology on ICI/DCN."""

    class Builder:
        def __init__(self, batch_size_per_worker: int = 32):
            self._batch = batch_size_per_worker
            self._freq = 1
            self._workers = None

        def averaging_frequency(self, n):
            self._freq = int(n)
            return self

        averagingFrequency = averaging_frequency

        def batch_size_per_worker(self, n):
            self._batch = int(n)
            return self

        batchSizePerWorker = batch_size_per_worker

        def workers(self, n):
            self._workers = int(n)
            return self

        def build(self):
            return ParameterAveragingTrainingMaster(
                batch_size_per_worker=self._batch,
                averaging_frequency=self._freq, workers=self._workers)

    def __init__(self, batch_size_per_worker: int = 32,
                 averaging_frequency: int = 1, workers: Optional[int] = None):
        self.batch_size_per_worker = batch_size_per_worker
        self.averaging_frequency = averaging_frequency
        self.workers = workers

    def execute_training(self, net, iterator):
        pw = (ParallelWrapper.Builder(net)
              .workers(self.workers or len(jax.devices()))
              .averaging_frequency(self.averaging_frequency)
              .training_mode(TrainingMode.AVERAGING)
              .build())
        pw.fit(iterator)
        return pw


class SharedTrainingMaster(TrainingMaster):
    """Async quantized-update sharing (reference ``SharedTrainingMaster``):
    within a slice this degenerates to the same fused all-reduce (ICI makes
    compression pointless — SURVEY.md §2.4 note); the threshold/accumulator
    knobs are kept and drive the DCN codec when updates cross slices."""

    class Builder:
        def __init__(self, threshold: float = 1e-3):
            self._threshold = threshold
            self._batch = 32
            self._workers = None

        def threshold(self, t):
            self._threshold = float(t)
            return self

        def batch_size_per_worker(self, n):
            self._batch = int(n)
            return self

        batchSizePerWorker = batch_size_per_worker

        def workers(self, n):
            self._workers = int(n)
            return self

        def build(self):
            return SharedTrainingMaster(threshold=self._threshold,
                                        batch_size_per_worker=self._batch,
                                        workers=self._workers)

    def __init__(self, threshold: float = 1e-3,
                 batch_size_per_worker: int = 32,
                 workers: Optional[int] = None):
        self.threshold = threshold
        self.batch_size_per_worker = batch_size_per_worker
        self.workers = workers
        self.accumulator = EncodedGradientsAccumulator(
            initial_threshold=threshold)

    def execute_training(self, net, iterator):
        pw = (ParallelWrapper.Builder(net)
              .workers(self.workers or len(jax.devices()))
              .training_mode(TrainingMode.SHARED_GRADIENTS)
              .gradients_accumulator(self.accumulator)
              .build())
        pw.fit(iterator)
        return pw


class SharedGradientsClusterTrainer:
    """Cross-host SHARED_GRADIENTS training over a real wire (reference
    ``SharedTrainingWrapper.java:160-244``: each executor encodes its local
    update, relays it to peers over Aeron, and applies everyone's decoded
    updates). Here the wire is ``parallel/transport.py``'s TCP mesh carrying
    the flat threshold-encoded frames; the *encoded* bytes are what cross the
    process boundary. All replicas apply the identical rank-ordered sum of
    decoded updates, so parameters stay bit-identical across hosts while the
    wire carries a fraction of the dense update size.

    Unlike ``ParameterAveragingTrainingMaster`` (a single jitted psum), hosts
    here run independent jitted steps — the pattern for training across
    slices where a fused collective is unavailable or DCN bandwidth makes
    dense exchange uneconomical.
    """

    def __init__(self, net, channel, accumulator: Optional[
            EncodedGradientsAccumulator] = None):
        import jax.numpy as jnp
        self.net = net
        self.channel = channel
        self.accumulator = accumulator or EncodedGradientsAccumulator()
        self._update_step = monitored_jit(net._raw_update_step(),
                                          name="distributed/update_step",
                                          donate_argnums=(2,))

        def apply_fn(params, update):
            return jax.tree_util.tree_map(
                lambda p, u: p - u.astype(p.dtype), params, update)

        self._apply_step = monitored_jit(apply_fn,
                                         name="distributed/apply_step",
                                         donate_argnums=(0,))
        self.wire_bytes_sent = 0
        self.dense_bytes_equiv = 0

    def fit(self, iterator, epochs: int = 1):
        import jax.numpy as jnp
        # function-level import: paramserver.training imports this module,
        # so a top-level import here would be circular
        from ..paramserver.overlap import async_device_get
        net = self.net
        acc = self.accumulator
        for _ in range(epochs):
            for ds in iterator:
                f = jnp.asarray(ds.features)
                l = jnp.asarray(ds.labels)
                itc = jnp.asarray(net.iteration_count, jnp.int32)
                update, net.states, net.updater_state, loss = \
                    self._update_step(net.params, net.states,
                                      net.updater_state, itc,
                                      net._next_rng(), f, l, None, None)
                # overlapped d2h (paramserver/overlap.py): every leaf's
                # transfer starts before the first gather blocks — the
                # PERF001 shape (blocking tree_map(np.asarray) in a hot
                # loop) removed the same way the paramserver master's was
                update = async_device_get(update)
                decoded_own = acc.store_update(update)
                frame = acc.serialize_last()
                self.wire_bytes_sent += len(frame) * (self.channel.P - 1)
                self.dense_bytes_equiv += sum(
                    np.asarray(u).nbytes for u in
                    jax.tree_util.tree_leaves(update)) * (self.channel.P - 1)
                peer_frames = self.channel.exchange(frame)
                # rank-ordered sum → identical float addition order on every
                # host → bit-identical replicas
                contributions = {self.channel.p: decoded_own}
                peers = [q for q in range(self.channel.P)
                         if q != self.channel.p]
                for q, fr in zip(peers, peer_frames):
                    contributions[q] = acc.decode_payload(fr)
                total = None
                for q in sorted(contributions):
                    c = contributions[q]
                    total = c if total is None else jax.tree_util.tree_map(
                        np.add, total, c)
                net.params = self._apply_step(
                    net.params, jax.tree_util.tree_map(jnp.asarray, total))
                net.score_ = loss
                net.iteration_count += 1
                for lst in net.listeners:
                    lst.iteration_done(net, net.iteration_count - 1,
                                       float(loss))
        return net


class DistributedMultiLayerNetwork:
    """User-facing facade (reference ``SparkDl4jMultiLayer``:
    ``fit(JavaRDD<DataSet>)`` :214 → ``trainingMaster.executeTraining``)."""

    def __init__(self, net, training_master: TrainingMaster,
                 checkpoint_path: Optional[str] = None):
        self.net = net
        self.training_master = training_master
        self.checkpoint_path = checkpoint_path

    def fit(self, iterator, epochs: int = 1):
        multi = jax.process_count() > 1
        if multi:
            # each process consumes only its round-robin share of the stream;
            # the wrapper assembles the global batch from the process locals
            iterator = ProcessLocalIterator(iterator)
            if not is_chief():
                # host-0 gating: listeners fire once per cluster, not per host
                saved_listeners, self.net.listeners = self.net.listeners, []
        try:
            for _ in range(epochs):
                self.training_master.execute_training(self.net, iterator)
        finally:
            if multi and not is_chief():
                self.net.listeners = saved_listeners
        if self.checkpoint_path and is_chief():
            from ..utils.model_serializer import ModelSerializer
            ModelSerializer.write_model(self.net, self.checkpoint_path)
        return self.net

    def evaluate(self, iterator):
        """Distributed evaluation (reference
        ``spark/impl/multilayer/evaluation/IEvaluateFlatMapFunction.java`` +
        ``IEvaluationReduceFunction.java``): each process evaluates only its
        round-robin shard of the stream, partial Evaluations are allgathered
        and MERGED, and every process returns the identical cluster-wide
        result."""
        import jax

        if jax.process_count() <= 1:
            return self.net.evaluate(iterator)
        local = self.net.evaluate(
            ProcessLocalIterator(iterator, drop_remainder=False))
        merged = None
        for part in allgather_objects(local):
            merged = part if merged is None else merged.merge(part)
        return merged

    def calculate_score(self, iterator, average: bool = True):
        """Reference ``calculateScore`` :332."""
        total, n = 0.0, 0
        for ds in iterator:
            b = ds.num_examples()
            total += self.net.score(ds) * b
            n += b
        return total / n if (average and n) else total

    calculateScore = calculate_score


SparkDl4jMultiLayer = DistributedMultiLayerNetwork  # reference-name alias


class DistributedComputationGraph(DistributedMultiLayerNetwork):
    """Reference ``SparkComputationGraph`` counterpart."""


SparkComputationGraph = DistributedComputationGraph


# -------------------------------------------------- cluster-wide reductions
def allgather_objects(obj) -> list:
    """Allgather arbitrary picklable host objects across processes (the
    reduce transport for distributed evaluation/scoring). Single-process:
    identity. Multi-process: length-prefixed pickle bytes through
    ``jax.experimental.multihost_utils.process_allgather`` (two fixed-shape
    collectives: max-length agreement, then padded payloads)."""
    import pickle

    if jax.process_count() <= 1:
        return [obj]
    from jax.experimental import multihost_utils

    data = np.frombuffer(pickle.dumps(obj), np.uint8)
    sizes = np.asarray(multihost_utils.process_allgather(
        np.asarray([data.size], np.int64))).reshape(-1)
    m = int(sizes.max())
    padded = np.zeros(m, np.uint8)
    padded[:data.size] = data
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    gathered = gathered.reshape(jax.process_count(), m)
    return [pickle.loads(gathered[i, :int(sizes[i])].tobytes())
            for i in range(jax.process_count())]


class DistributedDataSetLossCalculator:
    """Cluster-wide validation loss (reference
    ``spark/earlystopping/SparkDataSetLossCalculator.java``): each process
    sums loss over its shard, partial (total, n) pairs are allgathered, and
    every process computes the identical global average."""

    def __init__(self, iterator, average: bool = True):
        self.iterator = iterator
        self.average = average

    def minimize_score(self) -> bool:
        return True

    def calculate_score(self, net) -> float:
        it = (ProcessLocalIterator(self.iterator, drop_remainder=False)
              if jax.process_count() > 1 else self.iterator)
        total, n = 0.0, 0
        for ds in it:
            b = ds.num_examples()
            total += float(net.score(ds)) * b
            n += b
        parts = allgather_objects((total, n))
        total = sum(t for t, _ in parts)
        n = sum(c for _, c in parts)
        return total / n if (self.average and n) else total

    calculateScore = calculate_score


from ..earlystopping import EarlyStoppingTrainer, TerminationReason  # noqa: E402


class DistributedEarlyStoppingTrainer(EarlyStoppingTrainer):
    """Early stopping over the distributed facade (reference
    ``spark/earlystopping/SparkEarlyStoppingTrainer.java``): each epoch runs
    through the facade's TrainingMaster (process-sharded data, collective
    sync), and scoring should use :class:`DistributedDataSetLossCalculator`
    so conditions fire identically on every process."""

    def __init__(self, config, dist_net: DistributedMultiLayerNetwork,
                 train_iterator):
        super().__init__(config, dist_net.net, train_iterator)
        self.dist_net = dist_net

    def _train_one_epoch(self, c, reason, details):
        # the wrapper's fit already advances net.epoch_count; the base
        # trainer loop increments it too, so restore to avoid double-count
        before = self.net.epoch_count
        self.dist_net.fit(self.iterator, epochs=1)
        self.net.epoch_count = before
        last = float(self.net.score_)
        for cond in c.iteration_termination_conditions:
            if cond.terminate(last):
                reason = TerminationReason.IterationTerminationCondition
                details = f"{type(cond).__name__} at score {last}"
                return True, reason, details
        return False, reason, details


SparkEarlyStoppingTrainer = DistributedEarlyStoppingTrainer
SparkDataSetLossCalculator = DistributedDataSetLossCalculator

"""ParallelWrapper: single-host multi-device data-parallel training.

TPU-native equivalent of reference
``deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java`` (898 LoC;
modes enum :59-74, fit :468, dispatch :497-516, averaging barrier :551-562).

Mapping (SURVEY.md §7 Phase 3):
 - ``TrainingMode.AVERAGING`` with ``averaging_frequency=1`` and
   ``TrainingMode.SHARED_GRADIENTS`` → ONE jitted SPMD step whose gradient
   ``psum`` over ICI is the averaging/broadcast. No host barrier, no replica
   copies: the XLA partitioner emits the collective.
 - ``averaging_frequency=N > 1`` → local SGD: a ``shard_map`` step where every
   device advances its own replica for N micro-steps on its private batch
   stream, then parameters AND updater state are ``pmean``-averaged — exactly
   the reference's periodic averaging barrier (``averageUpdatersState`` :339),
   fused into one XLA computation instead of host thread coordination.

The reference's worker threads, MagicQueue device bucketing and AffinityManager
pinning all disappear: batches go to devices by sharding annotation.
"""
from __future__ import annotations

import logging
import os
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
from ..monitor import get_tracer, spanned
from ..monitor.jitwatch import monitored_jit

from .mesh import MODEL_AXIS, MeshSpec, record_step, require_axes
from .sharding import (DATA_AXIS, replicated, batch_sharded,
                       shard_batch, put_replicated, data_parallel_step,
                       data_parallel_tbptt_step,
                       data_parallel_tbptt_update_step, pvary,
                       composed_specs, put_sharded_tree)
from .accumulation import GradientsAccumulator, EncodedGradientsAccumulator
from ..nn.conf import BackpropType, CacheMode
from ..datasets.dataset import (DataSet, MultiDataSet, DataSetIterator,
                                ListDataSetIterator)
from ..datasets.iterators import AsyncDataSetIterator
from ..datasets.prefetch import PrefetchDataSetIterator

log = logging.getLogger(__name__)
_tm = jax.tree_util.tree_map


class TrainingMode:
    """Reference ``ParallelWrapper.TrainingMode`` (:59-74)."""
    AVERAGING = "averaging"
    SHARED_GRADIENTS = "shared_gradients"
    CUSTOM = "custom"


class ParallelWrapper:
    """Builder-style facade over the SPMD data-parallel step."""

    class Builder:
        def __init__(self, net):
            self._net = net
            self._workers = None
            self._prefetch = 2
            self._prefetch_workers = 2
            self._freq = 1
            self._mode = TrainingMode.AVERAGING
            self._report_after_avg = True
            self._accumulator = None
            self._mesh = None
            self._ws = False
            self._fsdp = False
            self._host_dtype = None
            self._tp = None
            self._tp_rules = None

        def workers(self, n):
            self._workers = int(n)
            return self

        def prefetch_buffer(self, n):
            self._prefetch = int(n)
            return self

        prefetchBuffer = prefetch_buffer

        def prefetch_workers(self, n):
            """Host ETL worker threads feeding the batch grouper
            (``datasets/prefetch.py`` multi-worker pipeline; default 2).
            The device placement itself stays with ``_global_batch`` —
            it shards over the wrapper's mesh — so the workers
            parallelize the iterator/decode/augment side only."""
            self._prefetch_workers = int(n)
            return self

        prefetchWorkers = prefetch_workers

        def averaging_frequency(self, n):
            self._freq = int(n)
            return self

        averagingFrequency = averaging_frequency

        def training_mode(self, mode):
            self._mode = mode
            return self

        trainingMode = training_mode

        def report_score_after_averaging(self, flag=True):
            self._report_after_avg = bool(flag)
            return self

        reportScoreAfterAveraging = report_score_after_averaging

        def gradients_accumulator(self, acc: GradientsAccumulator):
            self._accumulator = acc
            return self

        gradientsAccumulator = gradients_accumulator

        def mesh(self, mesh: Mesh):
            self._mesh = mesh
            return self

        def tensor_parallel(self, n: int = 2, rules=None):
            """Compose tensor parallelism INTO the data-parallel step on a
            2-D ``data × model`` mesh (parallel/mesh.py substrate): the
            wrapper keeps driving the batch over the ``data`` axis while
            ``rules`` ({param-path regex: PartitionSpec}, default
            :func:`~deeplearning4j_tpu.parallel.tensor.megatron_rules`)
            shard the params over a ``model`` axis of extent ``n`` in the
            SAME jitted step. The data extent auto-factorizes to
            ``devices / n``. Stacks with :meth:`weight_update_sharding` /
            :meth:`fsdp` — ZeRO takes the dims TP left free, over the
            ``data`` axis of the composed mesh. Supported for
            ``TrainingMode.AVERAGING`` with ``averaging_frequency=1``
            (including TBPTT); other modes reject loudly."""
            self._tp = int(n)
            self._tp_rules = rules
            return self

        tensorParallel = tensor_parallel

        def weight_update_sharding(self, flag=True):
            """Shard the OPTIMIZER STATE over the data axis instead of
            replicating it (Xu et al. 2020, arXiv:2004.13336; ZeRO-1 as
            sharding annotations) — numerically identical sync DP with ~N×
            less optimizer memory per device. Supported for
            ``TrainingMode.AVERAGING`` with ``averaging_frequency=1``
            (including its TBPTT variant); other modes reject loudly."""
            self._ws = bool(flag)
            return self

        weightUpdateSharding = weight_update_sharding

        def fsdp(self, flag=True):
            """ZeRO-3/FSDP-style sharded STORAGE: parameters AND optimizer
            state shard over the data axis (leaves with a divisible dim;
            the rest replicate). The SPMD partitioner inserts the
            all-gathers at the points of use and reduce-scatters gradients
            into the sharded update — numerically identical to replicated
            DP with ~N× less param+optimizer memory per device. Implies
            :meth:`weight_update_sharding`; same AVERAGING freq=1
            constraint. Non-step uses of the net (``output()``/``score()``/
            serialization) gather transparently."""
            self._fsdp = bool(flag)
            # the ws implication lives in __init__ ("ws or fsdp"), so
            # toggling fsdp back off leaves an explicit ws setting intact
            return self

        def host_transfer_dtype(self, dtype):
            """Cast float FEATURE arrays to ``dtype`` ON THE HOST before the
            device transfer. With ``compute_dtype='bfloat16'`` the layers
            cast inputs to bf16 on device anyway, so casting before the
            wire halves host→device bytes with BIT-IDENTICAL results — the
            lever for host-link-bound pipelines (the 137 MB/step
            299² InceptionV3 batch). EXPLICIT OPT-IN: unsafe for
            float-encoded integer id streams (embedding inputs — bf16
            rounds integers above 256); use only when features are real
            continuous data (images, audio, sensors). Labels and masks are
            not touched."""
            self._host_dtype = dtype
            return self

        hostTransferDtype = host_transfer_dtype

        def build(self) -> "ParallelWrapper":
            return ParallelWrapper(self._net, workers=self._workers,
                                   prefetch_buffer=self._prefetch,
                                   prefetch_workers=self._prefetch_workers,
                                   averaging_frequency=self._freq,
                                   training_mode=self._mode,
                                   report_score_after_averaging=self._report_after_avg,
                                   accumulator=self._accumulator,
                                   mesh=self._mesh,
                                   weight_update_sharding=self._ws,
                                   fsdp=self._fsdp,
                                   host_transfer_dtype=self._host_dtype,
                                   tensor_parallel=self._tp,
                                   tp_rules=self._tp_rules)

    def __init__(self, net, workers: Optional[int] = None,
                 prefetch_buffer: int = 2, prefetch_workers: int = 2,
                 averaging_frequency: int = 1,
                 training_mode: str = TrainingMode.AVERAGING,
                 report_score_after_averaging: bool = True,
                 accumulator: Optional[GradientsAccumulator] = None,
                 mesh: Optional[Mesh] = None,
                 weight_update_sharding: bool = False,
                 fsdp: bool = False,
                 host_transfer_dtype=None,
                 tensor_parallel: Optional[int] = None,
                 tp_rules=None):
        self.net = net
        self.host_transfer_dtype = host_transfer_dtype
        self.fsdp = bool(fsdp)
        self.weight_update_sharding = bool(weight_update_sharding) or self.fsdp
        if tp_rules is not None and tensor_parallel is None and mesh is None:
            raise ValueError("tp_rules needs a model axis: pass "
                             "tensor_parallel=<extent> or a mesh carrying "
                             "a 'model' axis")
        if tensor_parallel is not None and int(tensor_parallel) < 2:
            raise ValueError(f"tensor_parallel extent must be >= 2 "
                             f"(got {tensor_parallel}); without a model "
                             f"split just omit it")
        self.tensor_parallel = (None if tensor_parallel is None
                                else int(tensor_parallel))
        if self.tensor_parallel and tp_rules is None:
            from .tensor import megatron_rules
            tp_rules = megatron_rules(net)
        self.tp_rules = tp_rules
        if (int(getattr(net.gc, "iterations", 1) or 1) > 1
                and not getattr(net, "_warned_pw_iterations", False)):
            net._warned_pw_iterations = True
            log.warning("iterations(%s) is ignored under ParallelWrapper "
                        "(it re-jits the single-iteration step with mesh "
                        "shardings); each dispatched batch runs one "
                        "optimizer iteration",
                        net.gc.iterations)
        devices = jax.devices()
        if workers is not None and workers < len(devices):
            devices = devices[:workers]
        if mesh is not None:
            self.mesh = mesh
        elif self.tensor_parallel:
            # 2-D data × model: the model extent is fixed, the data extent
            # auto-factorizes over the remaining devices (MeshSpec rejects
            # non-dividing extents with an actionable message)
            self.mesh = MeshSpec(axes=(DATA_AXIS, MODEL_AXIS),
                                 shape=(None, self.tensor_parallel),
                                 devices=devices).build()
        else:
            self.mesh = MeshSpec(axes=(DATA_AXIS,), devices=devices).build()
        require_axes(self.mesh, (DATA_AXIS,), style="ParallelWrapper")
        if self.tp_rules is not None:
            require_axes(self.mesh, (MODEL_AXIS,),
                         style="ParallelWrapper.tensor_parallel")
        if (mesh is not None and self.tensor_parallel
                and int(mesh.shape[MODEL_AXIS]) != self.tensor_parallel):
            # an explicit mesh whose model extent disagrees with the
            # requested one must not silently win
            raise ValueError(
                f"tensor_parallel={self.tensor_parallel} but the given "
                f"mesh has model extent {int(mesh.shape[MODEL_AXIS])}; "
                f"drop one of the two or make them agree")
        # the wrapper drives the DATA axis: batch divisibility, round-robin
        # group size and iteration accounting all follow the data extent —
        # model-family axes shard params, not the batch
        n_devices = int(np.prod(self.mesh.devices.shape))
        self.workers_ = int(self.mesh.shape[DATA_AXIS])
        # multi-process (multi-host) awareness: each process feeds only its
        # addressable devices' share of the global batch
        self.process_count = jax.process_count()
        if self.process_count > 1:
            pidx = jax.process_index()
            local_devs = sum(1 for d in self.mesh.devices.flat
                             if d.process_index == pidx)
            # devices per data slice = model-family extents product; a
            # data slice spanning processes would make every process feed
            # a share of the SAME slice (double-fed global batch) — the
            # model-family axes must stay within a process (see
            # parallel/mesh.py axis conventions), so reject loudly
            per_slice = n_devices // self.workers_
            if per_slice > 1 and local_devs % per_slice:
                raise ValueError(
                    f"this process holds {local_devs} of the mesh's "
                    f"devices but each data slice spans {per_slice} "
                    f"(model-family extents); model/pipe/sequence axes "
                    f"must stay within a process — reshape the mesh so "
                    f"the data axis is the one crossing hosts")
            self.local_workers_ = max(1, local_devs // per_slice)
        else:
            self.local_workers_ = self.workers_
        self._mp_batch_size = None  # enforced-uniform size (multi-process)
        if self.weight_update_sharding or self.tp_rules is not None:
            # supported: AVERAGING freq=1 (fused psum step, incl. its TBPTT
            # variant). Loud rejection elsewhere — a silent no-op would let
            # a memory-tight job believe it has the N-fold saving (or the
            # model split)
            if (training_mode != TrainingMode.AVERAGING
                    or max(1, int(averaging_frequency)) != 1):
                what = ("weight_update_sharding"
                        if self.weight_update_sharding else "tensor_parallel")
                raise NotImplementedError(
                    f"{what} applies to "
                    "TrainingMode.AVERAGING with averaging_frequency=1 "
                    "(the fused-psum sync step); the local-SGD shard_map "
                    "and SHARED_GRADIENTS codec paths keep replicated "
                    "model state")
        # CacheMode.DEVICE for the sharded dispatch path: merged+sharded
        # global batches keyed by the group's array identities (see
        # DataSet._device_key). Values retain the KEYED HOST ARRAYS (the
        # same rule as _cached_device_put) so an id/data-pointer can't be
        # recycled into a stale-key collision, and the dict is LRU-evicted
        # under a byte budget so non-repeating data (augmentation,
        # streaming) can't pin unbounded HBM.
        self._sharded_batch_cache = {}   # key -> (out, retained, nbytes)
        self._sharded_cache_bytes = 0
        self.sharded_cache_budget = int(
            os.environ.get("DL4J_TPU_PW_CACHE_BYTES", 4 << 30))
        self.prefetch_buffer = prefetch_buffer
        self.prefetch_workers = max(0, int(prefetch_workers))
        self.averaging_frequency = max(1, int(averaging_frequency))
        self.training_mode = training_mode
        self.report_score_after_averaging = report_score_after_averaging
        self.accumulator = accumulator
        self.iteration_count = 0
        self.last_score = float("nan")
        self._sync_step = None
        self._local_sgd_step = None
        self.averaging_ms = 0.0
        # ComputationGraph steps take tuples of input/label streams (its
        # _raw_step zips network_inputs with the inputs arg); bare arrays
        # would be iterated along the batch axis — row 0 only
        self._is_graph = hasattr(net, "_as_multi")

    # ------------------------------------------------------------------
    def _ensure_sync_step(self):
        if self._sync_step is None:
            self._sync_step = data_parallel_step(
                self.net, self.mesh,
                shard_update=self.weight_update_sharding,
                shard_params=self.fsdp, tp_rules=self.tp_rules)
        return self._sync_step

    def _ensure_sync_tbptt_step(self):
        if getattr(self, "_sync_tbptt_step", None) is None:
            self._sync_tbptt_step = data_parallel_tbptt_step(
                self.net, self.mesh,
                shard_update=self.weight_update_sharding,
                shard_params=self.fsdp, tp_rules=self.tp_rules)
        return self._sync_tbptt_step

    # ------------------------------------------------------------ TBPTT
    def _tbptt_applicable(self, f):
        """True when this (possibly tuple-of-streams) feature batch should be
        trained as TBPTT segments — same predicate the containers use in
        ``_fit_batch``, so sharded, tail and single-device batches all get
        identical truncation semantics (reference: every ParallelWrapper
        worker runs the full fit loop, ``DefaultTrainer.java:244``)."""
        conf = self.net.conf
        if conf.backprop_type != BackpropType.TruncatedBPTT:
            return False
        xs = f if isinstance(f, tuple) else (f,)
        return (all(x.ndim == 3 for x in xs)
                and xs[0].shape[1] > conf.tbptt_fwd_length)

    @staticmethod
    def _tbptt_slices(f, l, fm, lm, sl):
        f_c = _tm(lambda x: x[:, sl], f)
        l_c = _tm(lambda x: x[:, sl] if x.ndim == 3 else x, l)
        fm_c = None if fm is None else _tm(lambda m: m[:, sl], fm)
        lm_c = None if lm is None else _tm(lambda m: m[:, sl], lm)
        return f_c, l_c, fm_c, lm_c

    def _stacked_n_segments(self, fs):
        """Segments per micro-batch for [N, b, T, ...] stacked TBPTT data —
        the stacked-shape sibling of ``_tbptt_applicable``."""
        conf = self.net.conf
        xs = jax.tree_util.tree_leaves(fs)
        if (conf.backprop_type == BackpropType.TruncatedBPTT
                and all(x.ndim == 4 for x in xs)
                and xs[0].shape[2] > conf.tbptt_fwd_length):
            return -(-xs[0].shape[2] // conf.tbptt_fwd_length)
        return 1

    def _fit_tbptt_segments(self, f, l, fm, lm, seg_step):
        """Shared TBPTT segment loop for the sharded paths (mirrors the
        containers' ``_fit_tbptt``: one optimizer update per segment, carry
        detached between segments, one listener event per batch).
        ``seg_step(itc, key, f_c, l_c, fm_c, lm_c, rnn) -> (loss, rnn)``
        applies one segment's update however the training mode does."""
        net = self.net
        leaves = jax.tree_util.tree_leaves(f)
        T, batch = int(leaves[0].shape[1]), int(leaves[0].shape[0])
        L = net.conf.tbptt_fwd_length
        rnn_state = net._init_rnn_state(batch)
        loss = jnp.asarray(float("nan"))
        for start in range(0, T, L):
            sl = slice(start, min(start + L, T))
            f_c, l_c, fm_c, lm_c = self._tbptt_slices(f, l, fm, lm, sl)
            itc = jnp.asarray(net.iteration_count, jnp.int32)
            key = put_replicated(net._next_rng(), self.mesh)
            loss, rnn_state = seg_step(itc, key, f_c, l_c, fm_c, lm_c,
                                       rnn_state)
            net.iteration_count += 1
        self.last_score = self._fetch_score(loss)
        net.score_ = loss
        self.iteration_count += 1
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration_count - 1, self.last_score)

    def _fit_sync_tbptt(self, f, l, fm, lm):
        """TBPTT over the fused-psum sharded step."""
        net = self.net
        step = self._ensure_sync_tbptt_step()

        def seg(itc, key, f_c, l_c, fm_c, lm_c, rnn):
            (net.params, net.states, net.updater_state, loss, rnn) = step(
                net.params, net.states, net.updater_state, itc, key, f_c,
                l_c, fm_c, lm_c, rnn)
            return loss, rnn

        self._fit_tbptt_segments(f, l, fm, lm, seg)

    def _ensure_local_sgd_step(self):
        """shard_map local-SGD: [N, b, ...] micro-batch stack per device, N
        local updates, then pmean of params/updater-state/layer-state."""
        if self._local_sgd_step is not None:
            return self._local_sgd_step
        net = self.net
        mesh = self.mesh
        raw = net._raw_step(False)
        raw_t = net._raw_step(True)
        conf = net.conf
        N = self.averaging_frequency

        def one_micro(params, states, upd, it, k, f, l, fm, lm):
            """One micro-batch on one device: TBPTT-segments when the traced
            shapes call for it (``_tbptt_applicable`` is trace-time static),
            else one full-BPTT update."""
            if not self._tbptt_applicable(f):
                return raw(params, states, upd, it, k, f, l, fm, lm)
            xs = jax.tree_util.tree_leaves(f)
            T, L = xs[0].shape[1], conf.tbptt_fwd_length
            rnn = net._init_rnn_state(xs[0].shape[0])
            rnn = _tm(lambda x: pvary(x, (DATA_AXIS,)), rnn)
            loss = pvary(jnp.asarray(0.0, jnp.float32), (DATA_AXIS,))
            for s_i, start in enumerate(range(0, T, L)):
                sl = slice(start, min(start + L, T))
                f_c, l_c, fm_c, lm_c = ParallelWrapper._tbptt_slices(
                    f, l, fm, lm, sl)
                params, states, upd, loss, rnn = raw_t(
                    params, states, upd, it + s_i,
                    jax.random.fold_in(k, s_i), f_c, l_c, fm_c, lm_c, rnn)
            return params, states, upd, loss

        def local_run(params, states, upd, it0, rng, fs, ls, fms, lms):
            # runs per-device under shard_map: fs/ls/fms/lms [N, b_local, ...]
            dev = jax.lax.axis_index(DATA_AXIS)
            rng = jax.random.fold_in(rng, dev)
            n_seg = self._stacked_n_segments(fs)

            def body(i, carry):
                params, states, upd, _ = carry
                # tree_map: arrays (MLN) or stream tuples (CG); None masks
                # are empty pytrees and pass through
                idx = lambda a: jax.lax.dynamic_index_in_dim(a, i,
                                                             keepdims=False)
                f, l, fm, lm = (_tm(idx, t) for t in (fs, ls, fms, lms))
                k = jax.random.fold_in(rng, i)
                params, states, upd, loss = one_micro(
                    params, states, upd, it0 + i * n_seg, k, f, l, fm, lm)
                return params, states, upd, loss

            # mark the carry as device-varying: replicas diverge locally
            # between averaging barriers. Under check_vma=False (below)
            # this is a no-op kept for documentation value and in case the
            # vma check is ever re-enabled — the pmean barrier after the
            # loop is what actually restores replica agreement; vma typing
            # does NOT verify it here
            init = jax.tree_util.tree_map(
                lambda x: pvary(x, (DATA_AXIS,)),
                (params, states, upd, jnp.asarray(0.0, jnp.float32)))
            params, states, upd, loss = jax.lax.fori_loop(0, N, body, init)
            # periodic averaging barrier (params + updater state + layer state)
            params = jax.lax.pmean(params, DATA_AXIS)
            states = jax.lax.pmean(states, DATA_AXIS)
            upd = jax.lax.pmean(upd, DATA_AXIS)
            loss = jax.lax.pmean(loss, DATA_AXIS)
            return params, states, upd, loss

        repl = P()
        data = P(None, DATA_AXIS)  # [N, global_b, ...] split on batch dim
        # check_vma=False: the step may route through Pallas kernels
        # (persistent/fused LSTM), whose out_shape ShapeDtypeStructs carry
        # no vma typing — same setting as every other shard_map in
        # parallel/ (sequence.py, pipeline.py)
        fn = shard_map(local_run, mesh=mesh,
                       in_specs=(repl, repl, repl, repl, repl, data, data,
                                 data, data),
                       out_specs=(repl, repl, repl, repl),
                       check_vma=False)
        record_step("wrapper/local_sgd", mesh)
        self._local_sgd_step = monitored_jit(
            fn, name="wrapper/local_sgd_step", donate_argnums=(0, 2))
        return self._local_sgd_step

    # ------------------------------------------------------------------ fit
    def fit(self, data, epochs: int = 1):
        """Train over the iterator with all devices (reference ``fit`` :468)."""
        import time
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        it = data
        owned = False
        if (isinstance(it, DataSetIterator)
                and not isinstance(it, (AsyncDataSetIterator,
                                        PrefetchDataSetIterator))
                and it.async_supported()
                and self.prefetch_workers > 0):
            # multi-worker host ETL ahead of the batch grouper. NO
            # device_put here: placement is _global_batch's job — it
            # merges one batch per device then shards over the mesh
            it = PrefetchDataSetIterator(it, workers=self.prefetch_workers,
                                         queue_size=self.prefetch_buffer,
                                         device_put=False)
            owned = True
        net = self.net
        try:
            for _ in range(epochs):
                if self.training_mode == TrainingMode.SHARED_GRADIENTS:
                    self._fit_shared(it)
                elif self.averaging_frequency == 1:
                    self._fit_sync(it)
                else:
                    self._fit_local_sgd(it)
                net.epoch_count += 1
        finally:
            if owned:
                it.shutdown()
        return self

    def _device_put_model(self):
        """Place params/updater-state with EXACTLY the specs the jitted
        step was built with (``composed_specs`` is the single source of
        truth for both) — TP rules claim the model axis, ZeRO flags layer
        the data axis; everything else replicates. A set-up span (kept by
        the tracer whatever the monitor switch says): one per ``fit``."""
        net = self.net
        put = lambda t: _tm(lambda x: put_replicated(x, self.mesh), t)
        leaves = jax.tree_util.tree_leaves(
            (net.params, net.states, net.updater_state))
        with get_tracer().span("pw/place_model", cat="setup",
                               leaves=len(leaves),
                               bytes=sum(int(x.nbytes) for x in leaves)):
            par, upd = composed_specs(
                net, self.mesh, tp_rules=self.tp_rules,
                shard_update=self.weight_update_sharding,
                shard_params=self.fsdp)
            net.params = put_sharded_tree(net.params, par)
            net.states = put(net.states)
            net.updater_state = put_sharded_tree(net.updater_state, upd)

    def _resolve_score(self, pending):
        """Resolve a deferred ``(loss, iteration_idx)`` score fetch. The
        value fetch is the device-sync point, so it is deferred by exactly
        one step: when it blocks here, the NEXT step's host→device transfer
        and dispatch are already enqueued, overlapping H2D with compute —
        the device-side half of the AsyncDataSetIterator promise
        (reference ``ParallelWrapper.java:468-516`` keeps workers busy via
        queues; XLA's async dispatch plays that role, and an eager per-step
        ``float(loss)`` would serialize it away).

        Deferral only happens with NO listeners attached (the bench/
        throughput shape): a deferred callback would hand listeners a model
        whose params/iteration_count had already advanced one step
        (CheckpointListener would save the wrong params under the label,
        ParamAndGradient would attribute the wrong delta), so with
        listeners the fetch stays eager and exact."""
        if pending is None:
            return
        loss, idx = pending
        self.last_score = v = self._fetch_score(loss)
        net = self.net
        for lst in net.listeners:
            lst.iteration_done(net, idx, v)

    @staticmethod
    def _fetch_score(loss):
        """The device→host fetch of a step's loss, under the span that
        names it as the wait it is (it lasts about a step)."""
        with get_tracer().span("pw/resolve_score", cat="train"):
            return float(loss)

    def _fit_sync(self, it):
        """AVERAGING freq=1 / SHARED_GRADIENTS: fused psum step per global
        batch (the reference's per-iteration averaging ≡ gradient all-reduce).

        Batch semantics match the reference's round-robin dispatch
        (``ParallelWrapper.java:497-516``): each device consumes ONE iterator
        batch per parallel iteration, so ``workers_`` iterator batches are
        merged into the global batch of a step. A tail group smaller than
        ``workers_`` is still trained (sharded across all devices) so no data
        is dropped.

        The per-step score fetch is double-buffered (``_resolve_score``)
        when no listeners are attached: step k's H2D + dispatch are
        enqueued before step k-1's loss is fetched, so the host link
        streams the next global batch while the chip computes the current
        one. With listeners the fetch is eager (exact model state per
        callback — see ``_resolve_score``)."""
        net = self.net
        step = self._ensure_sync_step()
        self._device_put_model()
        pending = None
        try:
            for group, _ in spanned(self._batch_groups(it), "pw/group"):
                if group is None:
                    continue  # tail handled unsharded by _batch_groups
                f, l, fm, lm = self._global_batch(group)
                if self._tbptt_applicable(f):
                    prev, pending = pending, None
                    self._resolve_score(prev)
                    self._fit_sync_tbptt(f, l, fm, lm)
                    continue
                with self._step_span():
                    itc = jnp.asarray(net.iteration_count, jnp.int32)
                    key = put_replicated(net._next_rng(), self.mesh)
                    net.params, net.states, net.updater_state, loss = step(
                        net.params, net.states, net.updater_state, itc, key,
                        f, l, fm, lm)
                net.score_ = loss
                net.iteration_count += 1
                self.iteration_count += 1
                cur = (loss, net.iteration_count - 1)
                if net.listeners:
                    self._resolve_score(cur)       # eager: exact state
                else:
                    # clear BEFORE resolving: a raise mid-resolve must not
                    # let the finally replay the same iteration
                    prev, pending = pending, cur
                    self._resolve_score(prev)
        finally:
            prev, pending = pending, None
            self._resolve_score(prev)

    def _step_span(self):
        """``pw/step``: the dispatch of one sharded step with the iteration
        scalar and the key it takes; ``step_num`` as the containers'
        ``step`` span has it."""
        return get_tracer().span("pw/step", cat="train",
                                 step_num=self.net.iteration_count)

    def _batch_groups(self, it):
        """Yield groups of iterator batches (reference round-robin dispatch):
        one batch per LOCAL device per parallel iteration — under multi-process
        each process feeds only its addressable share of the global batch.

        Single-process, a group whose example total is not divisible by the
        device count is trained unsharded right here (net's own replicated
        step) and yielded as None so no data is dropped or crashed on.
        Multi-process, an unsharded step would desync the collective schedule
        across processes, so the odd tail is dropped with a warning instead."""
        net = self.net
        group_size = self.local_workers_
        pending = []
        it = iter(it)
        exhausted = False
        while not exhausted:
            try:
                pending.append(next(it))
            except StopIteration:
                exhausted = True
            if not pending or (len(pending) < group_size and not exhausted):
                continue
            group, pending = pending, []
            total = sum(b.num_examples() for b in group)
            if self.process_count > 1:
                # the divisibility decision must be identical on every process
                # or collective schedules desync (hang); uniform batch sizes
                # guarantee that, so enforce them loudly instead
                sizes = {b.num_examples() for b in group}
                if self._mp_batch_size is None:
                    self._mp_batch_size = next(iter(sizes))
                sizes.add(self._mp_batch_size)
                if len(sizes) != 1:
                    raise ValueError(
                        f"multi-process training requires uniform iterator "
                        f"batch sizes; saw {sorted(sizes)}")
            if total % group_size:
                if self.process_count > 1:
                    log.warning("Dropping %d-example tail group (not divisible "
                                "by %d local devices; unsharded fallback would "
                                "desync processes)", total, group_size)
                    yield None
                    continue
                if len(group) == 1:
                    merged = group[0]
                elif self._is_graph:
                    merged = MultiDataSet.merge([net._as_multi(b)
                                                 for b in group])
                else:
                    merged = DataSet.merge(group)
                log.info("Batch group of %d examples not divisible by %d "
                         "devices; training it unsharded", total,
                         self.workers_)
                self._fit_unsharded(net, merged)
                self.iteration_count += 1
                self.last_score = float(net.score_)
                yield None
                continue
            yield group

    def _fit_unsharded(self, net, merged):
        """Train one unsharded fallback batch with exactly ONE optimizer
        iteration per step dispatch — consistent with every sharded dispatch
        (the net's own cached step may be an ``iterations(n)`` scan, which
        would give tail batches n× the updates and desync the iteration
        accounting). Routed through the container's own ``_fit_batch`` so
        feature/label masks and TBPTT segmentation are preserved exactly as
        on the sharded path (round-3 advisor finding)."""
        net._fit_batch(merged, single_iteration=True)

    def _ensure_shared_steps(self):
        """Two jitted halves around the host codec seam: compute the
        updater-transformed update (gradient psum on ICI), then apply a
        decoded update. The host hop between them is the DCN boundary the
        encoding exists for."""
        if getattr(self, "_shared_steps", None) is not None:
            return self._shared_steps
        net = self.net
        repl = replicated(self.mesh)
        data = batch_sharded(self.mesh)
        update_step = monitored_jit(
            net._raw_update_step(), name="wrapper/shared_update_step",
            in_shardings=(repl, repl, repl, repl, repl, data, data, data, data),
            out_shardings=(repl, repl, repl, repl),
            donate_argnums=(2,))

        def apply_fn(params, update):
            new = _tm(lambda p, u: p - u.astype(p.dtype), params, update)
            return net._apply_constraints(new)

        apply_step = monitored_jit(apply_fn, name="wrapper/shared_apply_step",
                                   out_shardings=repl, donate_argnums=(0,))
        record_step("wrapper/shared", self.mesh)
        self._shared_steps = (update_step, apply_step)
        return self._shared_steps

    def _fit_shared(self, it):
        """SHARED_GRADIENTS (reference ``SymmetricTrainer`` +
        ``EncodedGradientsAccumulator.java:257``): every round the all-reduced
        update is threshold-encoded — sub-threshold mass stays in the host
        residual, the quantized decode is what peers (other slices over DCN)
        would receive — and ALL replicas apply the decoded update, keeping
        them bit-identical while the wire carries ``encoded_bytes()`` instead
        of dense tensors. Trajectories genuinely differ from AVERAGING."""
        net = self.net
        if self.accumulator is None:
            self.accumulator = EncodedGradientsAccumulator()
        update_step, apply_step = self._ensure_shared_steps()
        self._device_put_model()
        for group, _ in spanned(self._batch_groups(it), "pw/group"):
            if group is None:
                continue
            f, l, fm, lm = self._global_batch(group)
            if self._tbptt_applicable(f):
                self._fit_shared_tbptt(f, l, fm, lm, apply_step)
                continue
            with self._step_span():
                itc = jnp.asarray(net.iteration_count, jnp.int32)
                key = put_replicated(net._next_rng(), self.mesh)
                update, net.states, net.updater_state, loss = update_step(
                    net.params, net.states, net.updater_state, itc, key, f,
                    l, fm, lm)
            self._apply_encoded(apply_step, update)
            self.last_score = self._fetch_score(loss)
            net.score_ = loss
            net.iteration_count += 1
            self.iteration_count += 1
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration_count - 1,
                                   self.last_score)

    def _apply_encoded(self, apply_step, update):
        """Host hop: encode (residual kept) → apply the decoded quantized
        update — what peers over DCN would receive."""
        net = self.net
        decoded = self.accumulator.store_update(_tm(np.asarray, update))
        net.params = apply_step(net.params, _tm(jnp.asarray, decoded))

    def _fit_shared_tbptt(self, f, l, fm, lm, apply_step):
        """SHARED_GRADIENTS × TBPTT: every segment's updater-transformed
        update passes through the threshold codec (one wire message per
        applied update — reference ``SymmetricTrainer`` encodes per
        iteration, and TBPTT iterations are per segment)."""
        net = self.net
        if getattr(self, "_shared_tbptt_step", None) is None:
            self._shared_tbptt_step = data_parallel_tbptt_update_step(
                net, self.mesh)
        step = self._shared_tbptt_step

        def seg(itc, key, f_c, l_c, fm_c, lm_c, rnn):
            (update, net.states, net.updater_state, loss, rnn) = step(
                net.params, net.states, net.updater_state, itc, key, f_c,
                l_c, fm_c, lm_c, rnn)
            self._apply_encoded(apply_step, update)
            return loss, rnn

        self._fit_tbptt_segments(f, l, fm, lm, seg)

    def _fit_local_sgd(self, it):
        """AVERAGING freq=N: collect N micro-batches, one fused local-SGD +
        averaging computation."""
        import time
        net = self.net
        step = self._ensure_local_sgd_step()
        self._device_put_model()
        pending: List[DataSet] = []
        for ds in it:
            pending.append(ds)
            if len(pending) < self.averaging_frequency:
                continue
            fs, ls, fms, lms = self._stacked_batches(pending)
            pending = []
            # TBPTT segments count as extra optimizer iterations per micro-
            # batch (mirror of the trace-time predicate in one_micro)
            n_seg = self._stacked_n_segments(fs)
            with self._step_span():
                itc = jnp.asarray(net.iteration_count, jnp.int32)
                key = put_replicated(net._next_rng(), self.mesh)
                t0 = time.perf_counter()
                net.params, net.states, net.updater_state, loss = step(
                    net.params, net.states, net.updater_state, itc, key, fs,
                    ls, fms, lms)
            # value fetch = completion barrier
            self.last_score = self._fetch_score(loss)
            self.averaging_ms = (time.perf_counter() - t0) * 1e3
            net.iteration_count += self.averaging_frequency * n_seg
            self.iteration_count += self.averaging_frequency
            net.score_ = loss
            if self.report_score_after_averaging:
                for lst in net.listeners:
                    lst.iteration_done(net, net.iteration_count - 1,
                                       self.last_score)
        if pending:
            log.info("Dropping %d tail micro-batches (< averaging_frequency)",
                     len(pending))

    # ---------------------------------------------------------------- helpers
    def _global_batch(self, batches):
        """Merge iterator batches into one sharded global batch.

        Source dtypes are preserved (integer embedding indices, f64 nets);
        the layers' own ``cast_in`` decides the compute dtype. For a
        ComputationGraph the step takes tuples of input/label streams.

        Under ``CacheMode.DEVICE`` the merged+sharded result is cached on
        the group's array identities, so repeated epochs over the same
        iterator batches skip the host→device transfer entirely — the
        reference's ``CacheMode.DEVICE`` semantics (`nn/conf/CacheMode.java`)
        applied to the ParallelWrapper dispatch path."""
        with get_tracer().span("pw/global_batch", cat="train"):
            return self._cached_sharded((), batches,
                                        self._global_batch_uncached)

    def _cached_sharded(self, prefix, batches, build):
        """LRU device-batch cache shared by the sync and local-SGD paths.
        Keyed on the batches' ``_device_key`` tuples; each entry retains the
        keyed host arrays (so ids/data pointers stay pinned for the entry's
        lifetime — the `_cached_device_put` rule) and records the device
        bytes it pins; total pinned bytes are bounded by
        ``sharded_cache_budget`` (env ``DL4J_TPU_PW_CACHE_BYTES``, default
        4 GiB) with least-recently-used eviction.

        CONTRACT — cached arrays must not be mutated in place: the key is
        (id, data pointer, shape, dtype), so a pipeline that WRITES into a
        reused batch buffer (e.g. augmentation into the same ndarray) keeps
        the same key and the step silently trains on the STALE device copy.
        Feed ``CacheMode.DEVICE`` fresh arrays per distinct batch, or call
        ``clear_device_cache()`` after mutating."""
        if getattr(self.net.gc, "cache_mode", None) != CacheMode.DEVICE:
            return build(batches)
        ckey = prefix + tuple(b._device_key() for b in batches)
        cache = self._sharded_batch_cache
        hit = cache.pop(ckey, None)
        if hit is not None:
            cache[ckey] = hit                     # re-insert: LRU freshness
            return hit[0]
        out = build(batches)

        def _retained(b):
            if isinstance(b, MultiDataSet):
                seqs = (b.features, b.labels, b.features_masks, b.labels_masks)
                return tuple(tuple(s) for s in seqs if s is not None)
            return (b.features, b.labels, b.features_mask, b.labels_mask)

        nbytes = sum(getattr(a, "nbytes", 0)
                     for a in jax.tree_util.tree_leaves(out))
        cache[ckey] = (out, tuple(_retained(b) for b in batches), nbytes)
        self._sharded_cache_bytes += nbytes
        # plain-dict insertion order + re-insert-on-hit above ⇒ first key
        # is the least recently used
        while (self._sharded_cache_bytes > self.sharded_cache_budget
               and len(cache) > 1):
            oldest = next(iter(cache))
            _, _, old_bytes = cache.pop(oldest)
            self._sharded_cache_bytes -= old_bytes
        return out

    def gather_model(self):
        """Re-replicate a sharded-storage model (``fsdp``/
        ``weight_update_sharding``) so its params/updater state are plain
        host-accessible arrays again — REQUIRED before ``np.asarray``/
        serialization/scoring on a MULTI-PROCESS mesh, where a sharded
        leaf spans non-addressable devices (single-process shards gather
        transparently). Uses ``process_allgather`` across hosts."""
        net = self.net
        if self.process_count > 1:
            from jax.experimental import multihost_utils

            def regather(t):
                return _tm(
                    lambda x: multihost_utils.process_allgather(
                        x, tiled=True)
                    if hasattr(x, "sharding") and x.sharding.spec else x, t)

            net.params = regather(net.params)
            net.updater_state = regather(net.updater_state)
        else:
            # leave HOST arrays (like the multi-process branch): the whole
            # point of fsdp is that a full copy may not fit one device
            host = lambda t: _tm(np.asarray, t)
            net.params = host(net.params)
            net.updater_state = host(net.updater_state)
        return net

    gatherModel = gather_model

    def clear_device_cache(self):
        """Drop every cached sharded batch (and the host arrays it retains).
        Use when training under ``CacheMode.DEVICE`` with data that does NOT
        repeat across epochs (augmentation, streaming): non-repeating batches
        insert entries that can never hit, and although the LRU byte budget
        bounds the HBM pinned, that budget is better spent on activations.
        ALSO required for correctness if batch arrays were mutated IN PLACE:
        the cache keys on array identity, so an in-place write leaves a
        stale device copy behind the same key (see ``_cached_sharded``)."""
        self._sharded_batch_cache.clear()
        self._sharded_cache_bytes = 0

    def _host_cast(self, x):
        """``host_transfer_dtype``: cast float feature arrays on the HOST so
        the device transfer carries half the bytes (bit-identical when the
        layers would cast to the same compute dtype anyway — see the
        Builder option's docstring for the embedding-id hazard)."""
        if self.host_transfer_dtype is None:
            return x
        a = np.asarray(x)
        if a.dtype not in (np.float32, np.float64):
            return x                       # ints/bools: never touched
        # ml_dtypes (a jax dependency) registers 'bfloat16' with numpy
        dt = np.dtype("bfloat16" if str(self.host_transfer_dtype) == "bf16"
                      else self.host_transfer_dtype)
        compute = str(getattr(self.net.gc, "compute_dtype", "float32"))
        if compute != str(dt) and not getattr(self, "_warned_host_cast",
                                              False):
            self._warned_host_cast = True
            log.warning(
                "host_transfer_dtype=%s with compute_dtype=%s: inputs are "
                "rounded BEFORE the (wider) compute — results will differ "
                "from the uncast run. Bit-identical only when the two "
                "dtypes match.", dt, compute)
        return a.astype(dt)

    def _global_batch_uncached(self, batches):
        if self._is_graph:
            mds_list = [self.net._as_multi(b) for b in batches]
            mds = mds_list[0] if len(mds_list) == 1 else MultiDataSet.merge(mds_list)
            b = mds.num_examples()
            if b % self.local_workers_:
                raise ValueError(
                    f"Local batch {b} not divisible by "
                    f"{self.local_workers_} local devices")
            f = tuple(shard_batch(jnp.asarray(self._host_cast(x)), self.mesh)
                      for x in mds.features)
            l = tuple(shard_batch(jnp.asarray(x), self.mesh)
                      for x in mds.labels)
            fm = (None if mds.features_masks is None else tuple(
                None if m is None else shard_batch(jnp.asarray(m), self.mesh)
                for m in mds.features_masks))
            lm = (None if mds.labels_masks is None else tuple(
                None if m is None else shard_batch(jnp.asarray(m), self.mesh)
                for m in mds.labels_masks))
            return f, l, fm, lm
        ds = batches[0] if len(batches) == 1 else DataSet.merge(batches)
        f = self._host_cast(np.asarray(ds.features))
        l = np.asarray(ds.labels)
        b = f.shape[0]
        if b % self.local_workers_:
            raise ValueError(
                f"Local batch {b} not divisible by "
                f"{self.local_workers_} local devices")
        fm = (None if ds.features_mask is None
              else shard_batch(jnp.asarray(ds.features_mask), self.mesh))
        lm = (None if ds.labels_mask is None
              else shard_batch(jnp.asarray(ds.labels_mask), self.mesh))
        return (shard_batch(jnp.asarray(f), self.mesh),
                shard_batch(jnp.asarray(l), self.mesh), fm, lm)

    def _stacked_batches(self, batches):
        """[N, global_b, ...] with the global batch dim sharded. Masks ride
        along (all-ones filled when presence is mixed across micro-batches).
        ``CacheMode.DEVICE`` reuses the stacked+sharded device copy across
        epochs (same cache as :meth:`_global_batch`)."""
        return self._cached_sharded(("stack",), batches,
                                    self._stacked_batches_uncached)

    def _stacked_batches_uncached(self, batches):
        def stack_masks(masks, data):
            if all(m is None for m in masks):
                return None
            ndim = next(m.ndim for m in masks if m is not None)
            return np.stack([m if m is not None
                             else np.ones(np.asarray(d).shape[:ndim],
                                          np.float32)
                             for m, d in zip(masks, data)])

        if self._is_graph:
            mds_list = [self.net._as_multi(b) for b in batches]
            n_in = len(mds_list[0].features)
            n_out = len(mds_list[0].labels)
            fs = tuple(np.stack([self._host_cast(m.features[i])
                                 for m in mds_list])
                       for i in range(n_in))
            ls = tuple(np.stack([np.asarray(m.labels[i]) for m in mds_list])
                       for i in range(n_out))
            fms = tuple(stack_masks(
                [None if m.features_masks is None else m.features_masks[i]
                 for m in mds_list],
                [m.features[i] for m in mds_list]) for i in range(n_in))
            lms = tuple(stack_masks(
                [None if m.labels_masks is None else m.labels_masks[i]
                 for m in mds_list],
                [m.labels[i] for m in mds_list]) for i in range(n_out))
            if all(m is None for m in fms):
                fms = None
            if all(m is None for m in lms):
                lms = None
            gb = fs[0].shape[1]
        else:
            fs = np.stack([self._host_cast(b.features) for b in batches])
            ls = np.stack([np.asarray(b.labels) for b in batches])
            fms = stack_masks([b.features_mask for b in batches],
                              [b.features for b in batches])
            lms = stack_masks([b.labels_mask for b in batches],
                              [b.labels for b in batches])
            gb = fs.shape[1]
        if gb % self.local_workers_:
            raise ValueError(f"Local batch {gb} not divisible by "
                             f"{self.local_workers_} local devices")
        sh = NamedSharding(self.mesh, P(None, DATA_AXIS))
        if self.process_count > 1:
            put_leaf = lambda a: jax.make_array_from_process_local_data(
                sh, np.asarray(a))
        else:
            put_leaf = lambda a: jax.device_put(jnp.asarray(a), sh)
        put = lambda t: (None if t is None else jax.tree_util.tree_map(
            put_leaf, t))
        return put(fs), put(ls), put(fms), put(lms)

    def shutdown(self):
        pass  # no worker threads to stop — SPMD has no zoo of replicas

"""Device-mesh sharding helpers — the SPMD data-parallel steps.

TPU-native replacement for the reference's device-affinity machinery
(``Nd4j.getAffinityManager()`` uses in ``ParallelWrapper.java:484`` and
``MultiLayerNetwork.java:1161``): instead of pinning model replicas to devices
from host threads, we declare a `jax.sharding.Mesh` and annotate the jitted
train step's inputs with `NamedSharding`s; XLA's SPMD partitioner inserts the
ICI collectives (psum for gradient all-reduce) that replace both parameter
averaging and Aeron gradient broadcast (SURVEY.md §2.4 "Distributed
communication backend").

Mesh construction, axis conventions, validation, and the partition-spec
machinery all live in ``parallel/mesh.py`` (the unified substrate —
docs/PARALLELISM.md "Unified mesh substrate"); this module keeps the
data-parallel STEP factories, now composition-aware: ``tp_rules`` shards
the ``model`` axis of a 2-D mesh inside the same jitted step, and the
ZeRO flags (``shard_update``/``shard_params``) ride the ``data`` axis of
whatever mesh they are given (:func:`~deeplearning4j_tpu.parallel.mesh.
zero_update_specs`).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np
import jax

from ..monitor.jitwatch import monitored_jit
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import (DATA_AXIS, MODEL_AXIS, SEQUENCE_AXIS, MeshSpec,
                   make_mesh, replicated, batch_sharded,
                   mirror_updater_shardings, require_axes, rule_shardings,
                   zero_update_specs, record_step)

__all__ = ["DATA_AXIS", "MODEL_AXIS", "SEQUENCE_AXIS", "MeshSpec",
           "make_mesh", "replicated", "batch_sharded", "shard_batch",
           "put_replicated", "put_sharded_tree", "update_sharded_specs",
           "composed_specs", "data_parallel_step",
           "data_parallel_tbptt_step", "data_parallel_tbptt_update_step",
           "pvary"]


def shard_batch(x, mesh: Mesh, axis: str = DATA_AXIS):
    """Place a host batch with its leading dim split across ``axis``.

    Single-process: a plain sharded device_put. Multi-process (after
    ``jax.distributed.initialize``): ``x`` is this process's LOCAL portion of
    the global batch — the global array is assembled from every process's
    local data without any host ever holding the full batch (the reference's
    per-executor ``VirtualDataSetIterator`` partition feeding, done the JAX
    multi-controller way)."""
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(
            batch_sharded(mesh, axis), np.asarray(x))
    return jax.device_put(x, batch_sharded(mesh, axis))


def put_replicated(x, mesh: Mesh):
    """Replicate a host value over the (possibly multi-process) mesh. Every
    process must hold the same value (same-seed init guarantees this)."""
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(replicated(mesh),
                                                      np.asarray(x))
    return jax.device_put(x, replicated(mesh))


def put_sharded_tree(tree, specs):
    """Place a host pytree with per-leaf ``NamedSharding``s. Single-process:
    plain sharded device_put. Multi-process: every process holds the same
    full host value (same-seed init), and ``make_array_from_callback``
    slices out each process's addressable shards — no host ever transfers
    more than its devices' portion."""
    multi = jax.process_count() > 1

    def put(x, sh):
        cur = getattr(x, "sharding", None)
        if cur == sh:
            return x                      # already placed (second fit call)
        if multi:
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                # already distributed under another sharding: device-side
                # reshard, no host round-trip
                return jax.device_put(x, sh)
            a = np.asarray(x)
            return jax.make_array_from_callback(
                a.shape, sh, lambda idx, _a=a: _a[idx])
        return jax.device_put(x, sh)

    return jax.tree_util.tree_map(put, tree, specs)


def update_sharded_specs(tree, mesh: Mesh, axis: str = DATA_AXIS):
    """Sharding pytree for OPTIMIZER STATE sharded over the data axis —
    weight-update / optimizer-state sharding (Xu et al. 2020,
    arXiv:2004.13336; the ZeRO-1 idea expressed as XLA sharding
    annotations). Thin alias of :func:`~deeplearning4j_tpu.parallel.mesh.
    zero_update_specs` with no base specs — see it for the dim-selection
    rule and the 2-D composition semantics."""
    return zero_update_specs(tree, mesh, axis)


def composed_specs(net, mesh: Mesh, axis: str = DATA_AXIS,
                   tp_rules: Optional[Dict[str, P]] = None,
                   shard_update: bool = False, shard_params: bool = False):
    """The ONE place the composed model-state shardings are decided, shared
    by the step factories below and ``ParallelWrapper._device_put_model``
    (specs used to jit and specs used to place MUST agree or every fit
    pays a reshard).

    Returns ``(param_specs, updater_specs)`` pytrees: tensor-parallel
    ``tp_rules`` claim the ``model`` axis first (updater state mirrors its
    param's sharding), then the ZeRO flags layer the ``data`` axis of the
    same mesh onto the remaining dims — ``shard_update`` for optimizer
    state (ZeRO-1), ``shard_params`` additionally for parameter storage
    (ZeRO-3/FSDP)."""
    # every axis the rules (or the ZeRO flags) name must exist on the
    # mesh — a raw KeyError from deep inside a tree_map is not a
    # substrate error message
    needed = set()
    if tp_rules:
        needed.update(s for spec in tp_rules.values()
                      for s in tuple(spec) if s is not None)
    if shard_update or shard_params:
        needed.add(axis)
    require_axes(mesh, sorted(needed), style="composed_specs(tp_rules/ZeRO)")
    if tp_rules:
        par = rule_shardings(net.params, mesh, tp_rules)
        upd = mirror_updater_shardings(net.params, net.updater_state, mesh,
                                       tp_rules)
    else:
        repl = replicated(mesh)
        par = jax.tree_util.tree_map(lambda _: repl, net.params)
        upd = jax.tree_util.tree_map(lambda _: repl, net.updater_state)
    if shard_update:
        upd = zero_update_specs(net.updater_state, mesh, axis, base=upd)
    if shard_params:
        par = zero_update_specs(net.params, mesh, axis, base=par)
    return par, upd


def data_parallel_step(net, mesh: Mesh, axis: str = DATA_AXIS, donate=True,
                       shard_update: bool = False,
                       shard_params: bool = False,
                       tp_rules: Optional[Dict[str, P]] = None):
    """Jit a network's train step for synchronous data parallelism.

    Equivalent role to the reference's ``ParallelWrapper`` AVERAGING mode with
    ``averagingFrequency=1`` (``ParallelWrapper.java:551-562``) — except the
    "averaging" is a single fused gradient ``psum`` over ICI emitted by the
    SPMD partitioner, not a host-side barrier + parameter copy.

    Returns a jitted ``step(params, states, upd_state, iteration, rng, f, l,
    fm, lm)`` whose batch inputs must be sharded along ``axis`` (use
    :func:`shard_batch`) and whose params/updater-state follow
    :func:`composed_specs`.

    ``tp_rules`` composes tensor parallelism INTO the same jitted step on a
    2-D ``data × model`` mesh: the rules' param shardings claim the
    ``model`` axis while the batch stays sharded over ``axis`` — DP and TP
    in one XLA computation instead of excluding each other.

    ``shard_update=True`` enables weight-update/optimizer-state sharding
    (ZeRO-1 over the ``data`` axis of whatever mesh is given) — numerically
    identical, ~N× less optimizer memory per device. ``shard_params=True``
    additionally SHARDS THE PARAMETER STORAGE (ZeRO-3/FSDP-style): the SPMD
    partitioner inserts the all-gathers at the points of use and
    reduce-scatters the gradients into the sharded update. Both compose
    with ``tp_rules`` (ZeRO takes the dims TP left free)."""
    raw = net._raw_step(False)
    repl = replicated(mesh)
    data = batch_sharded(mesh, axis)
    par, upd = composed_specs(net, mesh, axis, tp_rules,
                              shard_update, shard_params)
    in_sh = (par, repl, upd, repl, repl, data, data, data, data)
    out_sh = (par, repl, upd, repl)
    record_step("sharding/dp_step", mesh, par, upd,
                zero=shard_update or shard_params)
    return monitored_jit(raw, name="sharding/dp_step",
                         in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0, 2) if donate else ())


def _rnn_state_shardings(net, mesh: Mesh, axis: str):
    """Sharding pytree for a container's RNN/KV stream state: leaves with a
    batch dimension (LSTM (h, c), attention KV cache/positions) are sharded
    along ``axis``; scalars (the attention global token counter) replicate."""
    repl = replicated(mesh)
    data = batch_sharded(mesh, axis)
    template = net._init_rnn_state(1)
    return jax.tree_util.tree_map(
        lambda x: data if getattr(x, "ndim", 0) >= 1 else repl, template)


def data_parallel_tbptt_step(net, mesh: Mesh, axis: str = DATA_AXIS,
                             donate=True, shard_update: bool = False,
                             shard_params: bool = False,
                             tp_rules: Optional[Dict[str, P]] = None):
    """Sharded train step that also threads the detached RNN/KV carry —
    the TBPTT segment step under data parallelism. Reference semantics:
    ``ParallelWrapper`` workers run the full ``MultiLayerNetwork.fit`` loop
    per replica (``trainer/DefaultTrainer.java:244``), truncated-BPTT
    included, so the SPMD equivalent must segment time the same way.
    ``shard_update``/``shard_params``/``tp_rules`` as in
    :func:`data_parallel_step`."""
    raw = net._raw_step(True)
    repl = replicated(mesh)
    data = batch_sharded(mesh, axis)
    state_sh = _rnn_state_shardings(net, mesh, axis)
    par, upd = composed_specs(net, mesh, axis, tp_rules,
                              shard_update, shard_params)
    in_sh = (par, repl, upd, repl, repl, data, data, data, data, state_sh)
    out_sh = (par, repl, upd, repl, state_sh)
    record_step("sharding/dp_tbptt_step", mesh, par, upd,
                zero=shard_update or shard_params)
    return monitored_jit(raw, name="sharding/dp_tbptt_step",
                         in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0, 2) if donate else ())


def data_parallel_tbptt_update_step(net, mesh: Mesh, axis: str = DATA_AXIS):
    """TBPTT segment variant of the SHARED_GRADIENTS update step: returns the
    updater-transformed (un-applied) update plus the detached carry, so the
    host codec seam can encode per segment."""
    raw = net._raw_update_step(with_rnn_state=True)
    repl = replicated(mesh)
    data = batch_sharded(mesh, axis)
    state_sh = _rnn_state_shardings(net, mesh, axis)
    in_sh = (repl, repl, repl, repl, repl, data, data, data, data, state_sh)
    out_sh = (repl, repl, repl, repl, state_sh)
    record_step("sharding/dp_tbptt_update_step", mesh)
    return monitored_jit(raw, name="sharding/dp_tbptt_update_step",
                         in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(2,))


def pvary(x, axis_names):
    """Mark ``x`` as device-varying over ``axis_names`` inside shard_map
    (vma typing)."""
    return jax.lax.pcast(x, tuple(axis_names), to="varying")

"""Pipeline parallelism: GPipe microbatch schedule over a ``pipe`` mesh axis.

Net-new capability vs the 0.9.x reference (SURVEY.md §2.4: only data
parallelism exists there), completing the mesh-axis family alongside tensor
(``parallel/tensor.py``) and sequence (``parallel/sequence.py``) parallelism.

TPU-first design (the standard XLA pipelining pattern, not a thread-per-stage
port): the S pipeline stages must be structurally identical blocks — their
parameters are STACKED on a leading stage axis and sharded across the ``pipe``
mesh axis, so each device holds 1/S of the body parameters. The whole GPipe
schedule — M microbatches flowing through S stages in M+S-1 ticks, activations
hopping stage→stage over ICI via ``ppermute`` — is ONE jitted ``lax.scan``
inside ``shard_map``. Because ``scan``/``ppermute``/``where`` are all
differentiable, reverse-mode AD of the scheduled forward IS the reverse
pipeline schedule (backward bubbles included) — no hand-written backward pass,
the exact analogue of how the containers get backprop from AD.

The homogeneous-stage constraint is the same one production TPU pipelining
makes (stacked transformer blocks); heterogeneous nets pipeline their
homogeneous middle and keep entry/head replicated, which is what
:class:`GPipe` does with its ``head_fn``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
from ..monitor.jitwatch import monitored_jit

from .mesh import PIPELINE_AXIS, record_step, require_axes
from .sharding import pvary

_tm = jax.tree_util.tree_map


def spmd_pipeline(stage_fn: Callable[..., Any],
                  mesh: Mesh, axis: str = PIPELINE_AXIS,
                  data_axis: Optional[str] = None, squeeze_stage: bool = True,
                  _needs_x_grad: bool = False, stateful: bool = False,
                  with_masks: bool = False, with_rng: bool = False):
    """Build ``pipelined(stacked_params, xs) -> ys`` (stateless) or
    ``pipelined(stacked_params, stacked_state, xs) -> (ys, new_state)``
    (``stateful=True``).

    ``with_masks=True`` adds a ``masks`` argument ([M, mb, ...] like ``xs``,
    no stage transform): at tick t, stage s receives the mask of the
    microbatch it is processing (t − s) — how padded-sequence masking rides
    the schedule. ``with_rng=True`` adds a PRNG ``key`` argument; each tick
    hands ``stage_fn`` a key folded per (stage, microbatch), giving
    dropout/weight-noise inside the pipeline the same per-microbatch
    freshness as the container step. The extra arguments are appended to
    ``stage_fn``'s signature in the order (…, x[, mask][, key]).

    ``stacked_params``: pytree whose leaves carry a leading stage dim of
    extent S = mesh.shape[axis] (sharded over ``axis``). ``xs``: microbatches
    ``[M, mb, ...]``. ``stage_fn(params_slice, x) -> y`` — or
    ``stage_fn(params_slice, state_slice, x) -> (y, new_state)`` when
    stateful — must map ``[mb, F] → [mb, F]`` (same shape family every stage
    — the SPMD homogeneity rule). Returns ``ys`` ``[M, mb, ...]``, the last
    stage's outputs, replicated across ``axis``. When ``data_axis`` is given
    the microbatch dim stays sharded over it (combined DP×PP).

    Stateful stages (e.g. BatchNorm running stats) carry their state through
    the GPipe scan: a stage's state advances only on its LIVE ticks (tick t
    processes microbatch t - stage on stage ``stage``), so each stage folds
    its per-microbatch updates in microbatch order — the standard GPipe
    treatment of batch-statistics layers (per-microbatch normalization,
    running stats accumulated across microbatches).

    ``squeeze_stage=True`` (the classic one-block-per-stage case) strips the
    local leading stage dim of extent 1 before calling ``stage_fn``. With
    ``squeeze_stage=False`` the stage dim may pack SEVERAL layers per device
    (leading extent B/S) and ``stage_fn`` receives the whole local slice —
    how ``pipeline_parallel_step`` maps a B-layer homogeneous body onto S
    stages."""
    S = mesh.shape[axis]

    def per_device(params, state, xs, masks, key):
        if squeeze_stage:
            params = _tm(lambda p: p[0], params)  # [1, ...] local slice → stage
            if stateful:
                state = _tm(lambda s: s[0], state)
        idx = lax.axis_index(axis)
        M = xs.shape[0]
        if with_rng and data_axis is not None:
            # decorrelate noise across data shards (the container DP path
            # folds by data-axis index too — wrapper.py's per-worker rng)
            key = jax.random.fold_in(key, lax.axis_index(data_axis))
        if not _needs_x_grad:
            # mark the feed device-varying over the pipe axis. NOT done when
            # upstream (entry) layers need ∂loss/∂xs: pvary's transpose is a
            # psum over 'pipe', which under check_vma=False sees an untyped
            # cotangent and rejects it — and with check_vma=False the
            # varying mark is only documentation anyway.
            xs = pvary(xs, (axis,))
        perm = [(j, (j + 1) % S) for j in range(S)]
        buf0 = jnp.zeros_like(xs[0])

        def tick(carry, t):
            # stage 0 ingests microbatch t (zeros once the feed is drained);
            # everyone else consumes the activation received last tick
            buf, st = carry
            x_t = jnp.where(t < M, xs[jnp.minimum(t, M - 1)],
                            jnp.zeros_like(xs[0]))
            inp = jnp.where(idx == 0, x_t, buf)
            args = [inp]
            mi = jnp.clip(t - idx, 0, M - 1)   # microbatch this stage holds
            if with_masks:
                args.append(None if masks is None
                            else _tm(lambda m: m[mi], masks))
            if with_rng:
                # distinct stream per (stage, microbatch) — folding by mi
                # (not t) keeps a microbatch's noise independent of WHERE in
                # the schedule it meets each stage
                args.append(jax.random.fold_in(jax.random.fold_in(key, idx),
                                               mi))
            if stateful:
                out, st_new = stage_fn(params, st, *args)
                # state advances only while this stage is processing a real
                # microbatch (bubble ticks compute on garbage buffers)
                live = jnp.logical_and(t >= idx, t < idx + M)
                st = _tm(lambda a, b: jnp.where(live, b, a), st, st_new)
            else:
                out = stage_fn(params, *args)
            nxt = lax.ppermute(out, axis, perm)
            return (nxt, st), out

        (_, st_fin), outs = lax.scan(tick, (buf0, state),
                                     jnp.arange(M + S - 1))
        # tick t on the last stage finishes microbatch t-(S-1): ticks
        # S-1 .. M+S-2 are exactly microbatches 0..M-1
        ys = outs[S - 1:]
        ys = lax.psum(jnp.where(idx == S - 1, ys, jnp.zeros_like(ys)), axis)
        if not stateful:
            return ys
        if data_axis is not None:
            # under DP×PP each data shard folded batch statistics from its
            # own microbatch shard only — reconcile by averaging across the
            # data axis (the reference ParallelWrapper's worker-state
            # averaging applied to e.g. BatchNorm running stats), restoring
            # the replication the out-sharding declares
            st_fin = _tm(lambda s: lax.pmean(s, data_axis), st_fin)
        if squeeze_stage:
            st_fin = _tm(lambda s: s[None], st_fin)
        return ys, st_fin

    pspec = _leading_axis_spec(axis)
    xspec = P(None, data_axis) if data_axis else P()
    repl = P()

    def wrapper(params, *rest):
        i = 0
        state = rest[i] if stateful else {}
        i += int(stateful)
        xs = rest[i]
        i += 1
        masks = rest[i] if with_masks else None
        i += int(with_masks)
        key = rest[i] if with_rng else None
        return per_device(params, state, xs, masks, key)

    specs = ([pspec] + ([pspec] if stateful else []) + [xspec]
             + ([xspec] if with_masks else [])
             + ([repl] if with_rng else []))
    out_specs = (xspec, pspec) if stateful else xspec
    return shard_map(wrapper, mesh=mesh, in_specs=tuple(specs),
                     out_specs=out_specs, check_vma=False)


def _leading_axis_spec(axis: str):
    """PartitionSpec pytree-prefix: shard every leaf's leading dim."""
    return P(axis)


def stack_stage_params(per_stage_params) -> Any:
    """Stack a list of S identical pytrees along a new leading stage axis."""
    return _tm(lambda *leaves: jnp.stack(leaves), *per_stage_params)


class GPipe:
    """GPipe trainer: pipelined homogeneous body + replicated head.

    ``block_fn(block_params, x) -> x`` is one stage; ``head_fn(head_params,
    y_feats, labels) -> scalar mean loss`` closes the step. ``params`` is
    ``{"blocks": stacked-pytree [S, ...], "head": pytree}``. The jitted
    ``train_step`` does fwd + AD bwd (reverse pipeline schedule) + updater +
    apply in one XLA computation, with body params/updater-state sharded over
    ``pipe`` and the head replicated — the same whole-step-compile shape as
    the containers' ``_ensure_step``.
    """

    def __init__(self, block_fn, head_fn, mesh: Mesh, n_microbatches: int,
                 updater, axis: str = PIPELINE_AXIS,
                 data_axis: Optional[str] = None):
        require_axes(mesh, (axis, data_axis), style="GPipe")
        record_step("pipeline/gpipe", mesh,
                    {"blocks": P(axis), "head": P()})
        self.mesh = mesh
        self.axis = axis
        self.data_axis = data_axis
        self.n_microbatches = int(n_microbatches)
        self.updater = updater
        self._pipeline = spmd_pipeline(block_fn, mesh, axis, self.data_axis)
        self._head_fn = head_fn
        self._step = None

    # -- placement --------------------------------------------------------
    def block_sharding(self):
        return NamedSharding(self.mesh, P(self.axis))

    def place(self, params, upd_state=None):
        """device_put params (+ mirrored updater state) onto the mesh:
        blocks stage-sharded, head replicated."""
        repl = NamedSharding(self.mesh, P())
        blk = self.block_sharding()

        def put(tree):
            return {"blocks": _tm(lambda p: jax.device_put(p, blk),
                                  tree["blocks"]),
                    "head": _tm(lambda p: jax.device_put(p, repl),
                                tree["head"])}
        return put(params) if upd_state is None else (put(params),
                                                      put(upd_state))

    # -- the step ----------------------------------------------------------
    def _loss(self, params, x_mb, y_mb):
        feats = self._pipeline(params["blocks"], x_mb)
        # head applied per-microbatch; mean of means == global mean when
        # microbatches are equal-sized
        losses = jax.vmap(lambda f, y: self._head_fn(params["head"], f, y)
                          )(feats, y_mb)
        return jnp.mean(losses)

    def _build_step(self):
        upd = self.updater

        def step(params, upd_state, it, x, y):
            M = self.n_microbatches
            x_mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
            y_mb = y.reshape((M, y.shape[0] // M) + y.shape[1:])
            loss, grads = jax.value_and_grad(self._loss)(params, x_mb, y_mb)
            updates, new_state = upd.apply(upd_state, grads, it)
            new_params = _tm(lambda p, u: p - u, params, updates)
            return new_params, new_state, loss

        repl = NamedSharding(self.mesh, P())
        blk = self.block_sharding()
        tree_sh = {"blocks": blk, "head": repl}
        dsh = (NamedSharding(self.mesh, P(self.data_axis))
               if self.data_axis else repl)
        return monitored_jit(
            step, name="pipeline/step",
            in_shardings=(tree_sh, tree_sh, repl, dsh, dsh),
            out_shardings=(tree_sh, tree_sh, repl),
            donate_argnums=(0, 1))

    def train_step(self, params, upd_state, iteration, x, y):
        """One pipelined training step. Returns (params, upd_state, loss)."""
        if self._step is None:
            self._step = self._build_step()
        it = jnp.asarray(iteration, jnp.int32)
        return self._step(params, upd_state, it, x, y)


# ---------------------------------------------------------------------------
# Container-level pipeline parallelism
# ---------------------------------------------------------------------------
def _layer_confs_equal(a, b):
    import dataclasses
    return (type(a) is type(b)
            and dataclasses.asdict(a) == dataclasses.asdict(b))


def _best_periodic_run(confs, n_stages: int, max_period: int):
    """Longest lag-p periodic run over a list of layer configs, trimmed to a
    multiple of ``p * n_stages``: returns (offset, usable_len, period) with
    usable_len == 0 when nothing fits. Smaller periods win ties."""
    n = len(confs)
    best = (0, 0, 1)                          # (offset, usable_len, period)
    for p in range(1, max(1, min(max_period, n // max(1, n_stages))) + 1):
        j = 0
        while j + p < n:
            if not _layer_confs_equal(confs[j], confs[j + p]):
                j += 1
                continue
            a = j                              # maximal lag-p match run
            while j + p < n and _layer_confs_equal(confs[j], confs[j + p]):
                j += 1
            run = (j + p) - a                  # segment [a, a + run)
            usable = (run // (p * n_stages)) * (p * n_stages)
            if usable > best[1]:
                best = (a, usable, p)
    return best


def partition_network(net, n_stages: int, max_period: int = 8):
    """Find ``(start, length, period)`` of the body to pipeline: the longest
    PERIODIC run of layer configs — ``layers[j] == layers[j + period]``
    throughout — trimmed to the largest multiple of ``period * n_stages``.
    ``period == 1`` is the classic identical-layer stack (LSTM cells);
    ``period > 1`` pipelines repeated BLOCKS (Dense→BatchNorm→…, attention→
    FFN transformer blocks) — each stage then holds the same layer sequence,
    preserving the SPMD stage-homogeneity rule. Everything before the run is
    the replicated entry, everything after (plus any trimmed tail) the
    replicated head. Smaller periods win ties (simplest stage program)."""
    start, body, period = _best_periodic_run(net.conf.layers, n_stages,
                                             max_period)
    if body < n_stages:
        raise ValueError(
            f"No periodic run of ≥ {n_stages} repeated layers/blocks to map "
            f"onto {n_stages} pipeline stages (best: {body} layers at "
            f"{start}). Stack identical middle layers or blocks (e.g. "
            f"TextGenerationLSTM(num_layers=...)) or use fewer stages.")
    return start, body, period


def _graph_consumers(conf):
    """vertex/input name → list of vertex names consuming it."""
    consumers = {}
    for name, ins in conf.vertex_inputs.items():
        for i in ins:
            consumers.setdefault(i, []).append(name)
    return consumers


def partition_graph(cg, n_stages: int, max_period: int = 8):
    """ComputationGraph counterpart of :func:`partition_network`: find the
    best pipelinable CHAIN of layer vertices. A chain is a maximal path
    v₀ → v₁ → … where every vᵢ is a single-input Layer vertex, every
    interior vᵢ has exactly one consumer (no branches escape the chain) and
    none is a network output; the chain's layer configs are then trimmed to
    the longest lag-p periodic run (same rule as the MLN partition).
    Returns (chain_names list, period)."""
    conf = cg.conf
    from ..nn.conf.layers import Layer

    consumers = _graph_consumers(conf)

    def chainable(name):
        v = conf.vertices.get(name)
        return (isinstance(v, Layer)
                and len(conf.vertex_inputs.get(name, ())) == 1
                and name not in conf.network_outputs
                and conf.input_preprocessors.get(name) is None)

    chains, seen = [], set()
    for name in cg.topo:
        if name in seen or not chainable(name):
            continue
        # only start where the predecessor cannot extend the chain backward
        prev = conf.vertex_inputs[name][0]
        if (chainable(prev) and consumers.get(prev, []) == [name]):
            continue
        chain, cur = [name], name
        seen.add(name)
        while True:
            cons = consumers.get(cur, [])
            if len(cons) != 1 or not chainable(cons[0]):
                break
            cur = cons[0]
            chain.append(cur)
            seen.add(cur)
        chains.append(chain)

    best = None                               # (names, period)
    for chain in chains:
        confs = [conf.vertices[n] for n in chain]
        off, ln, p = _best_periodic_run(confs, n_stages, max_period)
        if ln >= n_stages and (best is None or ln > len(best[0])):
            best = (chain[off:off + ln], p)
    if best is None:
        raise ValueError(
            f"No periodic chain of ≥ {n_stages} repeated layer vertices to "
            f"map onto {n_stages} pipeline stages. Pipeline-parallel CGs "
            f"need a linear run of repeated single-input layer vertices "
            f"(e.g. stacked transformer blocks); use fewer stages or "
            f"restructure the graph.")
    return best


def _vertex_eq(a, b):
    """Structural equality of two vertex configs: every vertex/layer conf
    is a dataclass, whose generated ``__eq__`` compares class + fields."""
    return type(a) is type(b) and a == b


def partition_graph_blocks(cg, n_stages: int, max_block: int = 16):
    """Find repeated single-input/single-output SUBGRAPH windows along the
    topo order — the residual-transformer case :func:`partition_graph`'s
    linear-chain rule cannot express (skip connections live INSIDE each
    block: ``x + Attn(LN(x)); x + FFN(LN(x))``).

    A valid body is windows ``W_r = topo[s + r·p : s + (r+1)·p]`` where,
    for every repeat r: (1) vertex configs match offset-wise across
    repeats; (2) each vertex's inputs resolve to the SAME relative
    positions — an in-window offset or the window's single external input
    (window r's external input = window r-1's LAST vertex; window 0's =
    whatever name the pattern references); (3) interior vertices have no
    consumers outside their window, so the last offset is the only spine.
    Returns (body_names, period, template) with ``template`` a list of
    per-offset ``(is_layer, rel_inputs)`` where ``rel_inputs`` entries are
    ``("ext",)`` or ``("in", offset)`` — enough for a stage to execute the
    block without the global DAG. Raises like :func:`partition_graph` when
    nothing qualifies."""
    conf = cg.conf
    from ..nn.conf.layers import Layer

    topo = list(cg.topo)
    consumers = _graph_consumers(conf)
    n = len(topo)

    def window_tmpl(s, p, r, ext):
        """Template of window r = topo[s+r·p : s+(r+1)·p] given its single
        allowed external input name ``ext``; None when invalid."""
        base = s + r * p
        if base + p > n:
            return None
        names = topo[base:base + p]
        index = {nm: j for j, nm in enumerate(names)}
        tmpl = []
        for j, nm in enumerate(names):
            v = conf.vertices.get(nm)
            if v is None or nm in conf.network_outputs \
                    or conf.input_preprocessors.get(nm) is not None:
                return None
            rel = []
            for i_name in conf.vertex_inputs.get(nm, ()):
                if i_name in index:
                    if index[i_name] >= j:
                        return None
                    rel.append(("in", index[i_name]))
                elif i_name == ext:
                    rel.append(("ext",))
                else:
                    return None
            # interior vertices must not leak outside the window (the last
            # offset is the sole spine; its consumers are checked by the
            # caller against the NEXT window)
            if j < p - 1:
                if any(c not in index for c in consumers.get(nm, ())):
                    return None
            tmpl.append((isinstance(v, Layer), tuple(rel)))
        return tmpl

    def spine_pure(s, p, r):
        """Window r's last vertex may only feed window r+1."""
        last = topo[s + r * p + p - 1]
        nxt = set(topo[s + (r + 1) * p:s + (r + 2) * p])
        return all(c in nxt for c in consumers.get(last, ()))

    best = None                               # (start, period, R, template)
    for p in range(1, max_block + 1):
        for s in range(n - p * n_stages + 1):
            # window 0's external input: the single out-of-window name its
            # vertices reference (there must be exactly one)
            names0 = set(topo[s:s + p])
            refs = {i for nm in topo[s:s + p]
                    for i in conf.vertex_inputs.get(nm, ())
                    if i not in names0}
            if len(refs) != 1:
                continue
            ext0 = next(iter(refs))
            tmpl = window_tmpl(s, p, 0, ext0)
            if not tmpl or not any(("ext",) in rel for _, rel in tmpl):
                continue
            R = 1
            while spine_pure(s, p, R - 1):
                base = s + R * p
                t2 = window_tmpl(s, p, R, topo[base - 1])
                if (t2 != tmpl
                        or not all(_vertex_eq(conf.vertices[topo[s + j]],
                                              conf.vertices[topo[base + j]])
                                   for j in range(p))):
                    break
                R += 1
            R = (R // n_stages) * n_stages    # stage homogeneity
            if R >= n_stages and R * p > (0 if best is None
                                          else best[2] * best[1]):
                best = (s, p, R, tmpl)
    if best is None:
        raise ValueError(
            f"No repeated single-input/single-output block pattern of ≥ "
            f"{n_stages} repeats found to map onto {n_stages} pipeline "
            f"stages; stack identical blocks (e.g. TransformerLM(num_blocks"
            f"=...)) or use fewer stages.")
    s, p, R, tmpl = best
    return topo[s:s + R * p], p, tmpl


class _PipelinedBase:
    """Shared machinery for the container-level pipeline trainers
    (:class:`PipelinedNetwork` for MultiLayerNetwork, :class:`PipelinedGraph`
    for ComputationGraph): {entry, blocks, head} placement, the jitted
    donated train step (microbatch split → loss+AD → updater → constraints),
    and the container-layout import/export. Subclasses provide the
    partitioning, the stage/entry/head forward pieces and the loss."""

    def _init_common(self, net, mesh, n_microbatches, axis, data_axis):
        require_axes(mesh, (axis, data_axis), style=type(self).__name__)
        record_step("pipeline/" + type(self).__name__, mesh,
                    {"entry": P(), "blocks": P(axis), "head": P()})
        if int(getattr(net.gc, "iterations", 1) or 1) > 1:
            import logging
            logging.getLogger(__name__).warning(
                "iterations(%s) is ignored under %s; each fit_batch applies "
                "one optimizer iteration", net.gc.iterations,
                type(self).__name__)
        self.net = net
        self.mesh = mesh
        self.axis = axis
        self.data_axis = data_axis
        self.n_microbatches = int(n_microbatches)
        self.n_stages = mesh.shape[axis]
        self.updater = net.gc.updater
        self._step = None
        self.iteration_count = 0
        # per-step dropout/weight-noise stream, seeded like the container
        self._base_key = jax.random.PRNGKey(
            int(getattr(net.gc, "seed", None) or 0))

    def _check_layer_conf(self, where, lc):
        if getattr(lc, "updater", None) is not None:
            raise ValueError(
                f"{where} sets a per-layer updater override; the pipelined "
                f"step trains every partition with the network-level updater")
        if getattr(lc, "aux_loss_weight", 0.0):
            raise ValueError(
                f"{where} ({type(lc).__name__}) produces an activation-"
                f"dependent auxiliary loss (aux_loss_weight="
                f"{lc.aux_loss_weight}); the pipelined step does not collect "
                f"ctx['aux_loss'] — set aux_loss_weight=0 or train "
                f"unpipelined")

    # -- placement ---------------------------------------------------------
    def _shardings(self):
        repl = NamedSharding(self.mesh, P())
        blk = NamedSharding(self.mesh, P(self.axis))
        return {"entry": repl, "blocks": blk, "head": repl}

    def _place(self, tree):
        sh = self._shardings()
        # host round-trip = genuine copy: the jitted step DONATES these
        # buffers, and device_put with an equal sharding can alias — donation
        # must never invalidate the source container's params
        return {k: _tm(lambda p: jax.device_put(np.asarray(p), sh[k]),
                       tree[k])
                for k in tree}

    # -- container-layout import/export ------------------------------------
    def _from_layer_keyed(self, d):
        return self._partition_tree(d)

    def export_params(self):
        """Back to the container's per-layer/vertex keying (for
        ModelSerializer / evaluation on the unpipelined net)."""
        return {k: _tm(np.asarray, v)
                for k, v in self._to_layer_keyed(self.params).items()}

    def export_states(self):
        """Trained layer state (BatchNorm running stats, …) back to the
        container's per-layer/vertex keying."""
        return {k: _tm(np.asarray, v)
                for k, v in self._to_layer_keyed(self.states).items()}

    # -- the shared body stage -------------------------------------------
    def _stage_fn(self, params_slice, state_slice, x, *rest):
        """One pipeline stage = repeats_per_stage repeats of the period-p
        block (leaves carry the local [R/S, ...] repeat dim). ``rest`` is
        (mask, key) — both pipelines stream masks (the MLN's [b, T] mask,
        the CG's propagated body-input mask; None when unmasked); ``key``
        is the per-(stage, microbatch) PRNG key driving dropout/weight
        noise exactly like the container's per-layer keys. Returns the
        activations and the functionally-updated state slice."""
        mask, key = rest
        new_state = {str(l): state_slice[str(l)] for l in range(self.period)}
        for j in range(self.repeats_per_stage):
            for l, impl in enumerate(self.body_impls):
                k = jax.random.fold_in(key, j * self.period + l)
                p_j = _tm(lambda q: q[j], params_slice[str(l)])
                s_j = _tm(lambda q: q[j], new_state[str(l)])
                p_n = impl.noised_params(p_j, True, k)
                x, ns = impl.forward(p_n, s_j, x, train=True, rng=k,
                                     mask=mask, ctx={})
                new_state[str(l)] = _tm(lambda buf, v: buf.at[j].set(v),
                                        new_state[str(l)], ns)
        return x, new_state

    # -- the step ----------------------------------------------------------
    def _build_step(self):
        from ..optimize.updater import normalize_gradients

        gn_mode = self.net.gc.gradient_normalization
        gn_thresh = self.net.gc.gradient_normalization_threshold
        minimize = self.net.gc.minimize
        upd = self.updater
        M = self.n_microbatches

        def step(tree, states, upd_state, it, key, f, l, fm, lm):
            mb = lambda t: _tm(
                lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), t)
            (loss, new_states), grads = jax.value_and_grad(
                self._loss, has_aux=True)(tree, states, mb(f), mb(l),
                                          mb(fm), mb(lm), key)
            if not minimize:
                grads = _tm(lambda g: -g, grads)
            from ..nn.conf import GradientNormalization
            if gn_mode not in (None, GradientNormalization.None_, "none"):
                # per-layer normalization modes must see the container's
                # per-layer grouping, not {entry, blocks, head}
                grads = self._from_layer_keyed(normalize_gradients(
                    self._to_layer_keyed(grads), gn_mode, gn_thresh))
            updates, new_state = upd.apply(upd_state, grads, it)
            new_tree = _tm(lambda p, u: p - u.astype(p.dtype), tree, updates)
            new_tree = self._apply_constraints(new_tree)
            return new_tree, new_states, new_state, loss

        sh = self._shardings()
        repl = NamedSharding(self.mesh, P())
        dsh = (NamedSharding(self.mesh, P(self.data_axis))
               if self.data_axis else repl)
        return monitored_jit(
            step, name="pipeline/container_step",
            in_shardings=(sh, sh, sh, repl, repl, dsh, dsh, dsh, dsh),
            out_shardings=(sh, sh, sh, repl),
            donate_argnums=(0, 1, 2))

    def fit_batch(self, f, l, features_mask=None, labels_mask=None):
        """One pipelined optimizer step on a (features, labels) batch — each
        a single array (MultiLayerNetwork) or tuple of arrays
        (ComputationGraph) whose leading dim divides into
        ``n_microbatches`` equal chunks. Optional masks ride the schedule
        with their microbatch."""
        if self._step is None:
            self._step = self._build_step()
        it = jnp.asarray(self.iteration_count, jnp.int32)
        key = jax.random.fold_in(self._base_key, self.iteration_count)
        f = _tm(jnp.asarray, f)
        l = _tm(jnp.asarray, l)
        fm = _tm(jnp.asarray, features_mask)
        lm = _tm(jnp.asarray, labels_mask)
        self.params, self.states, self.upd_state, loss = self._step(
            self.params, self.states, self.upd_state, it, key, f, l, fm, lm)
        self.iteration_count += 1
        return loss


class PipelinedNetwork(_PipelinedBase):
    """Train a ``MultiLayerNetwork``'s homogeneous middle as GPipe stages
    (VERDICT round-3 item 3: container-level pipeline parallelism).

    The network is partitioned entry | body | head by
    :func:`partition_network` — the body is the longest PERIODIC run of
    layer configs, so stacked identical layers (period 1: LSTM cells) AND
    stacked blocks (period p: Dense→BatchNorm→…, attention→FFN) both
    pipeline. Body layer params are STACKED per in-block offset on a leading
    repeat axis and sharded over the mesh ``pipe`` axis (B/S layers per
    stage), entry/head stay replicated, and the body forward runs through
    :func:`spmd_pipeline` — reverse-mode AD of that schedule is the reverse
    pipeline, exactly like :class:`GPipe`. Combined DP×PP: pass a mesh with
    a ``data`` axis too and the (micro)batch dim stays sharded over it.

    STATEFUL layers (BatchNorm running stats, CenterLoss centers) are
    supported everywhere (v2): body state rides the GPipe scan (advancing
    only on live ticks), entry/head apply per microbatch via ``lax.scan``
    threading state in microbatch order. Note the GPipe-standard semantics:
    batch statistics are computed PER MICROBATCH (running stats fold across
    microbatches in order), which intentionally differs from the
    full-batch statistics of the unpipelined step.

    Container-step semantics carried over: l1/l2 regularization,
    ``minimize=False`` (sign flip), gradient normalization, per-layer
    parameter constraints after each update, [b, T] feature/label MASKS
    (each microbatch's mask rides the schedule with it), and dropout/
    weight-noise (per-(stage, microbatch, layer) folded keys — same
    freshness as the container's per-layer keys). Remaining constraints
    (checked loudly): no per-layer updater overrides, no preprocessors
    inside the body run; ``iterations(n)`` is ignored (one update per
    ``fit_batch``, like ParallelWrapper).
    """

    def __init__(self, net, mesh: Mesh, n_microbatches: int,
                 axis: str = PIPELINE_AXIS, data_axis: Optional[str] = None):
        if not hasattr(net.conf, "layers"):
            raise ValueError("PipelinedNetwork supports MultiLayerNetwork; "
                             "ComputationGraph pipelines via PipelinedGraph")
        for i, lc in enumerate(net.conf.layers):
            self._check_layer_conf(f"layer {i}", lc)
        self._init_common(net, mesh, n_microbatches, axis, data_axis)
        S = self.n_stages
        self.start, self.body_len, self.period = partition_network(net, S)
        self.layers_per_stage = self.body_len // S
        self.repeats_per_stage = self.layers_per_stage // self.period
        self.body_impls = [net.impls[self.start + l]
                           for l in range(self.period)]
        for i in range(self.start, self.start + self.body_len):
            if net.conf.preprocessor(i) is not None:
                raise ValueError("preprocessors inside the pipelined body "
                                 "are not supported")
        self._pipeline = spmd_pipeline(self._stage_fn, mesh, axis, data_axis,
                                       squeeze_stage=False,
                                       _needs_x_grad=self.start > 0,
                                       stateful=True, with_masks=True,
                                       with_rng=True)
        # partitioned + placed params/states and mirrored updater state
        self.params = self._place(self._partition_tree(net.params))
        self.states = self._place(self._partition_tree(net.states))
        self.upd_state = self._place(
            self.updater.init_state(self.params))

    # -- param/state layout ------------------------------------------------
    def _partition_tree(self, net_tree):
        """Container {layer-index: tree} → {entry, blocks, head}: body
        layers grouped by in-block offset l (0..period-1), stacked across
        the R = body/period repeats on a leading axis (sharded over
        ``pipe``)."""
        s, b, p = self.start, self.body_len, self.period
        n = len(self.net.impls)
        entry = {str(i): net_tree[str(i)] for i in range(s)}
        head = {str(i): net_tree[str(i)] for i in range(s + b, n)}
        blocks = {str(l): stack_stage_params(
            [net_tree[str(s + r * p + l)] for r in range(b // p)])
            for l in range(p)}
        return {"entry": entry, "blocks": blocks, "head": head}

    # -- forward pieces ----------------------------------------------------
    def _entry_apply(self, params, states, f_mb, fm_mb, keys_mb):
        """Entry layers over the [M, mb, ...] microbatches. Stateless entry
        (the common case) applies as ONE vmapped computation; a stateful
        entry (BatchNorm running stats) goes through ``lax.scan`` so state
        threads through microbatches in order, matching the body's
        live-tick order."""
        s = self.start

        def step(st, xmk):
            x, m, k = xmk
            ctx = {}
            new_st = dict(st)
            for i in range(s):
                ki = jax.random.fold_in(k, i)
                pre = self.net.conf.preprocessor(i)
                if pre is not None:
                    x = pre(x, ctx)
                impl = self.net.impls[i]
                p_n = impl.noised_params(params[str(i)], True, ki)
                x, ns = impl.forward(p_n, st[str(i)], x, train=True, rng=ki,
                                     mask=m, ctx=ctx)
                new_st[str(i)] = ns
            return new_st, x

        if not jax.tree_util.tree_leaves(states):
            return states, jax.vmap(
                lambda x, m, k: step(states, (x, m, k))[1],
                in_axes=(0, None if fm_mb is None else 0, 0))(
                    f_mb, fm_mb, keys_mb)
        return lax.scan(step, states, (f_mb, fm_mb, keys_mb))

    def _head_apply(self, params, states, feats, l_mb, fm_mb, lm_mb,
                    keys_mb):
        """Head layers + output loss per microbatch; returns
        (final head state, per-microbatch losses). Stateless head → one
        vmapped computation; stateful → scan threading state in microbatch
        order (see :meth:`_entry_apply`)."""
        net, s, b = self.net, self.start, self.body_len
        n = len(net.impls)
        out_impl = net.impls[-1]

        def step(st, xy):
            x, l, fm, lm, k = xy
            ctx = {}
            new_st = dict(st)
            for i in range(s + b, n - 1):
                ki = jax.random.fold_in(k, i)
                pre = net.conf.preprocessor(i)
                if pre is not None:
                    x = pre(x, ctx)
                impl = net.impls[i]
                p_n = impl.noised_params(params[str(i)], True, ki)
                x, ns = impl.forward(p_n, st[str(i)], x, train=True, rng=ki,
                                     mask=fm, ctx=ctx)
                new_st[str(i)] = ns
            pre = net.conf.preprocessor(n - 1)
            if pre is not None:
                x = pre(x, ctx)
            # container mask rule (MultiLayerNetwork._loss_fn): label mask,
            # else the feature mask for sequence outputs
            mask = lm if lm is not None else (fm if x.ndim == 3 else None)
            loss = out_impl.loss_on(params[str(n - 1)], st[str(n - 1)], x, l,
                                    mask=mask, train=True,
                                    rng=jax.random.fold_in(k, n - 1))
            if hasattr(out_impl, "update_state"):
                # e.g. CenterLoss EMA centers — updated outside AD
                new_st[str(n - 1)] = out_impl.update_state(
                    st[str(n - 1)], jax.lax.stop_gradient(x), l)
            return new_st, loss

        if not jax.tree_util.tree_leaves(states):
            return states, jax.vmap(
                lambda x, l, fm, lm, k: step(states, (x, l, fm, lm, k))[1],
                in_axes=(0, 0, None if fm_mb is None else 0,
                         None if lm_mb is None else 0, 0))(
                    feats, l_mb, fm_mb, lm_mb, keys_mb)
        return lax.scan(step, states, (feats, l_mb, fm_mb, lm_mb, keys_mb))

    def _loss(self, tree, states, f_mb, l_mb, fm_mb, lm_mb, key):
        s, b, p = self.start, self.body_len, self.period
        M = f_mb.shape[0]
        S = self.n_stages
        # disjoint streams: body stages fold (idx < S, mi); entry/head fold
        # (S, m) / (S + 1, m)
        ek = jax.random.split(jax.random.fold_in(key, S), M)
        hk = jax.random.split(jax.random.fold_in(key, S + 1), M)
        entry_st, entry = self._entry_apply(tree["entry"], states["entry"],
                                            f_mb, fm_mb, ek)
        feats, blocks_st = self._pipeline(tree["blocks"], states["blocks"],
                                          entry, fm_mb, key)
        head_st, losses = self._head_apply(tree["head"], states["head"],
                                           feats, l_mb, fm_mb, lm_mb, hk)
        # mean of per-microbatch means == global mean (equal-size chunks)
        loss = jnp.mean(losses)
        # l1/l2 (param-only → computable per partition; keeps loss parity
        # with MultiLayerNetwork._loss_fn's reg term)
        reg = 0.0
        n = len(self.net.impls)
        for i in range(s):
            reg = reg + self.net.impls[i].regularization(
                tree["entry"][str(i)])
        for r in range(b // p):   # unrolled: regularization may be plain 0.0
            for l in range(p):
                reg = reg + self.body_impls[l].regularization(
                    _tm(lambda q: q[r], tree["blocks"][str(l)]))
        for i in range(s + b, n):
            reg = reg + self.net.impls[i].regularization(tree["head"][str(i)])
        new_states = {"entry": entry_st, "blocks": blocks_st,
                      "head": head_st}
        return loss + reg, new_states

    # -- the step ----------------------------------------------------------
    def _to_layer_keyed(self, tree):
        """{entry|blocks|head} tree → the container's per-layer-index keying
        (body repeats unstacked) so per-layer gradient-normalization modes
        see the same grouping as MultiLayerNetwork."""
        s, b, p = self.start, self.body_len, self.period
        n = len(self.net.impls)
        out = {str(i): tree["entry"][str(i)] for i in range(s)}
        for r in range(b // p):
            for l in range(p):
                out[str(s + r * p + l)] = _tm(lambda q: q[r],
                                              tree["blocks"][str(l)])
        out.update({str(i): tree["head"][str(i)] for i in range(s + b, n)})
        return out

    def _layer_constraints(self, i):
        lc = self.net.conf.layers[i]
        return getattr(lc, "constraints", None) or \
            getattr(getattr(lc, "inner", None), "constraints", None)

    def fit_batch(self, f, l, features_mask=None, labels_mask=None):
        """One pipelined step; user-facing conv features are NCHW and
        adapted to internal NHWC exactly like ``MultiLayerNetwork.fit``.
        ``features_mask``/``labels_mask``: [b, T] sequence masks — streamed
        through every entry/body/head layer and the output loss, same
        semantics as the container's masked ``fit``."""
        return super().fit_batch(self.net._adapt_input(jnp.asarray(f)), l,
                                 features_mask, labels_mask)

    def _apply_constraints(self, tree):
        """Per-layer parameter constraints after each update — same timing
        as the containers' ``_apply_constraints``. Body constraints apply
        per STAGE SLICE (norms must not mix layers across the stacked dim)."""
        from ..nn.conf.dropout import apply_constraints

        s, b, p = self.start, self.body_len, self.period
        n = len(self.net.impls)
        out = {"entry": dict(tree["entry"]), "blocks": dict(tree["blocks"]),
               "head": dict(tree["head"])}
        for i in list(range(s)) + list(range(s + b, n)):
            cons = self._layer_constraints(i)
            if cons:
                part = "entry" if i < s else "head"
                out[part][str(i)] = apply_constraints(cons,
                                                      out[part][str(i)])
        for l in range(p):
            cons = self._layer_constraints(self.start + l)
            if cons:
                # per REPEAT slice: norms must not mix layers across the
                # stacked repeat dim
                per_rep = [apply_constraints(cons,
                                             _tm(lambda q: q[r],
                                                 tree["blocks"][str(l)]))
                           for r in range(b // p)]
                out["blocks"][str(l)] = stack_stage_params(per_rep)
        return out


class PipelinedGraph(_PipelinedBase):
    """Pipeline-parallel training for a ``ComputationGraph``: the best
    periodic CHAIN of single-input layer vertices (found by
    :func:`partition_graph` — e.g. stacked transformer blocks) becomes the
    GPipe body; the rest of the DAG splits into the replicated entry
    (everything the body does NOT depend on transitively downstream) and the
    replicated head (everything downstream of the chain end), so skip
    connections AROUND the body and multi-input/multi-output graphs work.
    Entry/head run per microbatch (vmapped when stateless, scanned when
    stateful); losses follow the container's multi-output sum with the
    fused-softmax skip. Same GPipe-standard caveat as
    :class:`PipelinedNetwork`: batch statistics are per microbatch."""

    def __init__(self, net, mesh: Mesh, n_microbatches: int,
                 axis: str = PIPELINE_AXIS, data_axis: Optional[str] = None):
        conf = net.conf
        if not hasattr(conf, "vertices"):
            raise ValueError("PipelinedGraph needs a ComputationGraph")
        from ..nn.conf.layers import Layer

        for name, v in conf.vertices.items():
            if isinstance(v, Layer):
                self._check_layer_conf(f"vertex '{name}'", v)
        self._init_common(net, mesh, n_microbatches, axis, data_axis)
        try:
            self.body, self.period = partition_graph(net, self.n_stages)
            self.body_tmpl = None            # linear chain of layer vertices
        except ValueError as chain_err:
            # residual-transformer case: repeated single-input/single-output
            # SUBGRAPH blocks (skip connections inside each block)
            try:
                self.body, self.period, self.body_tmpl = \
                    partition_graph_blocks(net, self.n_stages)
            except ValueError as block_err:
                raise ValueError(
                    f"Neither pipelining rule fits this graph.\n"
                    f"- linear chain: {chain_err}\n"
                    f"- block pattern: {block_err}") from block_err
        self.body_len = len(self.body)
        self.layers_per_stage = self.body_len // self.n_stages
        self.repeats_per_stage = self.layers_per_stage // self.period
        self.body_impls = [net.impls.get(n) for n in self.body[:self.period]]
        # masks through a block body need every vertex to propagate "first
        # (non-None) input mask" — true for the default rule and Merge;
        # Stack/Unstack/Reshape transform masks and are rejected at fit time
        from ..nn.conf.graph import GraphVertexConf, MergeVertex
        self._block_masks_ok = self.body_tmpl is None or all(
            is_layer or type(conf.vertices[self.body[off]]).propagate_mask
            in (GraphVertexConf.propagate_mask, MergeVertex.propagate_mask)
            for off, (is_layer, _) in enumerate(self.body_tmpl))
        body_set = set(self.body)
        # head = everything downstream of the chain end; entry = the rest
        consumers = _graph_consumers(conf)
        reach, stack = set(), [self.body[-1]]
        while stack:
            for c in consumers.get(stack.pop(), ()):
                if c not in reach:
                    reach.add(c)
                    stack.append(c)
        self.head_names = [n for n in net.topo
                           if n in reach and n not in body_set]
        self.entry_names = [n for n in net.topo
                            if n not in reach and n not in body_set]
        self.body_input = conf.vertex_inputs[self.body[0]][0]
        from ..nn.graph import fused_softmax_skip_set
        self._skip_outputs = fused_softmax_skip_set(conf, net.impls)
        # outputs NOT downstream of the body (auxiliary heads fed from the
        # entry): loss still computed, but their params/state live in the
        # entry tree. An entry-side output with running state updates
        # (update_state, e.g. CenterLoss) cannot update exactly per
        # microbatch from the head pass — reject loudly.
        self._entry_outputs = frozenset(n for n in conf.network_outputs
                                        if n not in reach
                                        and n not in body_set)
        for n in self._entry_outputs:
            impl = net.impls.get(n)
            if (impl is not None and hasattr(impl, "update_state")
                    and jax.tree_util.tree_leaves(net.states.get(n, {}))):
                raise ValueError(
                    f"auxiliary output '{n}' on the entry side carries "
                    f"running state (update_state); train unpipelined or "
                    f"restructure so it sits downstream of the body")
        self._pipeline = spmd_pipeline(self._stage_fn, mesh, axis, data_axis,
                                       squeeze_stage=False,
                                       _needs_x_grad=True, stateful=True,
                                       with_masks=True, with_rng=True)
        self.params = self._place(self._partition_tree(net.params))
        self.states = self._place(self._partition_tree(net.states))
        self.upd_state = self._place(self.updater.init_state(self.params))

    # -- param/state layout ------------------------------------------------
    def _layer_offsets(self):
        """Body offsets that are LAYER vertices (all of them for a chain
        body; the template's layer entries for a block body) — the offsets
        that own params/state."""
        if self.body_tmpl is None:
            return list(range(self.period))
        return [off for off, (is_layer, _) in enumerate(self.body_tmpl)
                if is_layer]

    def _partition_tree(self, net_tree):
        p = self.period
        entry = {n: net_tree[n] for n in self.entry_names
                 if n in net_tree}
        head = {n: net_tree[n] for n in self.head_names if n in net_tree}
        blocks = {str(l): stack_stage_params(
            [net_tree[self.body[r * p + l]]
             for r in range(self.body_len // p)])
            for l in self._layer_offsets()
            if self.body[l] in net_tree}
        return {"entry": entry, "blocks": blocks, "head": head}

    def _to_layer_keyed(self, tree):
        p = self.period
        out = dict(tree["entry"])
        for r in range(self.body_len // p):
            for l in self._layer_offsets():
                if str(l) in tree["blocks"]:
                    out[self.body[r * p + l]] = _tm(lambda q: q[r],
                                                    tree["blocks"][str(l)])
        out.update(tree["head"])
        return out

    # -- the block-body stage ---------------------------------------------
    def _stage_fn(self, params_slice, state_slice, x, *rest):
        """Chain bodies use the shared linear stage; a BLOCK body executes
        its template sub-DAG per repeat — in-window vertices resolve their
        inputs by relative offset, the window's single external input is
        the carried activation, and only layer offsets carry stacked
        params/state."""
        if self.body_tmpl is None:
            return super()._stage_fn(params_slice, state_slice, x, *rest)
        mask, key = rest
        conf = self.net.conf
        new_state = {k: state_slice[k] for k in state_slice}
        for j in range(self.repeats_per_stage):
            vals = {}
            for off, (is_layer, rel) in enumerate(self.body_tmpl):
                xs = [x if r[0] == "ext" else vals[r[1]] for r in rel]
                name0 = self.body[off]          # template (window-0) name
                if is_layer:
                    impl = self.net.impls[name0]
                    k = jax.random.fold_in(key, j * self.period + off)
                    p_j = _tm(lambda q: q[j], params_slice[str(off)])
                    s_j = (_tm(lambda q: q[j], new_state[str(off)])
                           if str(off) in new_state else {})
                    p_n = impl.noised_params(p_j, True, k)
                    y, ns = impl.forward(p_n, s_j, xs[0], train=True,
                                         rng=k, mask=mask, ctx={})
                    if str(off) in new_state:
                        new_state[str(off)] = _tm(
                            lambda buf, v: buf.at[j].set(v),
                            new_state[str(off)], ns)
                    vals[off] = y
                else:
                    vals[off] = conf.vertices[name0].forward(xs, {})
            x = vals[self.period - 1]
        return x, new_state

    # -- forward pieces ----------------------------------------------------
    def _apply_vertices(self, names, params, states, acts, masks, ctx, key):
        """Run the given vertices (already topo-ordered) functionally over
        ``acts``; returns (acts, masks, new_states) for the sub-DAG. ``key``
        seeds per-vertex dropout/weight-noise streams (folded by position).
        ``masks`` propagates [b, T] sequence masks exactly like
        ``ComputationGraph._apply_graph`` (layers carry their single input's
        mask; vertices combine via ``propagate_mask``)."""
        from ..nn.conf.layers import Layer

        conf = self.net.conf
        new_st = dict(states)
        acts = dict(acts)
        masks = dict(masks)
        for pos, name in enumerate(names):
            if name in self._skip_outputs:
                continue
            v = conf.vertices[name]
            in_names = conf.vertex_inputs[name]
            xs = [acts[i] for i in in_names]
            if isinstance(v, Layer):
                x = xs[0]
                pre = conf.input_preprocessors.get(name)
                if pre is not None:
                    x = pre(x, ctx)
                m = masks.get(in_names[0])
                impl = self.net.impls[name]
                k = jax.random.fold_in(key, pos)
                p_n = impl.noised_params(params[name], True, k)
                y, ns = impl.forward(p_n, states[name], x,
                                     train=True, rng=k, mask=m,
                                     ctx=ctx)
                new_st[name] = ns
                acts[name] = y
                masks[name] = m
            else:
                acts[name] = v.forward(xs, ctx)
                masks[name] = v.propagate_mask(
                    [masks.get(i) for i in in_names])
        return acts, masks, new_st

    def _entry_apply(self, params, states, inputs_mb, fm_mb, keys_mb):
        """Entry sub-DAG per microbatch → stacked activations AND propagated
        masks for every entry vertex (the head may consume any of them —
        skip connections around the body). ``fm_mb``: per-network-input
        [M, mb, T] masks (or None)."""
        conf = self.net.conf
        n_in = len(conf.network_inputs)

        def step(st, xk):
            inputs, in_masks, k = xk
            acts = dict(zip(conf.network_inputs, inputs))
            masks = dict(zip(conf.network_inputs,
                             in_masks or [None] * n_in))
            ctx = {"inputs": acts, "input_masks": masks}
            acts, masks, new_st = self._apply_vertices(
                self.entry_names, params, st, acts, masks, ctx, k)
            return new_st, (acts, masks)

        if not jax.tree_util.tree_leaves(states):
            acts, masks = jax.vmap(
                lambda i, m, k: step(states, (i, m, k))[1])(
                    inputs_mb, fm_mb, keys_mb)
            return states, acts, masks
        st, (acts, masks) = lax.scan(step, states,
                                     (inputs_mb, fm_mb, keys_mb))
        return st, acts, masks

    def _head_apply(self, params, states, entry_params, entry_states,
                    entry_acts, entry_masks, feats, l_mb, lm_mb, keys_mb):
        """Head sub-DAG + the container's multi-output summed loss per
        microbatch; returns (final head state, per-microbatch losses).
        Entry-side auxiliary outputs resolve their params from
        ``entry_params`` (their state is empty — checked at construction).
        ``entry_masks``: per-microbatch propagated masks of the entry
        vertices; the body is a chain of layers so its output carries the
        body input's mask unchanged (``_apply_graph``'s layer rule)."""
        conf = self.net.conf
        impls = self.net.impls

        def step(st, xy):
            acts, in_masks, feat, labels, lmasks, key = xy
            acts = dict(acts)
            acts[self.body[-1]] = feat
            masks = dict(in_masks)
            masks[self.body[-1]] = in_masks.get(self.body_input)
            ctx = {"inputs": {k: acts.get(k) for k in conf.network_inputs},
                   "input_masks": {k: masks.get(k)
                                   for k in conf.network_inputs}}
            acts, masks, new_st = self._apply_vertices(
                self.head_names, params, st, acts, masks, ctx, key)
            total = 0.0
            for oi, (out_name, lbl) in enumerate(zip(conf.network_outputs,
                                                     labels)):
                impl = impls.get(out_name)
                if impl is None or not hasattr(impl, "loss_on"):
                    raise ValueError(f"Output vertex '{out_name}' is not an "
                                     f"output layer")
                entry_side = out_name in self._entry_outputs
                p_o = (entry_params if entry_side else params)[out_name]
                s_o = (entry_states if entry_side else st)[out_name]
                in_name = conf.vertex_inputs[out_name][0]
                x = acts[in_name]
                pre = conf.input_preprocessors.get(out_name)
                if pre is not None:
                    x = pre(x, ctx)
                # container mask rule (ComputationGraph._loss_fn): label
                # mask, else the propagated mask for sequence outputs
                lm = None if lmasks is None else lmasks[oi]
                mask = lm if lm is not None else (
                    masks.get(in_name) if x.ndim == 3 else None)
                ko = jax.random.fold_in(key, len(self.head_names) + oi)
                total = total + impl.loss_on(p_o, s_o, x, lbl, mask=mask,
                                             train=True, rng=ko)
                if not entry_side and hasattr(impl, "update_state"):
                    new_st[out_name] = impl.update_state(
                        s_o, jax.lax.stop_gradient(x), lbl)
            return new_st, total

        if not jax.tree_util.tree_leaves(states):
            return states, jax.vmap(
                lambda a, m, f, l, lm, k: step(states,
                                               (a, m, f, l, lm, k))[1])(
                    entry_acts, entry_masks, feats, l_mb, lm_mb, keys_mb)
        return lax.scan(step, states, (entry_acts, entry_masks, feats, l_mb,
                                       lm_mb, keys_mb))

    def _loss(self, tree, states, inputs_mb, labels_mb, fm_mb, lm_mb, key):
        p = self.period
        M = inputs_mb[0].shape[0]
        S = self.n_stages
        ek = jax.random.split(jax.random.fold_in(key, S), M)
        hk = jax.random.split(jax.random.fold_in(key, S + 1), M)
        entry_st, entry_acts, entry_masks = self._entry_apply(
            tree["entry"], states["entry"], inputs_mb, fm_mb, ek)
        feats, blocks_st = self._pipeline(tree["blocks"], states["blocks"],
                                          entry_acts[self.body_input],
                                          entry_masks.get(self.body_input),
                                          key)
        head_st, losses = self._head_apply(tree["head"], states["head"],
                                           tree["entry"], states["entry"],
                                           entry_acts, entry_masks, feats,
                                           labels_mb, lm_mb, hk)
        loss = jnp.mean(losses)
        reg = 0.0
        for part, names in (("entry", self.entry_names),
                            ("head", self.head_names)):
            for n in names:
                impl = self.net.impls.get(n)
                if impl is not None:
                    reg = reg + impl.regularization(tree[part][n])
        for r in range(self.body_len // p):
            for l in self._layer_offsets():
                if str(l) in tree["blocks"]:
                    reg = reg + self.body_impls[l].regularization(
                        _tm(lambda q: q[r], tree["blocks"][str(l)]))
        return loss + reg, {"entry": entry_st, "blocks": blocks_st,
                            "head": head_st}

    def _apply_constraints(self, tree):
        from ..nn.conf.dropout import apply_constraints

        def cons_of(name):
            v = self.net.conf.vertices[name]
            return getattr(v, "constraints", None) or \
                getattr(getattr(v, "inner", None), "constraints", None)

        out = {"entry": dict(tree["entry"]), "blocks": dict(tree["blocks"]),
               "head": dict(tree["head"])}
        for part in ("entry", "head"):
            for n in list(out[part]):
                cons = cons_of(n)
                if cons:
                    out[part][n] = apply_constraints(cons, out[part][n])
        for l in self._layer_offsets():
            cons = cons_of(self.body[l])
            if cons and str(l) in tree["blocks"]:
                per_rep = [apply_constraints(cons,
                                             _tm(lambda q: q[r],
                                                 tree["blocks"][str(l)]))
                           for r in range(self.body_len // self.period)]
                out["blocks"][str(l)] = stack_stage_params(per_rep)
        return out

    def fit_batch(self, inputs, labels, features_mask=None,
                  labels_mask=None):
        """One pipelined step; ``inputs``/``labels`` are tuples of arrays
        (the ComputationGraph convention) — single arrays are wrapped.
        ``features_mask``/``labels_mask``: per-input / per-output [b, T]
        sequence masks (single arrays wrapped), propagated through
        entry/body/head with ``ComputationGraph._apply_graph``'s rules and
        applied to each output loss — same semantics as the container's
        masked ``fit``. User-facing conv inputs are NCHW (the container
        boundary rule) and adapted to internal NHWC exactly like
        ``ComputationGraph.fit``."""
        def as_tuple(t):
            return None if t is None else (
                tuple(t) if isinstance(t, (tuple, list)) else (t,))

        inputs = as_tuple(inputs)
        labels = as_tuple(labels)
        fm = as_tuple(features_mask)
        lm = as_tuple(labels_mask)
        if (fm is not None or lm is not None) and not self._block_masks_ok:
            raise ValueError(
                "this pipelined body contains a vertex whose mask "
                "propagation is not the identity (Stack/Unstack/Reshape "
                "class); masked training through the block pipeline would "
                "silently diverge — train unpipelined")
        if fm is not None and len(fm) != len(self.net.conf.network_inputs):
            raise ValueError(f"features_mask needs one entry per network "
                             f"input ({len(self.net.conf.network_inputs)})")
        if lm is not None and len(lm) != len(self.net.conf.network_outputs):
            raise ValueError(f"labels_mask needs one entry per network "
                             f"output ({len(self.net.conf.network_outputs)})")
        inputs = self.net._adapt_inputs(tuple(jnp.asarray(i)
                                              for i in inputs))
        return super().fit_batch(tuple(inputs), tuple(labels), fm, lm)


def pipeline_parallel_step(net, mesh: Mesh, n_microbatches: int = 4,
                           axis: str = PIPELINE_AXIS,
                           data_axis: Optional[str] = None):
    """Container-level entry: partition ``net``'s homogeneous middle into
    GPipe stages over ``mesh[axis]`` and return a :class:`PipelinedNetwork`
    (MultiLayerNetwork) or :class:`PipelinedGraph` (ComputationGraph) ready
    to ``fit_batch``. (Reference frame: the reference has no pipeline
    parallelism at all — SURVEY.md §2.4; this is the net-new ``pp`` member
    of the dp/tp/pp/sp/ep family, reachable from BOTH real containers
    instead of hand-written block functions.)"""
    if hasattr(net.conf, "vertices"):
        return PipelinedGraph(net, mesh, n_microbatches, axis, data_axis)
    return PipelinedNetwork(net, mesh, n_microbatches, axis, data_axis)

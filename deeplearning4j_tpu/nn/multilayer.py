"""MultiLayerNetwork: sequential network container.

TPU-native equivalent of reference ``nn/multilayer/MultiLayerNetwork.java``
(3156 LoC; ``fit`` :1156, ``feedForwardToLayer`` :903, ``computeGradientAndScore``
:2206, ``backprop`` :1267, TBPTT :1219).

Architectural shift (SURVEY.md §7): the reference executes op-by-op over JNI with a
mutable flattened param buffer (``:110/:601/:615``) and hand-written backprop; here
the whole step — forward, loss, AD backward, gradient normalization, updater, and
parameter update — is ONE jitted XLA computation with params/updater-state/layer-state
donated (the functional realization of the reference's in-place
``stepFunction.step``, ``StochasticGradientDescent.java:79``). Workspaces/CacheMode
(§2.8 item 3) collapse into XLA buffer donation + executable caching, which jit
gives us for free.

Training state (BN running stats, RNN streaming state) is explicit: ``states``
pytree and the TBPTT carry, replacing the reference's mutable layer fields.
"""
from __future__ import annotations

import contextlib
import logging
from functools import partial
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .conf import (MultiLayerConfiguration, BackpropType, CacheMode,
                   GradientNormalization)
from .conf.inputs import (InputTypeConvolutional, InputTypeConvolutionalFlat,
                          InputTypeRecurrent)
from jax.ad_checkpoint import checkpoint_name

from .layers import impl_for
from .layers.base import remat_enabled, remat_policy
from .layers.recurrent import _BaseLSTMImpl
from ..datasets.dataset import DataSet, DataSetIterator, ListDataSetIterator
from ..datasets.prefetch import wrap_for_training
from ..optimize.updater import NetworkUpdater, normalize_gradients
from .. import monitor as _mon
from ..monitor.jitwatch import monitored_jit

log = logging.getLogger(__name__)

_tm = jax.tree_util.tree_map


def _n_iterations(gc):
    """Configured optimizer iterations per minibatch/segment (0.9.x
    ``iterations`` config), with the legacy-config fallback in ONE place."""
    return int(getattr(gc, "iterations", 1) or 1)


def _scan_iterations(step, n_iter, with_rnn_state=False):
    """Wrap a train-step fn in a ``lax.scan`` running ``n_iter`` optimizer
    iterations on the SAME minibatch inside one compiled program — the
    TPU-native realization of the reference's 0.9.x ``iterations`` config
    (``NeuralNetConfiguration.Builder.iterations``): small-model training
    pays the dispatch latency once per n steps. Same signature as ``step``;
    the iteration counter advances per scanned step and the rng is split so
    dropout differs across iterations; returns the LAST loss (and, on the
    TBPTT variant, the last rnn state — every iteration of a segment starts
    from the same carried-in state, reference solver-per-segment
    semantics)."""
    def scanned(params, states, upd_state, iteration, rng, f, l, fm, lm,
                rnn_state_in=None):
        def body(carry, i):
            params, states, upd_state, rng = carry
            rng, key = jax.random.split(rng)
            out = step(params, states, upd_state, iteration + i, key, f, l,
                       fm, lm, rnn_state_in)
            params, states, upd_state, loss = out[:4]
            extra = out[4] if with_rnn_state else None
            return (params, states, upd_state, rng), (loss, extra)
        (params, states, upd_state, _), (losses, extras) = jax.lax.scan(
            body, (params, states, upd_state, rng),
            jnp.arange(n_iter, dtype=jnp.int32))
        if with_rnn_state:
            last_rnn = _tm(lambda x: x[-1], extras)
            return params, states, upd_state, losses[-1], last_rnn
        return params, states, upd_state, losses[-1]
    return scanned


def _build_tbptt_scan(step, n_iter):
    """Jit a with-rnn-state train step into ONE program running the whole
    TBPTT segment loop (``lax.scan`` over stacked segments, params/updater/
    RNN state carried, segments detached by the step itself). Shared by
    MultiLayerNetwork AND ComputationGraph so the two containers' fused
    TBPTT semantics cannot drift. Inputs are segment-stacked pytrees
    ``[S, ...]`` (tuples of streams for the graph container ride through
    untouched — scan maps over every leaf's leading dim)."""
    if n_iter > 1:
        step = _scan_iterations(step, n_iter, with_rnn_state=True)

    def scanned(params, states, upd, it0, rng, f_s, l_s, fm_s, lm_s, rnn0):
        def body(carry, xs):
            params, states, upd, rnn, s = carry
            f_c, l_c, fm_c, lm_c = xs
            params, states, upd, loss, rnn = step(
                params, states, upd, it0 + s * n_iter,
                jax.random.fold_in(rng, s), f_c, l_c, fm_c, lm_c, rnn)
            return (params, states, upd, rnn, s + 1), loss

        init = (params, states, upd, rnn0, jnp.asarray(0, jnp.int32))
        (params, states, upd, _, _), losses = jax.lax.scan(
            body, init, (f_s, l_s, fm_s, lm_s))
        return params, states, upd, losses[-1]

    return monitored_jit(scanned, name="nn/tbptt_scan",
                         donate_argnums=(0, 2))


def _map_streams(fn, x):
    """Apply ``fn`` to every stream array — bare arrays (MultiLayerNetwork),
    tuples of optional streams (ComputationGraph), None passthrough. Exactly
    ``tree_map`` semantics; the alias names the intent at the call sites."""
    return jax.tree_util.tree_map(fn, x)


def _run_tbptt(net, f, l, fm, lm, single_iteration):
    """The TBPTT dispatch loop shared by BOTH containers (reference
    ``doTruncatedBPTT`` in `MultiLayerNetwork.java:1219` and
    `ComputationGraph.java`): equal segments fuse into ONE scanned program
    (segment stacking [b, T, ...] → [S, b, L, ...], rank-2 labels/static
    streams broadcast over S); a ragged tail falls back to per-segment
    dispatch with the (h, c) carries threaded on the host. Stream-shape
    differences between the containers are confined to ``_map_streams``."""
    conf, gc = net.conf, net.gc
    first = f[0] if isinstance(f, tuple) else f
    T = int(first.shape[1])
    L = conf.tbptt_fwd_length
    n_applied = 1 if single_iteration else _n_iterations(gc)
    if T % L == 0:
        S, b = T // L, int(first.shape[0])

        def stack(x):
            return jnp.swapaxes(x.reshape(b, S, L, *x.shape[2:]), 0, 1)

        def stack_lbl(x):
            return (stack(x) if x.ndim == 3
                    else jnp.broadcast_to(x, (S,) + x.shape))

        scan_step = net._ensure_tbptt_scan_step(single_iteration)
        with _mon.get_tracer().span("fit/prepare", cat="train"):
            it0 = jnp.asarray(net.iteration_count, jnp.int32)
            rng = net._next_rng()
            streams = (_map_streams(stack, f), _map_streams(stack_lbl, l),
                       _map_streams(stack, fm), _map_streams(stack, lm))
            rnn0 = net._init_rnn_state(b)
        with _mon.step_span(net.iteration_count):
            (net.params, net.states, net.updater_state, loss) = scan_step(
                net.params, net.states, net.updater_state, it0, rng,
                *streams, rnn0)
        # one iteration per TBPTT segment × iterations(n) applied per
        # segment (reference increments iterationCount per applied update,
        # so Adam bias correction and lr schedules see each one)
        net.iteration_count += S * n_applied
    else:
        step = net._ensure_tbptt_step(single_iteration=single_iteration)
        rnn_state = net._init_rnn_state(int(first.shape[0]))
        for start in range(0, T, L):
            sl = slice(start, min(start + L, T))
            with _mon.get_tracer().span("fit/prepare", cat="train"):
                it = jnp.asarray(net.iteration_count, jnp.int32)
                rng = net._next_rng()
                streams = (
                    _map_streams(lambda x: x[:, sl], f),
                    _map_streams(lambda x: x[:, sl] if x.ndim == 3 else x, l),
                    _map_streams(lambda x: x[:, sl], fm),
                    _map_streams(lambda x: x[:, sl], lm))
            with _mon.step_span(net.iteration_count):
                (net.params, net.states, net.updater_state, loss,
                 rnn_state) = step(
                    net.params, net.states, net.updater_state, it, rng,
                    *streams, rnn_state)
            net.iteration_count += n_applied
    net.score_ = loss
    net._completions.dispatched(loss, int(first.shape[0]))


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.gc = conf.global_conf
        self.impls = None
        self.params = None          # {"0": {"W": ..., "b": ...}, ...}
        self.states = None          # non-trainable layer state
        self.updater = None         # NetworkUpdater
        self.updater_state = None
        self.iteration_count = 0
        self.epoch_count = 0
        self.listeners: List = []
        self.score_ = float("nan")
        self.last_batch_size = 0
        self.halt_requested = False  # TrainingHealthListener "halt" action
        self._completions = _mon.StepCompletions(self)   # fit starts its own
        self._rng = None
        self._jit_step = None
        self._jit_tbptt_step = None
        self._jit_output = {}
        self._rnn_state = None      # streaming state for rnn_time_step

    # ------------------------------------------------------------------ init
    def init(self, params=None):
        """Build layer impls and initialize parameters (reference ``init()`` :541)."""
        layers = self.conf.layers
        # resolve per-layer input types (best effort; None when unknown)
        input_types = [None] * len(layers)
        it = self.conf.input_type
        if it is not None:
            for i, lc in enumerate(layers):
                pre = self.conf.preprocessor(i)
                if pre is not None:
                    it = pre.get_output_type(it)
                input_types[i] = it
                lc.set_n_in(it, override=False)
                it = lc.get_output_type(i, it)
        from .conf.layers import FeedForwardLayer, DropoutLayer, LossLayer
        for i, lc in enumerate(layers):
            inner = getattr(lc, "inner", None) or lc
            if isinstance(inner, (DropoutLayer, LossLayer)):
                continue  # nIn/nOut not required (pass-through layers)
            if isinstance(inner, FeedForwardLayer):
                if inner.n_out is None:
                    raise ValueError(f"Layer {i} ({type(inner).__name__}): n_out "
                                     f"is not set")
                if inner.n_in is None:
                    raise ValueError(
                        f"Layer {i} ({type(inner).__name__}): n_in is not set — "
                        f"set n_in explicitly or call set_input_type(...) on the "
                        f"ListBuilder so it can be inferred")
        self.impls = []
        for i, lc in enumerate(layers):
            impl = impl_for(lc, self.gc, input_types[i])
            impl.index = i
            self.impls.append(impl)
        key = jax.random.PRNGKey(self.gc.seed)
        self._rng, *layer_keys = jax.random.split(key, len(layers) + 1)
        if params is not None:
            self.params = params
            self.states = {str(i): impl.init(layer_keys[i])[1]
                           for i, impl in enumerate(self.impls)}
        else:
            self.params = {}
            self.states = {}
            for i, impl in enumerate(self.impls):
                p, s = impl.init(layer_keys[i])
                self.params[str(i)] = p
                self.states[str(i)] = s
        # one updater per layer: per-layer override or global default
        layer_updaters = {}
        for i, lc in enumerate(layers):
            u = getattr(lc, "updater", None) or self.gc.updater
            layer_updaters[str(i)] = u
        self.updater = NetworkUpdater(layer_updaters)
        self.updater_state = self.updater.init_state(self.params)
        return self

    # -------------------------------------------------------------- forward
    def _apply_layers(self, params, states, x, fmask, train, rng, upto=None,
                      rnn_state_in=None):
        """Run layers [0, upto). Returns (x, new_states, rnn_state_out)."""
        n = len(self.impls)
        end = n if upto is None else upto
        keys = (jax.random.split(rng, end) if rng is not None else [None] * end)
        ctx = {}
        if rnn_state_in is not None:
            ctx["rnn_state_in"] = rnn_state_in
        new_states = dict(states)
        i = 0
        while i < end:
            pre = self.conf.preprocessor(i)
            if pre is not None:
                x = pre(x, ctx)
            impl = self.impls[i]
            # fused two-layer persistent LSTM (ops/lstm_fused.py): two
            # consecutive eligible LSTM layers run as ONE kernel chain —
            # half the sequential grid steps, no inter-layer HBM round
            # trip. Eligibility is static per (shape, config); ineligible
            # pairs (masks, bidirectional, dropout between, VMEM budget)
            # take the per-layer path below unchanged.
            if (i + 1 < end and self.conf.preprocessor(i + 1) is None
                    and self._lstm_pair_fusable(i, x, fmask, train)):
                with jax.named_scope(str(i)):    # the pair, under its first
                    x = self._fused_lstm_forward(params, x, train, keys[i],
                                                 ctx, i)
                i += 2
                continue
            # the layer's index names its ops in the device trace (op_name
            # metadata only: the compiled program is the same)
            with jax.named_scope(str(i)):
                p_i = impl.noised_params(params[str(i)], train, keys[i])
                x, ns = impl.forward(p_i, states[str(i)], x, train=train,
                                     rng=keys[i], mask=fmask, ctx=ctx)
                if impl.save_output:
                    # tag for the remat policy (identity outside
                    # jax.checkpoint)
                    x = checkpoint_name(x, "dl4j_act")
            new_states[str(i)] = ns
            i += 1
        return x, new_states, ctx

    def _lstm_pair_fusable(self, i, x, fmask, train):
        """Static eligibility for fusing layers (i, i+1) into
        ``ops/lstm_fused.lstm_scan2``: both plain (non-bidirectional) LSTM
        impls with matching peephole-ness and H, no step mask, no
        inter-layer dropout or weight noise in effect, each layer
        kernel-eligible, and the fused VMEM budget admits the shape."""
        from .layers.recurrent import (_BaseLSTMImpl,
                                       GravesBidirectionalLSTMImpl)
        from ..ops import lstm_cell as _lk
        from ..ops import lstm_fused as _lf

        if fmask is not None or getattr(x, "ndim", 0) != 3:
            return False
        a, b_ = self.impls[i], self.impls[i + 1]
        for im in (a, b_):
            if (not isinstance(im, _BaseLSTMImpl)
                    or isinstance(im, GravesBidirectionalLSTMImpl)):
                return False
            if train and im.weight_noise is not None:
                return False
        if a.peepholes != b_.peepholes:
            return False
        if train and b_.dropout_obj is not None:
            return False
        ca, cb = a.conf, b_.conf
        if not (ca.n_out == cb.n_in == cb.n_out):
            return False
        bsz, T = x.shape[0], x.shape[1]
        H = ca.n_out
        wb = jnp.dtype(a.compute_dtype).itemsize
        for im, c in ((a, ca), (b_, cb)):
            gate = str(getattr(c, "gate_activation", "sigmoid"))
            if not _lk.supported(bsz, T, H, im.activation_name, gate,
                                 weight_bytes=wb):
                return False
        return _lf.supported2(bsz, T, H, weight_bytes=wb)

    def _fused_lstm_forward(self, params, x, train, rng, ctx, i):
        """Run layers (i, i+1) through the fused kernel. Mirrors
        ``recurrent._BaseLSTMImpl._run``'s hoisted input projection and
        ctx-carried (h, c) state handling for BOTH layer indices."""
        from ..ops import lstm_fused as _lf
        from .layers.base import acc_dtype
        from .layers.recurrent import _match_vma

        a, b_ = self.impls[i], self.impls[i + 1]
        x = a.maybe_dropout(x, train, rng)
        pa, pb = params[str(i)], params[str(i + 1)]
        cd = a.compute_dtype
        ad = acc_dtype(cd)
        bsz, T, _ = x.shape
        H = a.conf.n_out
        xp1 = (x.reshape(bsz * T, -1).astype(cd)
               @ pa["W"].astype(cd)).astype(ad)
        xp1 = xp1.reshape(bsz, T, 4 * H) + pa["b"].astype(ad)
        zeros = lambda: jnp.zeros((bsz, H), ad)
        sin = (ctx or {}).get("rnn_state_in", {})
        h01, c01 = sin.get(i) or (zeros(), zeros())
        h02, c02 = sin.get(i + 1) or (zeros(), zeros())
        # same shard_map carry-typing fix as recurrent._run (fresh zero
        # states are not device-varying; xp1 is)
        h01, c01 = _match_vma(h01, xp1), _match_vma(c01, xp1)
        h02, c02 = _match_vma(h02, xp1), _match_vma(c02, xp1)
        peep1 = ((pa["pi"], pa["pf"], pa["po"]) if a.peepholes else None)
        peep2 = ((pb["pi"], pb["pf"], pb["po"]) if b_.peepholes else None)
        ys2, hc1, hc2 = _lf.lstm_scan2(
            xp1, pa["RW"].astype(cd), peep1, pb["W"].astype(cd),
            pb["b"], pb["RW"].astype(cd), peep2, h01, c01, h02, c02)
        if ctx is not None:
            out = ctx.setdefault("rnn_state_out", {})
            out[i] = hc1
            out[i + 1] = hc2
        y = ys2.astype(b_.out_dtype)
        if b_.save_output:
            y = checkpoint_name(y, "dl4j_act")
        return y

    def _adapt_input(self, f):
        """User-facing convolutional input is NCHW (reference convention);
        internally NHWC. Transpose once at the boundary."""
        it = self.conf.input_type
        if isinstance(it, InputTypeConvolutional) and f.ndim == 4:
            # accept NCHW when channel dim matches conf
            if f.shape[1] == it.channels and f.shape[2] == it.height:
                return jnp.transpose(f, (0, 2, 3, 1))
        return f

    def _loss_fn(self, params, states, f, l, fm, lm, train, rng, rnn_state_in=None):
        n = len(self.impls)
        x, new_states, ctx = self._apply_layers(params, states, f, fm, train,
                                                rng, upto=n - 1,
                                                rnn_state_in=rnn_state_in)
        out_impl = self.impls[-1]
        pre = self.conf.preprocessor(n - 1)
        if pre is not None:
            x = pre(x, ctx)
        mask = lm if lm is not None else (fm if x.ndim == 3 else None)
        if not hasattr(out_impl, "loss_on"):
            raise ValueError(f"Last layer {type(out_impl).__name__} is not an "
                             f"output layer")
        with jax.named_scope("loss"):
            loss = out_impl.loss_on(params[str(n - 1)], states[str(n - 1)],
                                    x, l, mask=mask, train=train, rng=rng)
        if hasattr(out_impl, "update_state"):
            # e.g. CenterLossOutputLayer EMA centers — updated outside AD
            xs = jax.lax.stop_gradient(x)
            new_states[str(n - 1)] = out_impl.update_state(states[str(n - 1)],
                                                           xs, l)
        reg = 0.0
        with jax.named_scope("loss"):
            for i, impl in enumerate(self.impls):
                reg = reg + impl.regularization(params[str(i)])
        # activation-dependent auxiliary losses (e.g. MoE load balancing)
        # accumulate in ctx during the forward pass
        aux = ctx.get("aux_loss", 0.0)
        return loss + reg + aux, (new_states, ctx.get("rnn_state_out"))

    # ---------------------------------------------------------- train step
    def _raw_update_core(self, grads_reduce=None):
        """Shared step core: loss → AD grads → gradient normalization →
        updater transform. Returns ``(updates, new_states, new_upd, loss,
        rnn_out)`` WITHOUT applying the update, so both ``_raw_step`` (apply
        in-graph) and ``_raw_update_step`` (ship the update through the
        SHARED_GRADIENTS codec) stay in lock-step by construction.

        ``grads_reduce(grads, loss, new_states) -> (grads, loss,
        new_states)``: optional cross-device reduction hook applied right
        after AD, BEFORE the minimize flip / normalization / updater —
        the seam ``parallel.sequence.sequence_parallel_step`` uses to psum
        time-sliced gradients while inheriting this core's remat/adapt/aux
        behavior instead of duplicating it."""
        gn_mode = self.gc.gradient_normalization
        gn_thresh = self.gc.gradient_normalization_threshold
        minimize = self.gc.minimize

        use_remat = remat_enabled(self.gc, self.impls)

        def core(params, states, upd_state, iteration, rng, f, l, fm, lm,
                 rnn_state_in=None):
            f = self._adapt_input(f)

            def loss_fn(p):
                return self._loss_fn(p, states, f, l, fm, lm, True, rng,
                                     rnn_state_in)

            if use_remat:
                loss_fn = jax.checkpoint(loss_fn, policy=remat_policy())
            (loss, (new_states, rnn_out)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if grads_reduce is not None:
                grads, loss, new_states = grads_reduce(grads, loss,
                                                       new_states)
            with jax.named_scope("updater"):
                if not minimize:
                    grads = _tm(lambda g: -g, grads)
                grads = normalize_gradients(grads, gn_mode, gn_thresh)
                updates, new_upd = self.updater.apply(upd_state, grads,
                                                      iteration)
            return updates, new_states, new_upd, loss, rnn_out

        return core

    def _raw_step(self, with_rnn_state):
        """The pure (unjitted) train-step function. ``_build_step`` jits it for
        single-device training; ``deeplearning4j_tpu.parallel`` re-jits it with
        explicit ``NamedSharding``s over a device mesh (SPMD data parallelism —
        the reference's ParallelWrapper role, SURVEY.md §2.4/§7 Phase 3)."""
        core = self._raw_update_core()

        def step(params, states, upd_state, iteration, rng, f, l, fm, lm,
                 rnn_state_in=None):
            updates, new_states, new_upd, loss, rnn_out = core(
                params, states, upd_state, iteration, rng, f, l, fm, lm,
                rnn_state_in)
            with jax.named_scope("updater"):
                new_params = _tm(lambda p, u: p - u.astype(p.dtype), params,
                                 updates)
                new_params = self._apply_constraints(new_params)
            if with_rnn_state:
                rnn_out = _tm(jax.lax.stop_gradient, rnn_out) if rnn_out else rnn_out
                return new_params, new_states, new_upd, loss, rnn_out
            return new_params, new_states, new_upd, loss

        return step

    def _raw_update_step(self, with_rnn_state=False):
        """Updater-transformed update without application — the
        SHARED_GRADIENTS wire seam: the reference encodes post-updater updates
        for peer broadcast (``SymmetricTrainer`` via
        ``EncodingHandler.java:136``), so the codec must see the update, not
        the raw gradient. ``with_rnn_state``: thread the detached RNN/KV
        carry through (TBPTT segments under SHARED_GRADIENTS)."""
        core = self._raw_update_core()

        def step(params, states, upd_state, iteration, rng, f, l, fm, lm,
                 rnn_state_in=None):
            updates, new_states, new_upd, loss, rnn_out = core(
                params, states, upd_state, iteration, rng, f, l, fm, lm,
                rnn_state_in)
            if with_rnn_state:
                rnn_out = (_tm(jax.lax.stop_gradient, rnn_out)
                           if rnn_out else rnn_out)
                return updates, new_states, new_upd, loss, rnn_out
            return updates, new_states, new_upd, loss

        return step

    def _apply_constraints(self, params):
        """Per-layer parameter constraints after each update (reference
        ``BaseConstraint.applyConstraint`` timing)."""
        from .conf.dropout import apply_constraints
        out = dict(params)
        for i, lc in enumerate(self.conf.layers):
            cons = getattr(lc, "constraints", None) or \
                getattr(getattr(lc, "inner", None), "constraints", None)
            if cons:
                out[str(i)] = apply_constraints(cons, params[str(i)])
        return out

    def _build_step(self, with_rnn_state, single_iteration=False):
        step = self._raw_step(with_rnn_state)
        n_iter = 1 if single_iteration else _n_iterations(self.gc)
        if n_iter > 1:
            step = _scan_iterations(step, n_iter, with_rnn_state)
        return monitored_jit(step, name="mln/step",
                             donate_argnums=(0, 2))

    def _ensure_step(self, single_iteration=False):
        if single_iteration and _n_iterations(self.gc) > 1:
            if getattr(self, "_jit_step_single", None) is None:
                self._jit_step_single = self._build_step(
                    with_rnn_state=False, single_iteration=True)
            return self._jit_step_single
        if self._jit_step is None:
            self._jit_step = self._build_step(with_rnn_state=False)
        return self._jit_step

    def _ensure_tbptt_step(self, single_iteration=False):
        if single_iteration and _n_iterations(self.gc) > 1:
            if getattr(self, "_jit_tbptt_step_single", None) is None:
                self._jit_tbptt_step_single = self._build_step(
                    with_rnn_state=True, single_iteration=True)
            return self._jit_tbptt_step_single
        if self._jit_tbptt_step is None:
            self._jit_tbptt_step = self._build_step(with_rnn_state=True)
        return self._jit_tbptt_step

    def _build_tbptt_scan_step(self, single_iteration=False):
        """The WHOLE TBPTT loop as one jitted program: ``lax.scan`` over
        stacked segments, carrying params/updater/RNN state (detached between
        segments by the inner step). One device dispatch per minibatch
        instead of one per segment: a 200-char/50-TBPTT batch saves 3 of 4
        dispatches (same move as the ``iterations(n)`` scan, applied to the
        segment dimension)."""
        n_iter = 1 if single_iteration else _n_iterations(self.gc)
        return _build_tbptt_scan(self._raw_step(True), n_iter)

    def _ensure_tbptt_scan_step(self, single_iteration=False):
        cache = getattr(self, "_jit_tbptt_scan", None)
        if cache is None:
            cache = self._jit_tbptt_scan = {}
        key = bool(single_iteration)
        if key not in cache:
            cache[key] = self._build_tbptt_scan_step(single_iteration)
        return cache[key]

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    # ----------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs=1):
        """Train (reference ``fit(DataSetIterator)`` :1156). Accepts a DataSet,
        a DataSetIterator, or (features, labels) arrays.

        .. note:: Timing caution: steps are dispatched asynchronously, so
           ``fit`` can return before the device has finished. Close a timed
           window with ``jax.block_until_ready(net.params)`` or a value
           fetch — e.g. ``float(net.score_)`` — or attach
           :class:`deeplearning4j_tpu.utils.profiling.StepTimerListener`,
           which does this for you."""
        if labels is not None:
            data = DataSet(np.asarray(data), np.asarray(labels))
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        if self.conf.pretrain and not getattr(self, "_pretrained", False):
            self.pretrain(data)
            self._pretrained = True
        # multi-worker prefetch + device-put-ahead (datasets/prefetch.py):
        # batch k+1 is transferred while step k computes, so etl_ms
        # measures a queue pop. DL4J_TPU_PREFETCH_WORKERS=0 restores the
        # fully synchronous path.
        it, own_pipeline = wrap_for_training(
            data, cache_device=self.gc.cache_mode == CacheMode.DEVICE)
        # a new fit() supersedes a previous health halt — without this, one
        # halt would silently truncate every later fit to a single batch
        self.halt_requested = False
        _mon.get_health().clear_halt()
        done = self._completions = _mon.StepCompletions(self)
        try:
            for epoch in range(epochs):
                for lst in self.listeners:
                    lst.on_epoch_start(self, self.epoch_count)
                with _mon.get_tracer().span("epoch", cat="train",
                                            epoch=self.epoch_count):
                    for ds, waited in _mon.spanned(it, "fit/next_batch"):
                        self._fit_batch(ds, etl_ms=waited * 1e3)
                        if self.halt_requested:
                            break
                    done.drain()
                for lst in self.listeners:
                    lst.on_epoch_end(self, self.epoch_count)
                self.epoch_count += 1
                if self.halt_requested:
                    log.warning("fit halted at epoch %d (halt_requested; see "
                                "TrainingHealthListener)", self.epoch_count)
                    break
        except BaseException as e:
            # error seam: listeners holding process-global resources (an
            # active ProfilerListener trace window) must release them
            # before the exception unwinds out of fit
            from ..optimize.listeners import dispatch_training_error
            dispatch_training_error(self, self.listeners, e)
            # the steps dispatched before the failure still count; a fetch
            # that fails in turn must not hide ``e``
            with contextlib.suppress(Exception):
                done.drain()
            raise
        finally:
            if own_pipeline:
                it.shutdown()   # no prefetch worker outlives its fit
        return self

    def _fit_batch(self, ds: DataSet, single_iteration=False, etl_ms=None):
        """One minibatch. ``single_iteration=True`` applies exactly ONE
        optimizer update even when ``iterations(n)`` scans are configured —
        the ParallelWrapper tail-batch fallback needs update-count parity
        with its sharded dispatches (masks and TBPTT routing preserved).
        ``etl_ms``: what ``fit`` waited for ``ds`` (``fit/next_batch``)."""
        with _mon.get_tracer().span("fit/prepare", cat="train"):
            if self.gc.cache_mode == CacheMode.DEVICE:
                f, l, fm, lm = ds.device_arrays()
            else:
                f = jnp.asarray(ds.features)
                l = jnp.asarray(ds.labels)
                fm = (None if ds.features_mask is None
                      else jnp.asarray(ds.features_mask))
                lm = (None if ds.labels_mask is None
                      else jnp.asarray(ds.labels_mask))
            self.last_batch_size = int(f.shape[0])
            tbptt = (self.conf.backprop_type == BackpropType.TruncatedBPTT
                     and f.ndim == 3
                     and f.shape[1] > self.conf.tbptt_fwd_length)
            if not tbptt:
                step = self._ensure_step(single_iteration=single_iteration)
                it = jnp.asarray(self.iteration_count, jnp.int32)
                rng = self._next_rng()
        if tbptt:
            self._fit_tbptt(f, l, fm, lm, single_iteration=single_iteration)
            return
        # dispatch only: a span is host time, the fetch is StepCompletions'
        with _mon.step_span(self.iteration_count):
            self.params, self.states, self.updater_state, loss = step(
                self.params, self.states, self.updater_state, it, rng,
                f, l, fm, lm)
        self.score_ = loss
        self.iteration_count += (1 if single_iteration
                                 else _n_iterations(self.gc))
        self._completions.dispatched(loss, self.last_batch_size, etl_ms)

    def _fit_tbptt(self, f, l, fm, lm, single_iteration=False):
        """Truncated BPTT (reference ``doTruncatedBPTT``): split time into
        chunks of tbptt_fwd_length, carry RNN state (detached) across chunks.
        Like the reference's practical behavior, the backward truncation equals
        the forward chunk length; a differing ``tbptt_back_length`` is treated
        as ``tbptt_fwd_length`` (warned once)."""
        if (self.conf.tbptt_back_length != self.conf.tbptt_fwd_length
                and not getattr(self, "_warned_tbptt", False)):
            log.warning("tbptt_back_length=%d differs from tbptt_fwd_length=%d; "
                        "backprop truncation uses the forward chunk length",
                        self.conf.tbptt_back_length, self.conf.tbptt_fwd_length)
            self._warned_tbptt = True
        _run_tbptt(self, f, l, fm, lm, single_iteration)

    def _init_rnn_state(self, batch):
        state = {}
        for i, impl in enumerate(self.impls):
            if hasattr(impl, "init_stream_state"):
                state[i] = impl.init_stream_state(batch)
        return state

    # -------------------------------------------------------------- pretrain
    def pretrain(self, iterator, epochs=1):
        """Layerwise unsupervised pretraining (reference ``pretrain(iter)``
        :1172): for each pretrain-capable layer (AutoEncoder, VAE), optimize its
        ``pretrain_loss`` on that layer's input activations."""
        for i, lc in enumerate(self.conf.layers):
            if lc.is_pretrain_layer():
                self.pretrain_layer(i, iterator, epochs=epochs)
        return self

    def pretrain_layer(self, layer_idx, iterator, epochs=1):
        """Reference ``pretrainLayer(int, DataSetIterator)``."""
        impl = self.impls[layer_idx]
        if not hasattr(impl, "pretrain_loss"):
            raise ValueError(f"Layer {layer_idx} ({type(impl).__name__}) is not "
                             f"a pretrainable layer")
        key = str(layer_idx)
        updater = self.updater.layer_updaters[key]

        def step(layer_params, upd_state, feats, rng, it):
            def loss_fn(p):
                return impl.pretrain_loss(p, feats, rng)
            loss, grads = jax.value_and_grad(loss_fn)(layer_params)
            updates, new_upd = updater.apply(upd_state, grads, it)
            new_params = _tm(lambda p, u: p - u.astype(p.dtype), layer_params,
                             updates)
            return new_params, new_upd, loss

        jstep = monitored_jit(step, name="mln/pretrain_step",
                              donate_argnums=(0, 1))
        upd_state = updater.init_state(self.params[key])
        it_count = 0
        for _ in range(epochs):
            for ds in iterator:
                x = jnp.asarray(ds.features)
                x = self._adapt_input(x)
                if layer_idx > 0:
                    x = self.feed_forward_to_layer(layer_idx - 1, x)
                p, upd_state, loss = jstep(self.params[key], upd_state, x,
                                           self._next_rng(),
                                           jnp.asarray(it_count, jnp.int32))
                self.params[key] = p
                it_count += 1
        self.score_ = loss
        return self

    # ------------------------------------------------------------- inference
    def output(self, x, train=False, mask=None):
        """Forward to activations of the last layer (reference ``output``).
        ``mask`` is the features mask for sequence inputs — affects mask-aware
        layers (bidirectional RNNs, global pooling) exactly as in training."""
        x = jnp.asarray(x)
        mask = None if mask is None else jnp.asarray(mask)
        key = (bool(train), mask is not None)
        if key not in self._jit_output:
            def fwd(params, states, f, fm):
                f = self._adapt_input(f)
                y, _, _ = self._apply_layers(params, states, f, fm, train, None)
                return y
            # jax.jit itself specializes per shape/dtype; one callable per
            # (train, has_mask) keeps the python-side cache bounded
            self._jit_output[key] = monitored_jit(fwd,
                                                  name="mln/output")
        return self._jit_output[key](self.params, self.states, x, mask)

    def feed_forward(self, x, train=False):
        """All layer activations, eager (reference ``feedForward`` list)."""
        x = jnp.asarray(x)
        x = self._adapt_input(x)
        acts = [x]
        ctx = {}
        for i, impl in enumerate(self.impls):
            pre = self.conf.preprocessor(i)
            if pre is not None:
                x = pre(x, ctx)
            x, _ = impl.forward(self.params[str(i)], self.states[str(i)], x,
                                train=train, rng=None, mask=None, ctx=ctx)
            acts.append(x)
        return acts

    feedForward = feed_forward

    def feed_forward_to_layer(self, layer_idx, x, train=False):
        """Reference ``feedForwardToLayer`` :903 (activation materialization
        point — partial-graph execution)."""
        x = jnp.asarray(x)
        x = self._adapt_input(x)
        ctx = {}
        for i in range(layer_idx + 1):
            pre = self.conf.preprocessor(i)
            if pre is not None:
                x = pre(x, ctx)
            x, _ = self.impls[i].forward(self.params[str(i)], self.states[str(i)],
                                         x, train=train, rng=None, mask=None,
                                         ctx=ctx)
        return x

    feedForwardToLayer = feed_forward_to_layer

    def rnn_time_step(self, x):
        """Stateful streaming inference (reference ``rnnTimeStep``)."""
        x = jnp.asarray(x)
        single_step = x.ndim == 2
        if single_step:
            x = x[:, None, :]
        if self._rnn_state is None:
            self._rnn_state = self._init_rnn_state(int(x.shape[0]))
        if getattr(self, "_jit_rnn_step", None) is None:
            # cached on self: jit re-traces per input shape, but a fresh
            # closure per call would recompile every streaming step
            def fwd(params, states, f, rnn_state):
                y, _, ctx = self._apply_layers(params, states, f, None, False,
                                               None, rnn_state_in=rnn_state)
                return y, ctx.get("rnn_state_out")
            self._jit_rnn_step = monitored_jit(fwd,
                                               name="mln/rnn_step")
        y, self._rnn_state = self._jit_rnn_step(self.params, self.states, x,
                                                self._rnn_state)
        return y[:, -1, :] if single_step else y

    rnnTimeStep = rnn_time_step

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    rnnClearPreviousState = rnn_clear_previous_state

    # ----------------------------------------------------------------- score
    def score(self, ds: Optional[DataSet] = None, training=False):
        """Loss (+reg) on a dataset (reference ``score(DataSet)``), or last
        training score when called without arguments."""
        if ds is None:
            return float(self.score_)
        f = jnp.asarray(ds.features)
        l = jnp.asarray(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        key = (bool(training), fm is not None, lm is not None)
        if not hasattr(self, "_jit_score"):
            self._jit_score = {}
        if key not in self._jit_score:
            # jitted: early stopping / evaluative listeners call this every
            # epoch over the full validation set — eager tracing per batch
            # would make evaluation the epoch bottleneck on TPU
            def score_fn(params, states, f, l, fm, lm):
                f2 = self._adapt_input(f)
                loss, _ = self._loss_fn(params, states, f2, l, fm, lm,
                                        training, None)
                return loss
            self._jit_score[key] = monitored_jit(score_fn,
                                                 name="mln/score")
        loss = self._jit_score[key](self.params, self.states, f, l, fm, lm)
        return float(loss)

    def compute_gradient_and_score(self, ds: DataSet):
        """Reference ``computeGradientAndScore`` :2206 — returns (grads, score)
        without updating params (used by gradient checks and external
        optimizers)."""
        f = self._adapt_input(jnp.asarray(ds.features))
        l = jnp.asarray(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)

        def loss_fn(p):
            loss, _ = self._loss_fn(p, self.states, f, l, fm, lm, True, None)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(self.params)
        self.score_ = loss
        return grads, float(loss)

    # ------------------------------------------------------------ evaluation
    def evaluate(self, iterator):
        from ..eval.evaluation import Evaluation
        ev = Evaluation()
        for ds in iterator:
            out = self.output(ds.features, mask=ds.features_mask)
            ev.eval(ds.labels, np.asarray(out),
                    mask=ds.labels_mask if ds.labels_mask is not None
                    else ds.features_mask)
        return ev

    def evaluate_regression(self, iterator):
        from ..eval.regression import RegressionEvaluation
        ev = RegressionEvaluation()
        for ds in iterator:
            out = self.output(ds.features)
            ev.eval(ds.labels, np.asarray(out))
        return ev

    # ------------------------------------------------------------ parameters
    def param_table(self):
        """{"0_W": array, ...} (reference ``paramTable()`` naming)."""
        out = {}
        for i in sorted(self.params, key=int):
            for k, v in self.params[i].items():
                out[f"{i}_{k}"] = v
        return out

    paramTable = param_table

    def get_param(self, key):
        i, k = key.split("_", 1)
        return self.params[i][k]

    def num_params(self) -> int:
        return sum(int(v.size) for v in jax.tree_util.tree_leaves(self.params))

    numParams = num_params

    def params_flat(self) -> np.ndarray:
        """Single flattened param vector, layer-major (reference's flattened
        params buffer ``MultiLayerNetwork.java:110``)."""
        chunks = []
        for i in sorted(self.params, key=int):
            for k in self.params[i]:
                chunks.append(np.asarray(self.params[i][k]).ravel())
        if not chunks:
            return np.zeros((0,), np.float32)
        return np.concatenate(chunks)

    def set_params_flat(self, vec):
        vec = np.asarray(vec)
        pos = 0
        new = {}
        for i in sorted(self.params, key=int):
            new[i] = {}
            for k, v in self.params[i].items():
                n = int(np.prod(v.shape)) if v.shape else 1
                new[i][k] = jnp.asarray(vec[pos:pos + n].reshape(v.shape),
                                        dtype=v.dtype)
                pos += n
        if pos != vec.size:
            raise ValueError(f"Param vector length {vec.size} != model {pos}")
        self.params = new

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    setListeners = set_listeners

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    # ------------------------------------------------------------------ misc
    def clone(self):
        net = MultiLayerNetwork(self.conf.clone())
        net.init()
        net.params = _tm(lambda x: x, self.params)
        net.states = _tm(lambda x: x, self.states)
        net.updater_state = _tm(lambda x: x, self.updater_state)
        return net

    @property
    def n_layers(self):
        return len(self.conf.layers)

    def summary(self) -> str:
        lines = [f"{'idx':>3}  {'type':<28} {'params':>10}"]
        for i, impl in enumerate(self.impls):
            n = impl.num_params(self.params[str(i)])
            lines.append(f"{i:>3}  {type(self.conf.layers[i]).__name__:<28} {n:>10}")
        lines.append(f"Total params: {self.num_params()}")
        return "\n".join(lines)

"""ComputationGraph: DAG network container with multi-input/multi-output.

TPU-native equivalent of reference ``nn/graph/ComputationGraph.java`` (3363 LoC;
init/topo-sort :394/:1190, ``fit(DataSetIterator)`` :863,
``fit(MultiDataSetIterator)`` :988, ``computeGradientAndScore`` :1298,
``calcBackpropGradients(truncatedBPTT, externalEpsilons)`` :1629,
``feedForward`` :1361-1440).

As with MultiLayerNetwork, the architectural shift is whole-graph compilation:
one jitted XLA computation covers forward over the cached topological order,
loss on every output vertex, AD backward, gradient normalization, updater, and
the parameter update, with params/updater state donated; that step and the
``fit`` loop around it are ``nn/training.py``'s, shared with
``MultiLayerNetwork``. External-errors training (the reference's
externalEpsilons path, used to couple a graph to an outside loss) is
``fit_external_errors``: VJP of the outputs against caller epsilons inside the
same jitted step.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from ..monitor.jitwatch import monitored_jit
from .conf.graph import ComputationGraphConfiguration
from .conf.layers import Layer
from .conf.inputs import InputTypeConvolutional, InputTypeLoopedRecurrent
from jax.ad_checkpoint import checkpoint_name

from .layers import impl_for
from .training import _TrainingBase, _device_arrays
from ..datasets.dataset import MultiDataSet
from ..optimize.updater import normalize_gradients

_tm = jax.tree_util.tree_map


def fused_softmax_skip_set(conf, impls):
    """Output-layer vertices whose forwards the loss pass SKIPS: ``loss_on``
    consumes their *input* activations so the fused softmax/xent path
    applies to preoutput. Only safe when nothing downstream consumes the
    output activation. Shared by ``ComputationGraph._loss_fn`` and the
    pipeline-parallel head (``parallel/pipeline.py``) so the rule cannot
    diverge between the two loss paths."""
    consumed = {i for ins in conf.vertex_inputs.values() for i in ins}
    return frozenset(n for n in conf.network_outputs
                     if hasattr(impls.get(n), "loss_on")
                     and n not in consumed)


class ComputationGraph(_TrainingBase):
    _jit_prefix = "cg"

    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__(conf)
        self.topo: List[str] = conf.topological_order()
        self.impls: Dict[str, object] = {}
        self._jit_ext_step = None
        self._jit_output = {}
        self._types = None

    # ------------------------------------------------------------------ init
    def _init(self, params):
        conf = self.conf
        # shape inference (idempotent; from_json configs arrive unresolved)
        types = conf.infer_shapes()
        self._types = types

        layer_names = [n for n in self.topo if isinstance(conf.vertices[n], Layer)]
        key = jax.random.PRNGKey(self.gc.seed)
        self._rng, *keys = jax.random.split(key, len(layer_names) + 1)
        for name in layer_names:
            in_name = conf.vertex_inputs[name][0] if conf.vertex_inputs[name] else None
            it = types.get(in_name) if in_name else None
            if name in conf.input_preprocessors and it is not None:
                it = conf.input_preprocessors[name].get_output_type(it)
            self.impls[name] = impl_for(conf.vertices[name], self.gc, it)
            self.impls[name].index = name
        self._init_layers([(n, self.impls[n], k)
                           for n, k in zip(layer_names, keys)], params)
        for name in layer_names:
            self._check_tie(name)
        self._init_updater({
            name: getattr(conf.vertices[name], "updater", None)
            or self.gc.updater for name in layer_names})

    # ------------------------------------------------------------ tied leaf
    def _check_tie(self, name):
        """A layer tied to another vertex (``RnnOutputLayer.tied_to``) finds
        there a leaf ``W`` of its own head's shape, transposed."""
        v = self.conf.vertices[name]
        src = getattr(v, "tied_to", None)
        if src is None:
            return
        w = self.params.get(src, {}).get("W") if src in self.impls else None
        if w is None or tuple(w.shape) != (v.n_out, v.n_in):
            raise ValueError(
                f"vertex '{name}' is tied to '{src}', which has to be a layer "
                f"with a leaf W of shape [{v.n_out}, {v.n_in}] (an embedding "
                f"of the output's classes); found "
                f"{None if w is None else tuple(w.shape)}")

    def _params_of(self, params, name):
        """What the layer ``name`` is handed: its own leaves and, where it is
        tied, the other vertex's ``W`` as ``tied_W``. The leaf lives once, in
        ``params`` under the other vertex's name, so it is counted, updated,
        regularised and saved once, and its gradient is the sum over both
        uses."""
        src = getattr(self.conf.vertices[name], "tied_to", None)
        if src is None:
            return params[name]
        return {**params[name], "tied_W": params[src]["W"]}

    # -------------------------------------------------------------- forward
    def _adapt_inputs(self, inputs):
        """User-facing conv inputs are NCHW; internal layout NHWC."""
        out = []
        its = self.conf.input_types or [None] * len(inputs)
        for x, it in zip(inputs, its):
            if (isinstance(it, InputTypeConvolutional) and x.ndim == 4
                    and x.shape[1] == it.channels and x.shape[2] == it.height):
                x = jnp.transpose(x, (0, 2, 3, 1))
            out.append(x)
        return out

    def _apply_graph(self, params, states, inputs, input_masks, train, rng,
                     skip=(), rnn_state_in=None):
        """Forward over the cached topo order. Returns (activations dict,
        new_states, masks dict, ctx). ``skip``: vertex names not to execute
        (the training loss path skips output-layer forwards; ``loss_on``
        evaluates them on preoutput with fused softmax/xent).
        ``rnn_state_in``: {layer name → carry} for TBPTT/streaming."""
        conf = self.conf
        acts: Dict[str, object] = dict(zip(conf.network_inputs, inputs))
        masks = dict(zip(conf.network_inputs,
                         input_masks or [None] * len(conf.network_inputs)))
        ctx = {"inputs": acts, "input_masks": masks}
        if rnn_state_in is not None:
            ctx["rnn_state_in"] = rnn_state_in
        new_states = dict(states)
        layer_names = [n for n in self.topo if n in self.impls]
        keys = (dict(zip(layer_names, jax.random.split(rng, len(layer_names))))
                if rng is not None and layer_names else {})
        for name in self.topo:
            if name in skip:
                continue
            v = conf.vertices[name]
            in_names = conf.vertex_inputs[name]
            xs = [acts[i] for i in in_names]
            if isinstance(v, Layer):
                # propagate the mask of the (single) input chain
                m = masks.get(in_names[0])
                impl = self.impls[name]
                # the vertex's name names its ops in the device trace
                # (op_name metadata only: the compiled program is the same)
                with jax.named_scope(name):
                    x = xs[0]
                    pre = conf.input_preprocessors.get(name)
                    if pre is not None:
                        x = pre(x, ctx)
                    p_n = impl.noised_params(self._params_of(params, name),
                                             train, keys.get(name))
                    y, ns = impl.forward(p_n, states[name], x, train=train,
                                         rng=keys.get(name), mask=m, ctx=ctx)
                    if impl.save_output:
                        # tag for the remat policy (identity outside
                        # jax.checkpoint)
                        y = checkpoint_name(y, "dl4j_act")
                new_states[name] = ns
                acts[name] = y
                masks[name] = m
            else:
                # vertex outputs are saved under the remat policy: junction
                # vertices (ElementWise/Merge) carry the residual spine, and
                # an unsaved spine would recompute-chain through every
                # upstream block during the backward pass
                with jax.named_scope(name):
                    acts[name] = checkpoint_name(v.forward(xs, ctx),
                                                 "dl4j_act")
                masks[name] = v.propagate_mask([masks.get(i) for i in in_names])
        return acts, new_states, masks, ctx

    def _loss_fn(self, params, states, inputs, labels, input_masks, label_masks,
                 train, rng, rnn_state_in=None):
        conf = self.conf
        out_set = fused_softmax_skip_set(conf, self.impls)
        acts, new_states, masks, ctx = self._apply_graph(
            params, states, inputs, input_masks, train, rng, skip=out_set,
            rnn_state_in=rnn_state_in)
        total = 0.0
        for out_name, lbl, lm in zip(conf.network_outputs, labels,
                                     label_masks or [None] * len(labels)):
            impl = self.impls.get(out_name)
            if impl is None or not hasattr(impl, "loss_on"):
                raise ValueError(f"Output vertex '{out_name}' is not an output "
                                 f"layer — cannot compute training loss")
            in_name = conf.vertex_inputs[out_name][0]
            x = acts[in_name]
            pre = conf.input_preprocessors.get(out_name)
            if pre is not None:
                x = pre(x, ctx)
            # a sequence's [b, T] mask follows [b, T, F] activations and a
            # looped stack's [R, b, T, F] (InputTypeLoopedRecurrent)
            looped = isinstance((self._types or {}).get(in_name),
                                InputTypeLoopedRecurrent)
            mask = lm if lm is not None else (
                masks.get(in_name) if x.ndim == 3 or looped else None)
            with jax.named_scope("loss"):
                total = total + impl.loss_on(self._params_of(params, out_name),
                                             states[out_name], x, lbl,
                                             mask=mask, train=train, rng=rng)
            if hasattr(impl, "update_state"):
                xs = jax.lax.stop_gradient(x)
                new_states[out_name] = impl.update_state(states[out_name], xs, lbl)
        reg = 0.0
        with jax.named_scope("loss"):
            for name, impl in self.impls.items():
                reg = reg + impl.regularization(params[name])
        aux = ctx.get("aux_loss", 0.0)  # e.g. MoE load balancing
        return total + reg + aux, (new_states, ctx.get("rnn_state_out"))

    # --------------------------------- what nn/training.py asks of a container
    def _as_multi(self, ds):
        if isinstance(ds, MultiDataSet):
            return ds
        return MultiDataSet([ds.features], [ds.labels],
                            None if ds.features_mask is None else [ds.features_mask],
                            None if ds.labels_mask is None else [ds.labels_mask])

    def _batch_streams(self, ds, cached=False):
        """``(inputs, labels, feature masks, label masks)`` of ``ds`` as the
        step takes them: tuples of device arrays, masks None where absent."""
        streams = _device_arrays(ds, cached)
        if isinstance(ds, MultiDataSet):
            return streams
        return tuple(None if a is None else (a,) for a in streams)

    def _layers(self):
        """``(params key, layer conf, impl)``, in topological order."""
        return ((name, self.conf.vertices[name], impl)
                for name, impl in self.impls.items())

    # ------------------------------------------------------------- streaming
    def rnn_time_step(self, *inputs):
        """Stateful streaming inference over the DAG (reference CG
        ``rnnTimeStep``)."""
        xs = tuple(jnp.asarray(x) for x in inputs)
        single_step = xs[0].ndim == 2
        if single_step:
            xs = tuple(x[:, None, :] for x in xs)
        if getattr(self, "_rnn_state", None) is None:
            self._rnn_state = self._init_rnn_state(int(xs[0].shape[0]))
        if getattr(self, "_jit_rnn_step", None) is None:
            # cached on self: a fresh closure per call would recompile every
            # streaming step (jit still specializes per input shape)
            def fwd(params, states, fs, rnn_state):
                fs = self._adapt_inputs(fs)
                acts, _, _, ctx = self._apply_graph(params, states, fs, None,
                                                    False, None,
                                                    rnn_state_in=rnn_state)
                outs = tuple(acts[n] for n in self.conf.network_outputs)
                return outs, ctx.get("rnn_state_out")
            self._jit_rnn_step = monitored_jit(fwd,
                                               name="cg/rnn_step")
        outs, self._rnn_state = self._jit_rnn_step(self.params, self.states, xs,
                                                   self._rnn_state)
        if single_step:
            outs = tuple(o[:, -1, :] if o.ndim == 3 else o for o in outs)
        return outs[0] if len(outs) == 1 else list(outs)

    rnnTimeStep = rnn_time_step

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    rnnClearPreviousState = rnn_clear_previous_state

    # ------------------------------------------------- external errors path
    def fit_external_errors(self, inputs, epsilons):
        """Reference external-epsilons training (``calcBackpropGradients``
        :1629 with externalEpsilons): apply d(outputs)·epsilons through VJP and
        update params. ``epsilons`` aligns with ``network_outputs``."""
        inputs = tuple(jnp.asarray(x) for x in (inputs if isinstance(inputs, (list, tuple)) else [inputs]))
        epsilons = tuple(jnp.asarray(e) for e in (epsilons if isinstance(epsilons, (list, tuple)) else [epsilons]))
        if self._jit_ext_step is None:
            gn_mode = self.gc.gradient_normalization
            gn_thresh = self.gc.gradient_normalization_threshold

            def ext_step(params, states, upd_state, iteration, xs, eps):
                xs = self._adapt_inputs(xs)

                def out_fn(p):
                    acts, _, _, _ = self._apply_graph(p, states, xs, None, True, None)
                    outs = []
                    for name in self.conf.network_outputs:
                        outs.append(acts[name])
                    return tuple(outs)

                _, vjp = jax.vjp(out_fn, params)
                grads = vjp(eps)[0]
                grads = normalize_gradients(grads, gn_mode, gn_thresh)
                updates, new_upd = self.updater.apply(upd_state, grads, iteration)
                new_params = _tm(lambda p, u: p - u.astype(p.dtype), params, updates)
                return new_params, new_upd

            self._jit_ext_step = monitored_jit(
                ext_step, name="cg/ext_grad_step", donate_argnums=(0, 2))
        it = jnp.asarray(self.iteration_count, jnp.int32)
        self.params, self.updater_state = self._jit_ext_step(
            self.params, self.states, self.updater_state, it, inputs, epsilons)
        self.iteration_count += 1
        return self

    # ------------------------------------------------------------- inference
    def output(self, *inputs, train=False, masks=None):
        """Activations of all output vertices (reference ``output``). Returns a
        single array when the graph has one output."""
        xs = tuple(jnp.asarray(x) for x in inputs)
        ms = (None if masks is None
              else tuple(None if m is None else jnp.asarray(m) for m in masks))
        key = (bool(train), ms is not None)
        if key not in self._jit_output:
            def fwd(params, states, xs, ms):
                xs = self._adapt_inputs(xs)
                acts, _, _, _ = self._apply_graph(params, states, xs, ms, train, None)
                return tuple(acts[n] for n in self.conf.network_outputs)
            self._jit_output[key] = monitored_jit(fwd,
                                                  name="cg/output")
        outs = self._jit_output[key](self.params, self.states, xs, ms)
        return outs[0] if len(outs) == 1 else list(outs)

    def feed_forward(self, *inputs, train=False):
        """All vertex activations as a dict (reference ``feedForward`` map)."""
        xs = self._adapt_inputs([jnp.asarray(x) for x in inputs])
        acts, _, _, _ = self._apply_graph(self.params, self.states, xs, None,
                                          train, None)
        return acts

    feedForward = feed_forward

    # ------------------------------------------------------------ evaluation
    def evaluate(self, iterator, output_idx=0):
        """Classification evaluation on output ``output_idx`` (reference
        ``evaluate``; accepts DataSet or MultiDataSet iterators)."""
        from ..eval.evaluation import Evaluation
        ev = Evaluation()
        for ds in iterator:
            mds = self._as_multi(ds)
            outs = self.output(*mds.features, masks=mds.features_masks)
            out = outs[output_idx] if isinstance(outs, list) else outs
            lm = (None if mds.labels_masks is None
                  else mds.labels_masks[output_idx])
            if lm is None and mds.features_masks is not None:
                lm = mds.features_masks[0]
            ev.eval(mds.labels[output_idx], np.asarray(out), mask=lm)
        return ev

    # ------------------------------------------------------------ parameters
    def param_table(self):
        out = {}
        for name in self.topo:
            if name in self.params:
                for k, v in self.params[name].items():
                    out[f"{name}_{k}"] = v
        return out

    paramTable = param_table

    def summary(self) -> str:
        lines = [f"{'vertex':<32} {'type':<28} {'params':>10}"]
        for name in self.topo:
            v = self.conf.vertices[name]
            n = (self.impls[name].num_params(self.params[name])
                 if name in self.impls else 0)
            lines.append(f"{name:<32} {type(v).__name__:<28} {n:>10}")
        lines.append(f"Total params: {self.num_params()}")
        return "\n".join(lines)

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
gives us for free. That step and the ``fit`` loop around it are ``nn/training.py``'s,
shared with ``ComputationGraph``; this module holds the forward pass and the loss.

Training state (BN running stats, RNN streaming state) is explicit: ``states``
pytree and the TBPTT carry, replacing the reference's mutable layer fields.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .conf import MultiLayerConfiguration
from .conf.inputs import InputTypeConvolutional
from jax.ad_checkpoint import checkpoint_name

from .layers import impl_for
from .training import _TrainingBase, _device_arrays
from ..monitor.jitwatch import monitored_jit

_tm = jax.tree_util.tree_map


class MultiLayerNetwork(_TrainingBase):
    _jit_prefix = "mln"

    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__(conf)
        self.impls = None
        self._jit_output = {}
        self._rnn_state = None      # streaming state for rnn_time_step

    # ------------------------------------------------------------------ init
    def _init(self, params):
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
            if getattr(inner, "tied_to", None) is not None:
                raise ValueError(
                    f"Layer {i} ({type(inner).__name__}): tied_to="
                    f"{inner.tied_to!r} names a vertex, and only a "
                    f"ComputationGraph has vertices: a MultiLayerNetwork "
                    f"gives every layer its own parameters")
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
        self._init_layers([(str(i), impl, layer_keys[i])
                           for i, impl in enumerate(self.impls)], params)
        # one updater per layer: per-layer override or global default
        self._init_updater({
            str(i): getattr(lc, "updater", None) or self.gc.updater
            for i, lc in enumerate(layers)})

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

    # --------------------------------- what nn/training.py asks of a container
    _adapt_inputs = _adapt_input      # the one stream is the inputs

    _batch_streams = staticmethod(_device_arrays)   # bare arrays

    def _layers(self):
        """``(params key, layer conf, impl)``; stream state is keyed by the
        impl's integer ``index``, parameters by its string."""
        return ((str(i), lc, impl) for i, (lc, impl)
                in enumerate(zip(self.conf.layers, self.impls)))

    def _before_fit(self, iterator):
        if self.conf.pretrain and not getattr(self, "_pretrained", False):
            self.pretrain(iterator)
            self._pretrained = True

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

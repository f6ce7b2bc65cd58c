"""Layer implementation protocol and registry.

TPU-native equivalent of the reference's ``Layer`` runtime interface
(reference ``nn/api/Layer.java:38``: ``activate``/``backpropGradient``/``preOutput``)
and the per-layer impl tree ``nn/layers/`` (SURVEY.md §2.1 "Layer impls").

Central idiom shift (SURVEY.md §7 Phase 0): the reference dispatches every op over
JNI and hand-writes ``backpropGradient`` per layer; here each layer is a *pure
function* ``forward(params, state, x) -> (y, state)`` traced once into the jitted
training step, and the backward pass is ``jax.grad`` of the whole step. There is no
per-layer backprop code to keep in sync with forward — the cuDNN-helper
pattern (``ConvolutionLayer.java:76`` reflective Cudnn*Helper loading) maps to XLA
fusing + optional Pallas kernels registered per layer type in ``ops/``.

Every impl exposes:
 - ``init(rng) -> (params, state)``: params = trainable pytree ({"W": ..., "b": ...},
   reference param-name parity), state = non-trainable (BN running stats, ...)
 - ``forward(params, state, x, train, rng, mask, ctx) -> (y, new_state)``
 - ``regularization(params) -> scalar`` (l1/l2 penalty contribution)
"""
from __future__ import annotations

from typing import Dict, Optional, Type

import jax
import jax.numpy as jnp

from ..activations import get_activation
from ..weights import init_weight, host_full, WeightInit
from ..conf.layers import BaseLayer

_IMPL_REGISTRY: Dict[str, Type["LayerImpl"]] = {}


def implements(*config_class_names):
    def deco(cls):
        for n in config_class_names:
            _IMPL_REGISTRY[n] = cls
        return cls
    return deco


def impl_for(conf, global_conf, input_type=None) -> "LayerImpl":
    name = type(conf).__name__
    if name not in _IMPL_REGISTRY:
        raise ValueError(f"No layer implementation registered for config '{name}'")
    return _IMPL_REGISTRY[name](conf, global_conf, input_type)


def _resolved(conf, gc, field, default=None):
    v = getattr(conf, field, None)
    if v is None:
        v = getattr(gc, field, None)
    if v is None:
        v = default
    return v


class LayerImpl:
    """Base implementation; resolves per-layer vs global config fields."""

    #: Whether this layer's output is worth storing for the backward pass.
    #: Under the train step's remat policy (``GlobalConfig.remat``), outputs of
    #: layers with ``save_output=True`` (convs, gemms, pooling — expensive to
    #: recompute) are checkpointed; cheap elementwise layers (BN normalize,
    #: activations, dropout, padding) are recomputed during the backward pass
    #: instead of being written to and re-read from HBM. This is the TPU
    #: answer to the reference's workspace memory management
    #: (``WorkspaceMode``, ``nn/conf/WorkspaceMode.java``): activation
    #: residency is a compiler-visible policy, not a buffer pool.
    save_output = True

    def __init__(self, conf, gc, input_type=None):
        self.conf = conf
        self.gc = gc
        self.input_type = input_type
        self.dtype = jnp.dtype(gc.dtype)
        self.compute_dtype = jnp.dtype(gc.compute_dtype)
        # Mixed-precision activation policy: params live in `dtype` (f32
        # master copies), activations flow between layers in the compute
        # dtype when it is sub-32-bit (bfloat16). Casting every layer output
        # back to f32 — the naive reading of the reference's single global
        # dtype — doubles HBM traffic on conv nets, and HBM bandwidth is the
        # TPU bottleneck (see PERF.md).
        self.out_dtype = (self.compute_dtype
                          if self.compute_dtype.itemsize < 4 else self.dtype)
        if isinstance(conf, BaseLayer):
            self.activation_name = _resolved(conf, gc, "activation", "identity")
            self.activation = get_activation(self.activation_name)
            self.weight_init = _resolved(conf, gc, "weight_init", WeightInit.XAVIER)
            self.dist = _resolved(conf, gc, "dist")
            self.bias_init = float(_resolved(conf, gc, "bias_init", 0.0))
            self.l1 = float(_resolved(conf, gc, "l1", 0.0))
            self.l2 = float(_resolved(conf, gc, "l2", 0.0))
            self.l1_bias = float(_resolved(conf, gc, "l1_bias", 0.0))
            self.l2_bias = float(_resolved(conf, gc, "l2_bias", 0.0))
        from ..conf.dropout import resolve_dropout
        # float (retain prob) or IDropout object → unified apply() object
        self.dropout_p = _resolved(conf, gc, "dropout")
        self.dropout_obj = resolve_dropout(self.dropout_p)
        self.weight_noise = getattr(conf, "weight_noise", None)

    # ------------------------------------------------------------------
    def init(self, rng):
        return {}, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        raise NotImplementedError

    # ------------------------------------------------------------------
    def _init_w(self, rng, shape, fan_in, fan_out):
        return init_weight(rng, shape, fan_in, fan_out, self.weight_init, self.dist,
                           self.dtype)

    def _init_b(self, shape, value=None):
        v = self.bias_init if value is None else value
        return host_full(shape, v, self.dtype)

    def maybe_dropout(self, x, train, rng):
        """Input dropout/noise (reference ``BaseLayer.preOutput`` input
        dropout). Accepts the float retain-probability shorthand or any
        IDropout object (Dropout, AlphaDropout, GaussianDropout,
        GaussianNoise)."""
        if self.dropout_obj is None or not train or rng is None:
            return x
        return self.dropout_obj.apply(x, rng, train)

    def noised_params(self, params, train, rng):
        """Apply weight noise (DropConnect/WeightNoise) for this forward pass
        (reference ``weightnoise`` applied on param views per iteration)."""
        wn = self.weight_noise
        if wn is None or not train or rng is None or not params:
            return params
        out = {}
        for i, (k, v) in enumerate(params.items()):
            out[k] = wn.apply_to_weights(v, k, jax.random.fold_in(rng, i),
                                         train)
        return out

    def cast_in(self, *arrays):
        """Cast to compute dtype (bfloat16 policy targets the MXU)."""
        out = tuple(a.astype(self.compute_dtype) if a is not None else None
                    for a in arrays)
        return out if len(out) > 1 else out[0]

    def regularization(self, params):
        """L1/L2 penalty, matching reference ``BaseLayer.calcL1/calcL2``:
        applied to weight params ("W"-like) and biases separately."""
        if not params:
            return 0.0
        total = 0.0
        for k, v in params.items():
            if _is_bias_key(k):
                if self.l1_bias:
                    total = total + self.l1_bias * jnp.sum(jnp.abs(v))
                if self.l2_bias:
                    total = total + 0.5 * self.l2_bias * jnp.sum(v * v)
            else:
                if self.l1:
                    total = total + self.l1 * jnp.sum(jnp.abs(v))
                if self.l2:
                    total = total + 0.5 * self.l2 * jnp.sum(v * v)
        return total

    def num_params(self, params):
        return sum(int(v.size) for v in jax.tree_util.tree_leaves(params))


def remat_enabled(gc, impls) -> bool:
    """Whether the jitted train step should run under the named-saveable
    remat policy (``GlobalConfig.remat``). "auto" enables it for
    convolutional feed-forward nets — where activation HBM round-trips
    dominate the step — and leaves recurrent nets alone (scan residuals
    interact badly with whole-step remat)."""
    mode = getattr(gc, "remat", "off")
    if mode == "on":
        return True
    if mode != "auto":
        return False

    def unwrap(i):
        # wrapper impls (Frozen, Bidirectional, LastTimeStep) hide the inner
        # layer behind .inner — recurse so a wrapped LSTM still counts as
        # recurrent
        seen = []
        while i is not None:
            seen.append(i)
            i = getattr(i, "inner", None)
        return seen

    flat = [j for i in impls for j in unwrap(i)]
    has_conv = any(getattr(j.conf, "kernel_size", None) is not None
                   for j in flat)
    # scan-carrying layers (true RNNs) defeat the named-saveable policy;
    # attention has a stream state (KV cache) but its training forward is
    # scan-free, so it must not disable remat for conv+attention nets
    has_rnn = any(hasattr(j, "init_stream_state")
                  and not getattr(j, "scan_free_training", False)
                  for j in flat)
    return has_conv and not has_rnn


#: jax.checkpoint policy saving exactly the tensors the layer protocol tags:
#: layer outputs flagged ``save_output`` ("dl4j_act") and BN statistics
#: ("dl4j_stat"). Everything else is recomputed during the backward pass.
def remat_policy():
    return jax.checkpoint_policies.save_only_these_names("dl4j_act",
                                                         "dl4j_stat")


#: the one name the flash kernels give the residuals their backward reads
#: (``ops/flash_attention.py``, ``_flash_fwd``)
FLASH_RES = "dl4j_flash_res"
#: the name a block gives a sub-layer's output where a norm reads it: a
#: norm's backward reads the norm's input, and nothing else in the backward
#: sweep reads that output (``looped.LoopedBlockStackImpl.block``)
NORM_IN = "dl4j_norm_in"
#: the name a chunked scan gives the state that one of its checkpointed
#: segments is handed (``kda.delta_rule_chunked``): with it and the segments'
#: read-out kept, a block's backward runs no segment's forward but the one
#: the segment's own checkpoint asks for
SCAN_CARRY = "dl4j_scan_carry"
#: the name a chunked scan gives the values of a chunk that are costly to
#: form and small to hold (``kda.delta_rule_segment``: the delta rule's two
#: decayed products and its triangular inverse): a backward that finds them
#: kept forms none of them a second time
CHUNK_MATS = "dl4j_chunk_mats"
#: one object for every stack and run: jax caches a checkpoint's partial
#: evaluation by its policy, and like sub-programs of two runs stay one
_BLOCK_POLICY = jax.checkpoint_policies.save_only_these_names(
    FLASH_RES, NORM_IN, SCAN_CARRY, CHUNK_MATS)


def block_checkpoint(block):
    """``block`` under the block stacks' checkpoint: a block application
    keeps its input, what the flash kernels' backward reads, what a
    post-norm reads (a sub-layer's output that the block named
    ``NORM_IN``: the projection that made it is not run again for the
    norm's backward alone), the states a chunked scan's segments were
    handed (``SCAN_CARRY``) and the in-chunk matrices such a scan named
    ``CHUNK_MATS`` (the delta rule's ``A``, ``B`` and triangular inverse:
    its backward forms none of them again), and recomputes the rest
    backward. A block with no flash call (the dense path, a state-space
    block) and no post-norm (the hybrid stack's) tags nothing and keeps its
    input alone."""
    return jax.checkpoint(block, policy=_BLOCK_POLICY)


def acc_dtype(compute_dtype):
    """Accumulator/stats dtype: f32 when computing in a sub-32-bit dtype
    (bf16/f16), otherwise the compute dtype itself — forcing f32 under f64
    compute would silently truncate, breaking the f64 gradient-check path.
    Used for BN statistics, RNN carries and softmax accumulation."""
    cd = jnp.dtype(compute_dtype)
    return jnp.dtype(jnp.float32) if cd.itemsize < 4 else cd


def pet_dtype(compute_dtype):
    """``preferred_element_type`` for dots/convs. For sub-32-bit compute the
    answer is None: XLA's TPU MXU already accumulates bf16 operands in f32
    internally, and requesting an f32 *output* breaks the conv-transpose
    dtype rule under AD (cotangent f32 vs operand bf16). For f32/f64 compute
    the compute dtype itself keeps results exact."""
    cd = jnp.dtype(compute_dtype)
    return None if cd.itemsize < 4 else cd


def _is_bias_key(k: str) -> bool:
    return k == "b" or k.endswith("_b") or k in ("beta",)


class NoParamLayerImpl(LayerImpl):
    def init(self, rng):
        return {}, {}

    def regularization(self, params):
        return 0.0

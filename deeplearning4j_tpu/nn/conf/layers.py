"""Layer configuration classes.

TPU-native equivalent of reference ``nn/conf/layers/`` (41 config classes,
SURVEY.md §2.1 "Layer configs"): one dataclass per layer type, JSON-serializable
via :mod:`..conf.serde`, with shape-inference hooks (``get_output_type``,
``set_n_in``, ``preprocessor_for``) mirroring the reference's
``Layer.getOutputType/setNIn/getPreProcessorForInputType`` used by
``ListBuilder.setInputType`` (reference ``NeuralNetConfiguration.java:215-324``).

Layer *implementations* (init/forward as pure JAX functions) live in
``deeplearning4j_tpu.nn.layers`` and are looked up by config class name.

Note on dropout: following the reference's 0.9.x semantics, ``dropout`` is the
**retain probability** (1.0 = keep everything / disabled).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Tuple

from .serde import register
from .inputs import (InputTypeConvolutional, InputTypeConvolutionalFlat,
                     InputTypeFeedForward, InputTypeLoopedRecurrent,
                     InputTypeRecurrent)

__all__ = [
    "Layer", "BaseLayer", "FeedForwardLayer", "DenseLayer", "ConvolutionLayer",
    "Convolution1DLayer", "SeparableConvolution2D", "Deconvolution2D",
    "SubsamplingLayer", "Subsampling1DLayer", "PoolingType",
    "Upsampling1D", "Upsampling2D", "ZeroPaddingLayer", "ZeroPadding1DLayer",
    "Cropping2D", "SpaceToDepthLayer", "DepthwiseConvolution2D",
    "BatchNormalization", "LocalResponseNormalization", "ActivationLayer",
    "DropoutLayer", "EmbeddingLayer", "EmbeddingSequenceLayer", "LSTM", "GravesLSTM",
    "GravesBidirectionalLSTM", "SimpleRnn", "Bidirectional", "LastTimeStep",
    "OutputLayer", "RnnOutputLayer", "LossLayer", "CenterLossOutputLayer",
    "AutoEncoder", "VariationalAutoencoder", "GlobalPoolingLayer",
    "Yolo2OutputLayer", "FrozenLayer", "ConvolutionMode", "SelfAttentionLayer",
    "MoEDenseLayer", "RMSNorm", "GatedDenseLayer", "LoopedBlockStack",
    "LoopLMOutputLayer", "Mamba2Layer", "HybridBlockStack",
]


class ConvolutionMode:
    """Reference ``nn/conf/ConvolutionMode.java``: Strict/Truncate/Same."""
    Strict = "strict"
    Truncate = "truncate"
    Same = "same"


class PoolingType:
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    PNORM = "pnorm"


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (list, tuple)):
        if len(v) == 1:
            return (int(v[0]), int(v[0]))
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def conv_out_size(in_size, k, s, p, d, mode):
    """Output spatial size (reference ``util/ConvolutionUtils.getOutputSize``)."""
    eff_k = (k - 1) * d + 1
    if mode == ConvolutionMode.Same:
        return int(math.ceil(in_size / s))
    return (in_size - eff_k + 2 * p) // s + 1


@register
@dataclasses.dataclass
class Layer:
    """Base config: fields shared by every layer (reference ``nn/conf/layers/Layer.java``)."""
    name: Optional[str] = None
    dropout: Optional[float] = None  # retain probability, reference semantics

    # shape inference hooks -------------------------------------------------
    def get_output_type(self, index, input_type):
        return input_type

    def set_n_in(self, input_type, override=False):
        pass

    def preprocessor_for(self, input_type):
        return None

    def is_pretrain_layer(self):
        return False

    def initializer_keys(self):
        return []


@register
@dataclasses.dataclass
class BaseLayer(Layer):
    """Layers with weights: activation/init/regularization/updater overrides
    (reference ``nn/conf/layers/BaseLayer.java``)."""
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    dist: Optional[Any] = None
    bias_init: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    updater: Optional[Any] = None  # per-layer IUpdater override
    weight_noise: Optional[Any] = None
    constraints: Optional[List[Any]] = None


@register
@dataclasses.dataclass
class FeedForwardLayer(BaseLayer):
    """Reference ``nn/conf/layers/FeedForwardLayer.java``: has nIn/nOut."""
    n_in: Optional[int] = None
    n_out: Optional[int] = None

    def get_output_type(self, index, input_type):
        return InputTypeFeedForward(self.n_out)

    def set_n_in(self, input_type, override=False):
        if self.n_in is None or override:
            self.n_in = input_type.arity()

    def preprocessor_for(self, input_type):
        from .preprocessors import (CnnToFeedForwardPreProcessor,
                                    RnnToFeedForwardPreProcessor)
        if isinstance(input_type, (InputTypeConvolutional, InputTypeConvolutionalFlat)):
            return CnnToFeedForwardPreProcessor(input_type.height, input_type.width,
                                                input_type.channels)
        if isinstance(input_type, InputTypeRecurrent):
            return RnnToFeedForwardPreProcessor()
        return None


@register
@dataclasses.dataclass
class DenseLayer(FeedForwardLayer):
    """Fully connected layer (reference ``nn/conf/layers/DenseLayer.java``)."""
    has_bias: bool = True


@register
@dataclasses.dataclass
class GatedDenseLayer(FeedForwardLayer):
    """Gated feed-forward block (SwiGLU with the default activation):
    ``(act(x Wgate) * (x Wup)) Wdown`` with ``n_hidden`` units between two
    ``n_in`` → ``n_out`` sides. Net-new vs the 0.9.x reference; applied per
    position, so [b, F] and [b, T, F] both pass through as they are."""
    n_hidden: Optional[int] = None
    activation: Optional[str] = "silu"

    def get_output_type(self, index, input_type):
        if isinstance(input_type, InputTypeRecurrent):
            return InputTypeRecurrent(self.n_out, input_type.timeseries_length)
        return InputTypeFeedForward(self.n_out)

    def preprocessor_for(self, input_type):
        return None


@register
@dataclasses.dataclass
class MoEDenseLayer(FeedForwardLayer):
    """Mixture-of-experts dense layer — net-new vs the 0.9.x reference
    (like :class:`SelfAttentionLayer`), included because expert parallelism
    is a first-class mesh axis in the TPU build: the expert dim of the
    parameters shards over the ``expert`` mesh axis
    (``parallel/expert.py``), XLA partitioning the per-expert einsums.

    Dense (Shazeer-style) top-k routing: every token's input reaches each
    local expert shard, gate weights zero the non-selected experts, and the
    expert-dim reduction becomes a psum over the axis. ``aux_loss_weight``
    scales the Switch-Transformer load-balancing loss, accumulated through
    the forward ``ctx`` into the training objective."""
    num_experts: int = 4
    top_k: int = 2
    aux_loss_weight: float = 1e-2
    has_bias: bool = True
    #: > 0 enables SPARSE capacity-factor dispatch IN THE TRAIN STEP: each
    #: expert processes at most ``ceil(top_k * tokens * capacity_factor /
    #: num_experts)`` tokens (lane-aligned), so per-step FLOPs scale with
    #: ``top_k/num_experts`` instead of paying every expert for every
    #: token; over-capacity (token, expert) assignments are dropped,
    #: Switch-Transformer style — raise the factor if exact parity with
    #: dense routing matters more than FLOPs. Inference (train=False)
    #: always routes exactly via the dense combine, so output/score/
    #: streaming agree regardless of batch shape. 0 keeps the dense einsum
    #: path everywhere (the correctness oracle).
    capacity_factor: float = 0.0
    #: token-group size for the sparse dispatch (GShard "group" dim):
    #: capacity is enforced PER GROUP of this many tokens, so the one-hot
    #: dispatch tensor is [groups, G, E, C_g] with C_g ∝ G — memory linear
    #: in token count instead of quadratic ([n, E, C] with C ∝ n). Smaller
    #: groups = less dispatch memory but more capacity fragmentation
    #: (drops decided within each group). Token counts that don't divide
    #: evenly are zero-gate padded to a group multiple.
    group_size: int = 1024


@register
@dataclasses.dataclass
class RoutedExpertsLayer(FeedForwardLayer):
    """Routed gated-expert feed-forward layer with a shared expert, of which
    this chip holds its share (net-new vs the 0.9.x reference; a class
    beside :class:`MoEDenseLayer`, whose experts are single projections
    routed by a softmax with a capacity). ``num_experts`` is the published
    count, the router's width; ``experts_held`` the ids whose weights live
    here (None: all of them). Over [b, T, n_in] or [b, n_in], per position:

        s   = sigmoid(x Wr)                           [num_experts], float32
        top = the top_k largest of s + b              b: state, choice only
        w_e = routed_scaling_factor * s_e / (sum_{j in top} s_j + 1e-20)
                                                      (``renormalize``)
        y   = Shared(x) + sum_{e in top and held} w_e Expert_e(x)

    with ``Expert_e(x) = (silu(x Wgate_e) * (x Wup_e)) Wdown_e`` of
    ``n_hidden`` units and ``Shared`` the same of ``shared_hidden`` (0:
    none). The sum over ``top`` is the published layer; over the held ones
    it is this chip's part, and nothing stands in for the chips that hold
    the others. No choice is dropped whatever the routing: the held choices
    are sorted by expert, each expert's rows padded to whole tiles, and one
    loop runs an expert's three products tile by tile: over a standing number
    of tiles, twice what uniform routing sends here, so that a router that
    leans costs what a level one does, and over the tiles in use where those
    are more (``nn/layers/moe.py: tile_plan`` derives both from the tokens,
    ``top_k`` and the two expert counts)."""
    num_experts: int = 8
    experts_held: Optional[List[int]] = None
    top_k: int = 2
    n_hidden: Optional[int] = None
    shared_hidden: Optional[int] = None
    renormalize: bool = True
    routed_scaling_factor: float = 1.0
    #: ``"sigmoid"`` (above, with the score-correction bias) or
    #: ``"softmax"``: ``s = softmax(x Wr)`` over all ``num_experts`` in
    #: float32 and no bias, the rest as above
    score: str = "sigmoid"
    activation: Optional[str] = "identity"

    def get_output_type(self, index, input_type):
        if isinstance(input_type, InputTypeRecurrent):
            return InputTypeRecurrent(self.n_out, input_type.timeseries_length)
        return InputTypeFeedForward(self.n_out)

    def preprocessor_for(self, input_type):
        return None


@register
@dataclasses.dataclass
class ConvolutionLayer(FeedForwardLayer):
    """2-D convolution (reference ``nn/conf/layers/ConvolutionLayer.java``).

    ``n_in`` = input channels, ``n_out`` = output channels. The reference's
    cuDNN algo-mode knobs (``cudnnAlgoMode`` etc.) have no TPU meaning; XLA
    picks conv algorithms. Kernel layout is HWIO internally (MXU-friendly).
    """
    kernel_size: Tuple[int, int] = (5, 5)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = ConvolutionMode.Truncate
    has_bias: bool = True

    def get_output_type(self, index, input_type):
        if not isinstance(input_type, InputTypeConvolutional):
            raise ValueError(f"ConvolutionLayer '{self.name}' needs convolutional "
                             f"input, got {input_type}")
        k, s, p, d = _pair(self.kernel_size), _pair(self.stride), _pair(self.padding), _pair(self.dilation)
        h = conv_out_size(input_type.height, k[0], s[0], p[0], d[0], self.convolution_mode)
        w = conv_out_size(input_type.width, k[1], s[1], p[1], d[1], self.convolution_mode)
        return InputTypeConvolutional(h, w, self.n_out)

    def set_n_in(self, input_type, override=False):
        if self.n_in is None or override:
            self.n_in = input_type.channels

    def preprocessor_for(self, input_type):
        from .preprocessors import FeedForwardToCnnPreProcessor, RnnToCnnPreProcessor
        if isinstance(input_type, InputTypeConvolutionalFlat):
            return FeedForwardToCnnPreProcessor(input_type.height, input_type.width,
                                                input_type.channels)
        return None


@register
@dataclasses.dataclass
class Convolution1DLayer(ConvolutionLayer):
    """1-D convolution over [batch, channels, length] (reference
    ``nn/conf/layers/Convolution1DLayer.java``)."""

    def get_output_type(self, index, input_type):
        if not isinstance(input_type, InputTypeRecurrent):
            raise ValueError("Convolution1DLayer needs recurrent input")
        k, s, p, d = _pair(self.kernel_size)[0], _pair(self.stride)[0], _pair(self.padding)[0], _pair(self.dilation)[0]
        t = input_type.timeseries_length
        t_out = None if t is None else conv_out_size(t, k, s, p, d, self.convolution_mode)
        return InputTypeRecurrent(self.n_out, t_out)

    def set_n_in(self, input_type, override=False):
        if self.n_in is None or override:
            self.n_in = input_type.size

    def preprocessor_for(self, input_type):
        return None


@register
@dataclasses.dataclass
class DepthwiseConvolution2D(ConvolutionLayer):
    depth_multiplier: int = 1

    def set_n_in(self, input_type, override=False):
        super().set_n_in(input_type, override)
        # depthwise output channels are determined: n_in × depth_multiplier
        if self.n_out is None and self.n_in is not None:
            self.n_out = self.n_in * int(self.depth_multiplier)


@register
@dataclasses.dataclass
class SeparableConvolution2D(ConvolutionLayer):
    depth_multiplier: int = 1


@register
@dataclasses.dataclass
class Deconvolution2D(ConvolutionLayer):
    """Transposed convolution."""

    def get_output_type(self, index, input_type):
        k, s, p, d = _pair(self.kernel_size), _pair(self.stride), _pair(self.padding), _pair(self.dilation)
        if self.convolution_mode == ConvolutionMode.Same:
            h = input_type.height * s[0]
            w = input_type.width * s[1]
        else:
            h = s[0] * (input_type.height - 1) + (k[0] - 1) * d[0] + 1 - 2 * p[0]
            w = s[1] * (input_type.width - 1) + (k[1] - 1) * d[1] + 1 - 2 * p[1]
        return InputTypeConvolutional(h, w, self.n_out)


@register
@dataclasses.dataclass
class SubsamplingLayer(Layer):
    """Spatial pooling (reference ``nn/conf/layers/SubsamplingLayer.java``)."""
    pooling_type: str = PoolingType.MAX
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = ConvolutionMode.Truncate
    pnorm: Optional[int] = None
    eps: float = 1e-8

    def get_output_type(self, index, input_type):
        if not isinstance(input_type, InputTypeConvolutional):
            raise ValueError("SubsamplingLayer needs convolutional input")
        k, s, p, d = _pair(self.kernel_size), _pair(self.stride), _pair(self.padding), _pair(self.dilation)
        h = conv_out_size(input_type.height, k[0], s[0], p[0], d[0], self.convolution_mode)
        w = conv_out_size(input_type.width, k[1], s[1], p[1], d[1], self.convolution_mode)
        return InputTypeConvolutional(h, w, input_type.channels)


@register
@dataclasses.dataclass
class Subsampling1DLayer(SubsamplingLayer):
    def get_output_type(self, index, input_type):
        if not isinstance(input_type, InputTypeRecurrent):
            raise ValueError("Subsampling1DLayer needs recurrent input")
        k, s, p, d = _pair(self.kernel_size)[0], _pair(self.stride)[0], _pair(self.padding)[0], _pair(self.dilation)[0]
        t = input_type.timeseries_length
        t_out = None if t is None else conv_out_size(t, k, s, p, d, self.convolution_mode)
        return InputTypeRecurrent(input_type.size, t_out)


@register
@dataclasses.dataclass
class Upsampling2D(Layer):
    size: Tuple[int, int] = (2, 2)

    def get_output_type(self, index, input_type):
        s = _pair(self.size)
        return InputTypeConvolutional(input_type.height * s[0], input_type.width * s[1],
                                      input_type.channels)


@register
@dataclasses.dataclass
class Upsampling1D(Layer):
    size: int = 2

    def get_output_type(self, index, input_type):
        t = input_type.timeseries_length
        return InputTypeRecurrent(input_type.size, None if t is None else t * int(self.size))


@register
@dataclasses.dataclass
class ZeroPaddingLayer(Layer):
    """[top, bottom, left, right] padding (reference ``ZeroPaddingLayer.java``)."""
    padding: Tuple[int, int, int, int] = (0, 0, 0, 0)

    def _pads(self):
        p = list(self.padding)
        if len(p) == 2:
            p = [p[0], p[0], p[1], p[1]]
        return p

    def get_output_type(self, index, input_type):
        p = self._pads()
        return InputTypeConvolutional(input_type.height + p[0] + p[1],
                                      input_type.width + p[2] + p[3],
                                      input_type.channels)


@register
@dataclasses.dataclass
class ZeroPadding1DLayer(Layer):
    padding: Tuple[int, int] = (0, 0)

    def get_output_type(self, index, input_type):
        p = _pair(self.padding)
        t = input_type.timeseries_length
        return InputTypeRecurrent(input_type.size, None if t is None else t + p[0] + p[1])


@register
@dataclasses.dataclass
class Cropping2D(Layer):
    cropping: Tuple[int, int, int, int] = (0, 0, 0, 0)

    def _crops(self):
        c = list(self.cropping)
        if len(c) == 2:
            c = [c[0], c[0], c[1], c[1]]
        return c

    def get_output_type(self, index, input_type):
        c = self._crops()
        return InputTypeConvolutional(input_type.height - c[0] - c[1],
                                      input_type.width - c[2] - c[3],
                                      input_type.channels)


@register
@dataclasses.dataclass
class SpaceToDepthLayer(Layer):
    block_size: int = 2

    def get_output_type(self, index, input_type):
        b = int(self.block_size)
        return InputTypeConvolutional(input_type.height // b, input_type.width // b,
                                      input_type.channels * b * b)


@register
@dataclasses.dataclass
class BatchNormalization(FeedForwardLayer):
    """Reference ``nn/conf/layers/BatchNormalization.java``. ``decay`` is the
    running-stats momentum; gamma/beta trainable unless ``lock_gamma_beta``."""
    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0
    beta: float = 0.0
    lock_gamma_beta: bool = False

    def get_output_type(self, index, input_type):
        return input_type

    def set_n_in(self, input_type, override=False):
        if self.n_in is None or override:
            self.n_in = (input_type.channels if isinstance(input_type, InputTypeConvolutional)
                         else input_type.arity())
        self.n_out = self.n_in

    def preprocessor_for(self, input_type):
        return None


@register
@dataclasses.dataclass
class LayerNormalization(FeedForwardLayer):
    """Per-token normalization over the FEATURE dim with learned gain/bias —
    net-new vs the 0.9.x reference (which predates transformers; its only
    norms are Batch/LRN, ``nn/conf/layers/BatchNormalization.java``).
    Included because the transformer family (SelfAttentionLayer, MoEDense,
    TransformerLM) is first-class in the TPU build: LN is stateless (no
    running stats ⇒ no cross-replica/shard state to reconcile), normalizes
    each position independently (works for [b, F] and [b, T, F], and the
    time dim may be sharded — sp-safe by construction), and XLA fuses the
    two-moment pass into neighbouring elementwise work."""
    eps: float = 1e-5

    def get_output_type(self, index, input_type):
        return input_type

    def set_n_in(self, input_type, override=False):
        if self.n_in is None or override:
            self.n_in = input_type.arity()
        self.n_out = self.n_in

    def preprocessor_for(self, input_type):
        return None


@register
@dataclasses.dataclass
class RMSNorm(LayerNormalization):
    """Root-mean-square normalization over the feature dim with a learned
    gain and no bias: ``x / sqrt(mean(x^2) + eps) * gain``, the statistics in
    float32 (Zhang, Sennrich 2019; the norm of the decoder-only families
    after 2023). Net-new like :class:`LayerNormalization`, and stateless and
    per-position like it."""
    eps: float = 1e-6


@register
@dataclasses.dataclass
class LocalResponseNormalization(Layer):
    """Reference ``nn/conf/layers/LocalResponseNormalization.java``."""
    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75


@register
@dataclasses.dataclass
class ActivationLayer(BaseLayer):
    pass


@register
@dataclasses.dataclass
class DropoutLayer(FeedForwardLayer):
    def get_output_type(self, index, input_type):
        return input_type

    def set_n_in(self, input_type, override=False):
        pass

    def preprocessor_for(self, input_type):
        return None


@register
@dataclasses.dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Index → vector lookup, one index per example
    (reference ``nn/conf/layers/EmbeddingLayer.java``)."""
    has_bias: bool = True


@register
@dataclasses.dataclass
class EmbeddingSequenceLayer(FeedForwardLayer):
    """Index sequence → vector sequence (added post-0.9 in the reference line;
    included for NLP-model parity)."""
    has_bias: bool = False
    #: the looked-up vectors are multiplied by it (an embedding multiplier);
    #: None multiplies nothing
    scale: Optional[float] = None

    def get_output_type(self, index, input_type):
        t = input_type.timeseries_length if isinstance(input_type, InputTypeRecurrent) else None
        return InputTypeRecurrent(self.n_out, t)

    def preprocessor_for(self, input_type):
        # consumes [b, T] token ids directly — a recurrent input type
        # describes the SEQUENCE (vocab arity), never a tensor to flatten
        return None


@register
@dataclasses.dataclass
class BaseRecurrentLayer(FeedForwardLayer):
    def get_output_type(self, index, input_type):
        t = input_type.timeseries_length if isinstance(input_type, InputTypeRecurrent) else None
        return InputTypeRecurrent(self.n_out, t)

    def set_n_in(self, input_type, override=False):
        if self.n_in is None or override:
            self.n_in = input_type.size

    def preprocessor_for(self, input_type):
        from .preprocessors import (FeedForwardToRnnPreProcessor, CnnToRnnPreProcessor)
        if isinstance(input_type, InputTypeFeedForward):
            return FeedForwardToRnnPreProcessor()
        if isinstance(input_type, InputTypeConvolutional):
            return CnnToRnnPreProcessor(input_type.height, input_type.width,
                                        input_type.channels)
        return None


@register
@dataclasses.dataclass
class LSTM(BaseRecurrentLayer):
    """Standard LSTM, no peepholes (reference ``nn/conf/layers/LSTM.java``);
    compiled as a fused-gate ``lax.scan`` on TPU (one [4H] gemm per step)."""
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"


@register
@dataclasses.dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (reference ``GravesLSTM.java``,
    ``LSTMHelpers.java:68``)."""
    pass


@register
@dataclasses.dataclass
class GravesBidirectionalLSTM(GravesLSTM):
    """Two independent GravesLSTMs run forward and backward over time, with
    per-direction parameter sets; direction outputs are summed so the layer
    output stays nOut-sized (reference ``GravesBidirectionalLSTM.java``)."""

    def get_output_type(self, index, input_type):
        t = input_type.timeseries_length if isinstance(input_type, InputTypeRecurrent) else None
        return InputTypeRecurrent(self.n_out, t)


@register
@dataclasses.dataclass
class SimpleRnn(BaseRecurrentLayer):
    pass


@register
@dataclasses.dataclass
class Bidirectional(Layer):
    """Wrapper running an inner recurrent layer in both directions.
    ``mode``: concat | add | mul | ave (reference 1.0 line ``Bidirectional.java``)."""
    inner: Optional[Any] = None
    mode: str = "concat"

    def get_output_type(self, index, input_type):
        out = self.inner.get_output_type(index, input_type)
        if self.mode == "concat":
            out = InputTypeRecurrent(out.size * 2, out.timeseries_length)
        return out

    def set_n_in(self, input_type, override=False):
        self.inner.set_n_in(input_type, override)

    def preprocessor_for(self, input_type):
        return self.inner.preprocessor_for(input_type)


@register
@dataclasses.dataclass
class LastTimeStep(Layer):
    """Wrapper extracting the last (mask-aware) timestep of an inner RNN layer."""
    inner: Optional[Any] = None

    def get_output_type(self, index, input_type):
        out = self.inner.get_output_type(index, input_type)
        return InputTypeFeedForward(out.size)

    def set_n_in(self, input_type, override=False):
        self.inner.set_n_in(input_type, override)

    def preprocessor_for(self, input_type):
        return self.inner.preprocessor_for(input_type)


@register
@dataclasses.dataclass
class SelfAttentionLayer(BaseRecurrentLayer):
    """Multi-head self-attention over a sequence — net-new vs the 0.9.x reference
    (which has no attention, SURVEY.md §5 "Long-context"); included because
    long-context/sequence-parallel support is first-class in the TPU build.
    Supports ring-attention sequence parallelism (see ``parallel/sequence.py``)."""
    num_heads: int = 4
    head_dim: Optional[int] = None
    causal: bool = True
    dropout_rate: float = 0.0
    #: KV-cache capacity for streaming inference (``rnn_time_step``) and
    #: cross-segment TBPTT attention; static so the cached step keeps one
    #: compiled shape. Streams beyond this length roll over the tail.
    stream_max_length: int = 512
    #: rotary position embedding base; None (today's behaviour) rotates
    #: nothing. Applied to q and k over the whole head dim, pairing
    #: (i, i + head_dim/2), at the tokens' global positions.
    rope_theta: Optional[float] = None
    #: False drops the output projection's bias (the only bias the layer has)
    has_bias: bool = True
    #: grouped-query attention: ``Wk`` / ``Wv`` make this many heads and
    #: query head i reads key-value head ``i // (num_heads // num_kv_heads)``.
    #: None: as many as ``num_heads``
    num_kv_heads: Optional[int] = None
    #: what the scores are multiplied by before the softmax; None is
    #: ``1 / sqrt(head_dim)``
    attention_scale: Optional[float] = None
    #: the latent layout (multi-head latent attention), a layout of this
    #: layer and no other attention: with a rank here, keys and values come
    #: through one latent of that width,
    #: ``[c | kr] = x Wkv_a``, ``[kn | v] = RMSNorm_gc(c) Wkv_b`` per head,
    #: ``k = [kn | kr]`` with the ``qk_rope_head_dim`` channels ``kr`` shared
    #: by all heads, so the query and key heads are ``qk_nope_head_dim +
    #: qk_rope_head_dim`` wide and the value heads ``v_head_dim``. Leaves
    #: ``Wq``, ``Wkv_a`` [n_in, rank + qk_rope_head_dim], ``gc`` [rank],
    #: ``Wkv_b`` [rank, heads * (qk_nope_head_dim + v_head_dim)], ``Wo``
    #: [heads * v_head_dim, n_out]. ``rope_theta`` rotates whole heads as
    #: ever; left None the shared channels are carried as they are.
    kv_latent_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    latent_norm_eps: float = 1e-5
    #: causal only: query i sees the keys i - window < j <= i (a sliding
    #: window of ``window`` keys, itself among them); None sees every
    #: earlier key. The flash kernels walk such a call as a band of blocks
    window: Optional[int] = None
    #: a published ``rope_parameters`` block that scales ``rope_theta``'s
    #: rotation (``rope_type`` "yarn": ``factor``,
    #: ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    #: ``attention_factor``); None rotates by the plain frequencies
    rope_scaling: Optional[dict] = None


@register
@dataclasses.dataclass
class LoopedBlockStack(BaseRecurrentLayer):
    """``num_blocks`` decoder blocks applied ``num_passes`` times with ONE set
    of weights (a looped / universal transformer; Ouro's LoopLM). A block is
    sandwich-normed: ``a = x + RMSNorm(Attn(RMSNorm(x)))``,
    ``y = a + RMSNorm(SwiGLU(RMSNorm(a)))`` with rotary causal attention and
    no biases; after every pass over the blocks comes a final RMSNorm, and
    its output both feeds the next pass and is handed on. The output is the
    ``num_passes`` normed states stacked [R, b, T, n_out], which
    :class:`LoopLMOutputLayer` reads.

    The blocks' weights are stacked leaf by leaf ``[num_blocks, ...]`` (one
    leaf per kind of weight) and run under ``lax.scan``; the training step
    keeps each block application's input and recomputes the rest in the
    backward pass."""
    num_blocks: int = 1
    num_passes: int = 1
    num_heads: int = 4
    head_dim: Optional[int] = None
    n_hidden: Optional[int] = None
    eps: float = 1e-6
    rope_theta: Optional[float] = 10000.0

    def get_output_type(self, index, input_type):
        t = input_type.timeseries_length if isinstance(input_type, InputTypeRecurrent) else None
        return InputTypeLoopedRecurrent(self.n_out, t, self.num_passes)

    def set_n_in(self, input_type, override=False):
        super().set_n_in(input_type, override)
        if self.n_out is None:
            self.n_out = self.n_in


@register
@dataclasses.dataclass
class Mamba2Layer(BaseRecurrentLayer):
    """Selective state-space mixer (Mamba-2; Dao, Gu 2024) over [b, T, n_in],
    net-new vs the 0.9.x reference. ``num_heads`` heads of ``head_dim``
    channels (``d_inner = num_heads * head_dim``), a state of ``state_size``
    per channel, one group (B and C are shared by all heads):

        [z | xBC | dt] = x W_in;  xBC = silu(conv1d(xBC))   depthwise, causal,
                                                   width ``conv_size``, bias
        [x | B | C] = xBC;  D_t = softplus(dt + dt_bias);  a_t = exp(D_t * A),
                                                            A = -exp(A_log)
        S_t = a_t S_{t-1} + D_t x_t (x) B_t;  y_t = S_t C_t + D x_t
        out = RMSNorm_gn(y * silu(z)) W_out

    The recurrence is computed in chunks of ``chunk_size`` steps: within a
    chunk as masked-decay products, between chunks by the carried state (a
    length that is no multiple of the chunk is padded with steps that leave
    the state alone). No bias in the two projections."""
    num_heads: int = 8
    head_dim: int = 64
    state_size: int = 128
    conv_size: int = 4
    chunk_size: int = 256
    eps: float = 1e-5
    activation: Optional[str] = "identity"

    def set_n_in(self, input_type, override=False):
        super().set_n_in(input_type, override)
        if self.n_out is None:
            self.n_out = self.n_in


@register
@dataclasses.dataclass
class KimiDeltaAttentionLayer(BaseRecurrentLayer):
    """Kimi Delta Attention mixer (Kimi Linear; Moonshot AI 2025) over
    [b, T, n_in], net-new vs the 0.9.x reference: a gated delta rule with one
    decay a key channel. ``num_heads`` heads H of ``head_dim`` channels K for
    keys and values alike:

        q, k, v = silu(conv1d(x Wq)), silu(conv1d(x Wk)), silu(conv1d(x Wv))
                          depthwise, causal, width ``conv_size``, no bias
        q, k    = q / ||q|| * K^-0.5,  k / ||k||       per head (eps 1e-6,
                                                       under the root)
        g_t     = -exp(A_log[h]) softplus((x W_fa W_fb)_t + dt_bias)  [H, K]
        beta_t  = sigmoid(x W_b)_t                                    [H]
        S_t     = Diag(exp(g_t)) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T
        o_t     = S_t^T q_t                            S [K, K] a head, S_0 = 0
        out     = (RMSNorm_gn(o) * sigmoid(x W_ga W_gb)) Wo    gn [K], a head

    The rule is computed in chunks of ``chunk_size`` steps, a unit-lower-
    triangular solve a chunk and head and one state carried across each
    boundary (``nn/layers/kda.py``); a length that is no multiple of the
    chunk is padded with steps that leave the state alone. No bias."""
    num_heads: int = 4
    head_dim: int = 64
    conv_size: int = 4
    chunk_size: int = 64
    eps: float = 1e-5
    activation: Optional[str] = "identity"

    def set_n_in(self, input_type, override=False):
        super().set_n_in(input_type, override)
        if self.n_out is None:
            self.n_out = self.n_in


@register
@dataclasses.dataclass
class HybridBlockStack(BaseRecurrentLayer):
    """A stack of pre-normed decoder blocks, one per entry of
    ``layer_types`` (``"mamba"``: a :class:`Mamba2Layer` mixer; ``"attention"``:
    causal grouped-query attention, rotary where ``rope_theta`` is given;
    ``"window"``: the same in a sliding window of ``window`` keys; ``"kda"``: a
    :class:`KimiDeltaAttentionLayer` mixer; ``"mla"``: causal attention in
    :class:`SelfAttentionLayer`'s latent layout, without positions), each
    followed by what its entry of ``ffn_types`` names (``"dense"``, the
    default: a gated MLP of ``n_hidden`` units; ``"experts"``: a
    :class:`RoutedExpertsLayer`):

        u = h + r * Mixer(RMSNorm(h));   h' = u + r * FFN(RMSNorm(u))

    with ``r = residual_multiplier``, and a final RMSNorm after the last
    block. No biases but the state-space convolution's. Every run of blocks
    alike in mixer and feed-forward keeps its weights stacked leaf by leaf
    ``[n, ...]`` under the keys ``r<run>.<leaf>`` and is one ``lax.scan``;
    the training step keeps each block's input and recomputes the rest in
    the backward pass. The stream between blocks is float32 whatever the
    compute dtype. The experts' score-correction bias of sigmoid scores
    (``expert_score``) is the stack's state (``r<run>.b``), which no
    optimizer moves; softmax scores have none."""
    layer_types: Optional[List[str]] = None
    ffn_types: Optional[List[str]] = None
    n_hidden: Optional[int] = None
    eps: float = 1e-5
    residual_multiplier: float = 1.0
    num_heads: int = 4
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    attention_scale: Optional[float] = None
    mamba_heads: int = 8
    mamba_head_dim: int = 64
    mamba_state_size: int = 128
    mamba_conv_size: int = 4
    mamba_chunk_size: int = 256
    kda_heads: int = 4
    kda_head_dim: int = 64
    kda_conv_size: int = 4
    kda_chunk_size: int = 64
    #: the ``"mla"`` blocks' latent layout (``num_heads`` heads)
    kv_latent_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: rotary positions of the ``"attention"`` blocks (None: none) and
    #: their scaling (:class:`SelfAttentionLayer`'s ``rope_scaling``)
    rope_theta: Optional[float] = None
    rope_scaling: Optional[dict] = None
    #: the ``"window"`` blocks: grouped-query attention of ``num_heads``
    #: over ``num_kv_heads`` in a sliding window of ``window`` keys, rotary
    #: by ``rope_theta`` unscaled
    window: Optional[int] = None
    #: the ``"experts"`` blocks' :class:`RoutedExpertsLayer`
    num_experts: int = 8
    experts_held: Optional[List[int]] = None
    experts_per_token: int = 2
    expert_hidden: Optional[int] = None
    shared_hidden: Optional[int] = None
    renormalize: bool = True
    routed_scaling_factor: float = 1.0
    expert_score: str = "sigmoid"

    def set_n_in(self, input_type, override=False):
        super().set_n_in(input_type, override)
        if self.n_out is None:
            self.n_out = self.n_in


@register
@dataclasses.dataclass
class OutputLayer(FeedForwardLayer):
    """Dense + loss (reference ``nn/conf/layers/OutputLayer.java``)."""
    loss: str = "mcxent"
    has_bias: bool = True


@register
@dataclasses.dataclass
class RnnOutputLayer(OutputLayer):
    #: name of an embedding vertex of the same ``ComputationGraph`` whose
    #: ``W`` [n_out, n_in] is this layer's head, transposed (tied word
    #: embeddings): the layer then has no ``W`` of its own, and the one leaf
    #: is counted, updated, regularised and saved under the embedding's name
    tied_to: Optional[str] = None
    #: the logits are divided by it before the loss (a logits scaling); with
    #: it or the tie the logits are float32 whatever the compute dtype
    logits_divisor: Optional[float] = None

    def get_output_type(self, index, input_type):
        t = input_type.timeseries_length if isinstance(input_type, InputTypeRecurrent) else None
        return InputTypeRecurrent(self.n_out, t)

    def set_n_in(self, input_type, override=False):
        if self.n_in is None or override:
            self.n_in = input_type.size

    def preprocessor_for(self, input_type):
        from .preprocessors import FeedForwardToRnnPreProcessor
        if isinstance(input_type, InputTypeFeedForward):
            return FeedForwardToRnnPreProcessor()
        return None


@register
@dataclasses.dataclass
class LoopLMOutputLayer(RnnOutputLayer):
    """Output layer of a looped language model: reads the [R, b, T, n_in]
    states of a :class:`LoopedBlockStack`, applies the one head to every
    pass, and weighs the passes' next-token losses by a learned exit gate
    (Ouro's stage-I objective): per token ``lambda_t = sigmoid(h_t . w + b)``,
    ``p_t = lambda_t * prod_{j<t}(1 - lambda_j)`` with the last pass taking
    the remainder, and the loss ``sum_t p_t * xent_t - entropy_weight *
    H(p)``, reduced as ``sparse_mcxent`` reduces. Labels are integer ids
    [b, T]. Inference returns the last pass's softmax.

    One pass's float32 logits [b, T, n_out] are alive at a time, and each is
    formed once a step: the weights ``p_t`` are computed first, from the
    states, and the cross-entropy's gradient is built in the forward sweep
    from the live logits (its own differentiation rule,
    ``nn.layers.output.weighted_xent``). Kept for the backward sweep: the
    states' gradient [R, b, T, n_in], the head's [n_in, n_out] and the
    per-token cross-entropies."""
    loss: str = "sparse_mcxent"
    activation: Optional[str] = "softmax"
    has_bias: bool = False
    entropy_weight: float = 0.05


@register
@dataclasses.dataclass
class LossLayer(FeedForwardLayer):
    """Loss without weights (reference ``nn/conf/layers/LossLayer.java``)."""
    loss: str = "mcxent"

    def get_output_type(self, index, input_type):
        return input_type

    def set_n_in(self, input_type, override=False):
        pass


@register
@dataclasses.dataclass
class CenterLossOutputLayer(OutputLayer):
    """Reference ``nn/conf/layers/CenterLossOutputLayer.java``: softmax loss +
    center loss with per-class feature centers updated by EMA."""
    alpha: float = 0.05
    lambda_: float = 2e-4
    gradient_check: bool = False


@register
@dataclasses.dataclass
class AutoEncoder(FeedForwardLayer):
    """Denoising autoencoder pretrain layer (reference ``nn/conf/layers/AutoEncoder.java``)."""
    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: str = "mse"

    def is_pretrain_layer(self):
        return True


@register
@dataclasses.dataclass
class RBM(FeedForwardLayer):
    """Restricted Boltzmann Machine (reference ``nn/conf/layers/RBM.java:62``
    config + ``nn/layers/feedforward/rbm/RBM.java:1`` CD-k impl — deprecated
    there in favor of the VAE, ported for §2.1 layer-inventory completeness).

    Supervised forward = ``propUp`` (hidden mean activation). Unsupervised
    pretraining = CD-k contrastive divergence behind the standard pretrain
    seam: ``pretrain_loss`` is the free-energy-difference surrogate
    ``mean(F(v0) - F(v_k))`` with the k-step Gibbs chain stop-gradiented,
    whose gradient IS the CD-k update ``⟨v0 h0⟩ - ⟨vk hk⟩`` (TPU-first: the
    whole chain jits; no hand-written update rule).

    ``hidden_unit``: binary | rectified | gaussian | identity;
    ``visible_unit``: binary | gaussian | linear | identity (reference
    enums; softmax units were never wired into the reference's gradient
    path and are rejected here rather than silently mis-trained)."""
    hidden_unit: str = "binary"
    visible_unit: str = "binary"
    k: int = 1
    sparsity: float = 0.0

    def is_pretrain_layer(self):
        return True


@register
@dataclasses.dataclass
class VariationalAutoencoder(FeedForwardLayer):
    """Reference ``nn/conf/layers/variational/VariationalAutoencoder.java`` /
    impl ``nn/layers/variational/VariationalAutoencoder.java`` (1163 LoC).

    ``n_out`` = latent size. Forward (supervised use) emits the mean of q(z|x).
    Pretraining maximizes the ELBO with ``num_samples`` MC samples.
    """
    encoder_layer_sizes: Tuple[int, ...] = (100,)
    decoder_layer_sizes: Tuple[int, ...] = (100,)
    pzx_activation: str = "identity"
    # a ReconstructionDistribution object (conf.reconstruction) or a legacy
    # string name: gaussian (learned variance) | bernoulli | exponential
    reconstruction_distribution: Any = "gaussian"
    num_samples: int = 1

    def is_pretrain_layer(self):
        return True


class PoolingDimension:
    pass


@register
@dataclasses.dataclass
class GlobalPoolingLayer(Layer):
    """Pool over spatial/time dims (reference ``nn/conf/layers/GlobalPoolingLayer.java``);
    mask-aware for RNN input."""
    pooling_type: str = PoolingType.MAX
    pooling_dimensions: Optional[Tuple[int, ...]] = None
    collapse_dimensions: bool = True
    pnorm: int = 2

    def get_output_type(self, index, input_type):
        if isinstance(input_type, InputTypeConvolutional):
            return InputTypeFeedForward(input_type.channels)
        if isinstance(input_type, InputTypeRecurrent):
            return InputTypeFeedForward(input_type.size)
        return input_type


@register
@dataclasses.dataclass
class Yolo2OutputLayer(Layer):
    """YOLOv2 detection loss (reference ``nn/conf/layers/objdetect/Yolo2OutputLayer.java``,
    impl ``nn/layers/objdetect/Yolo2OutputLayer.java`` 714 LoC).

    ``boxes``: [[h,w], ...] anchor box priors in grid units.
    Labels: [batch, 4 + C, gridH, gridW] as in the reference.
    """
    boxes: Optional[List[List[float]]] = None
    lambda_coord: float = 5.0
    lambda_no_obj: float = 0.5

    def get_output_type(self, index, input_type):
        return input_type


@register
@dataclasses.dataclass
class FrozenLayer(Layer):
    """Wrapper marking the inner layer non-trainable (reference
    ``nn/conf/layers/misc/FrozenLayer.java``); gradients are zeroed via
    ``jax.lax.stop_gradient`` on the inner params."""
    inner: Optional[Any] = None

    def get_output_type(self, index, input_type):
        return self.inner.get_output_type(index, input_type)

    def set_n_in(self, input_type, override=False):
        self.inner.set_n_in(input_type, override)

    def preprocessor_for(self, input_type):
        return self.inner.preprocessor_for(input_type)

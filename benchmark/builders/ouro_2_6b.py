"""Builder of the ``ouro_2_6b`` configuration (ByteDance Ouro, a looped
language model): ``EmbeddingSequenceLayer`` over ids [b, T] (a gather), one
``LoopedBlockStack`` of ``blocks`` sandwich-normed blocks (RMSNorm, rotary
causal attention with no bias, SwiGLU) applied ``passes`` times with the one
set of stacked weights, and ``LoopLMOutputLayer``: the untied head read after
every pass and the exit-gate-weighted next-token loss over integer labels
[b, T]. Adam 1e-3, the zoo's. Written out layer by layer because the zoo has
no such model; every size is an argument, and ``configs/ouro_2_6b.json``
holds the published ones."""
from __future__ import annotations


def build(seed, vocab, hidden, heads, head_dim, intermediate, blocks, passes,
          rope_theta, rms_norm_eps, entropy_weight):
    from deeplearning4j_tpu import Adam
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                                   LoopedBlockStack,
                                                   LoopLMOutputLayer)

    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-3)).activation("identity")
            .graph_builder().add_inputs("ids")
            .add_layer("embed", EmbeddingSequenceLayer(n_in=vocab,
                                                       n_out=hidden), "ids")
            .add_layer("stack", LoopedBlockStack(
                n_in=hidden, n_out=hidden, num_blocks=blocks,
                num_passes=passes, num_heads=heads, head_dim=head_dim,
                n_hidden=intermediate, eps=rms_norm_eps,
                rope_theta=rope_theta), "embed")
            .add_layer("out", LoopLMOutputLayer(
                n_in=hidden, n_out=vocab, entropy_weight=entropy_weight),
                "stack")
            .set_outputs("out").build())

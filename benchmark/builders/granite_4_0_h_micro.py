"""Builder of the ``granite_4_0_h_micro`` configuration (IBM Granite 4.0-H
Micro, a hybrid of Mamba-2 state-space layers and grouped-query attention
without positions): ``EmbeddingSequenceLayer`` over ids [b, T] (a gather,
times the embedding multiplier), one ``HybridBlockStack`` of the first
``layers`` entries of ``layer_types`` (every block a mixer and a gated MLP,
both scaled by the residual multiplier, pre-normed; a final RMSNorm), and an
``RnnOutputLayer`` whose head is the embedding's own leaf (tied), its logits
divided by the logits scaling, under the next-token cross-entropy over integer
labels [b, T]. Adam 1e-3, the zoo's. Written out layer by layer because the
zoo has no such model; every size is an argument, and
``configs/granite_4_0_h_micro.json`` holds the published ones."""
from __future__ import annotations


def build(seed, vocab, hidden, layers, layer_types, intermediate, heads,
          kv_heads, head_dim, attention_multiplier, embedding_multiplier,
          residual_multiplier, logits_scaling, rms_norm_eps, mamba_heads,
          mamba_head_dim, mamba_state, mamba_conv, mamba_chunk):
    from deeplearning4j_tpu import Adam
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                                   HybridBlockStack,
                                                   RnnOutputLayer)

    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-3)).activation("identity")
            .graph_builder().add_inputs("ids")
            .add_layer("embed", EmbeddingSequenceLayer(
                n_in=vocab, n_out=hidden, scale=embedding_multiplier), "ids")
            .add_layer("stack", HybridBlockStack(
                n_in=hidden, n_out=hidden,
                layer_types=list(layer_types[:layers]),
                n_hidden=intermediate, eps=rms_norm_eps,
                residual_multiplier=residual_multiplier, num_heads=heads,
                num_kv_heads=kv_heads, head_dim=head_dim,
                attention_scale=attention_multiplier,
                mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
                mamba_state_size=mamba_state, mamba_conv_size=mamba_conv,
                mamba_chunk_size=mamba_chunk), "embed")
            .add_layer("out", RnnOutputLayer(
                n_in=hidden, n_out=vocab, loss="sparse_mcxent",
                activation="softmax", has_bias=False, tied_to="embed",
                logits_divisor=logits_scaling), "stack")
            .set_outputs("out").build())

"""Builder of the ``kimi_linear_48b_a3b`` configuration (Moonshot AI's Kimi
Linear 48B-A3B: Kimi Delta Attention layers, a gated delta rule with one decay
a key channel, three to one of latent attention without positions; a dense
gated MLP in the first layer and routed gated experts with a shared expert in
every later one): ``EmbeddingSequenceLayer`` over ids [b, T] (a gather), one
``HybridBlockStack`` of the published layers 1 .. ``layers`` (a layer named in
``kda_layers`` is a ``kda`` block, any other an ``mla`` block; the first
``first_k_dense`` have the dense MLP, the rest the experts, of which this chip
holds ids 0 .. ``experts`` - 1 of the ``experts_published`` the router spans;
pre-normed, a final RMSNorm), and an untied ``RnnOutputLayer`` under the
next-token cross-entropy over integer labels [b, T]
(``logits_divisor`` 1: the head's logits are the gemm's float32 accumulator,
as the other language models' are). Adam at 1e-5, not the zoo's 1e-3: at
1e-3 from random weights, without a warm-up, Adam's first steps move every
entry of a router by 1e-3 and a token's scores by a whole unit at this
width, every token picks the same 8 experts from the third step on, and the
cell would measure that transient (``assumed.optimizer`` of the file below
has the readings). Every size is an argument;
``configs/kimi_linear_48b_a3b.json`` holds the published ones."""
from __future__ import annotations


def layer_kinds(layers, kda_layers, first_k_dense):
    """(mixer kinds, feed-forward kinds) of the published layers 1 ..
    ``layers``."""
    order = range(1, int(layers) + 1)
    return (["kda" if i in set(kda_layers) else "mla" for i in order],
            ["dense" if i <= first_k_dense else "experts" for i in order])


def build(seed, vocab, hidden, layers, kda_layers, first_k_dense,
          intermediate, heads, kda_heads, kda_head_dim, kda_conv, kda_chunk,
          kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
          experts, experts_published, experts_per_token, moe_intermediate,
          shared_experts, routed_scaling_factor, rms_norm_eps):
    from deeplearning4j_tpu import Adam
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                                   HybridBlockStack,
                                                   RnnOutputLayer)

    mixers, ffns = layer_kinds(layers, kda_layers, first_k_dense)
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-5)).activation("identity")
            .graph_builder().add_inputs("ids")
            .add_layer("embed", EmbeddingSequenceLayer(
                n_in=vocab, n_out=hidden), "ids")
            .add_layer("stack", HybridBlockStack(
                n_in=hidden, n_out=hidden, layer_types=mixers, ffn_types=ffns,
                n_hidden=intermediate, eps=rms_norm_eps, num_heads=heads,
                kda_heads=kda_heads, kda_head_dim=kda_head_dim,
                kda_conv_size=kda_conv, kda_chunk_size=kda_chunk,
                kv_latent_rank=kv_lora_rank,
                qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
                num_experts=experts_published,
                experts_held=list(range(experts)),
                experts_per_token=experts_per_token,
                expert_hidden=moe_intermediate,
                shared_hidden=shared_experts * moe_intermediate,
                renormalize=True,
                routed_scaling_factor=routed_scaling_factor), "embed")
            .add_layer("out", RnnOutputLayer(
                n_in=hidden, n_out=vocab, loss="sparse_mcxent",
                activation="softmax", has_bias=False, logits_divisor=1.0),
                "stack")
            .set_outputs("out").build())

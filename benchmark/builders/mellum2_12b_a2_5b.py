"""Builder of the ``mellum2_12b_a2_5b`` configuration (JetBrains' Mellum2
12B-A2.5B: grouped-query attention, three layers in a sliding window of 1024
keys with plain rotary positions to one full layer with YaRN's, and routed
gated experts in every layer, top 8 of 64 by a softmax renormalised over the
chosen, no shared expert): ``EmbeddingSequenceLayer`` over ids [b, T] (a
gather), one ``HybridBlockStack`` of the published layers 0 .. ``layers`` - 1
(a ``sliding_attention`` layer of ``layer_types`` is a ``window`` block, a
``full_attention`` one an ``attention`` block; every block's feed-forward the
routed experts, of which this chip holds ids 0 .. ``experts`` - 1 of the
``experts_published`` the router spans; pre-normed, a final RMSNorm), and an
untied ``RnnOutputLayer`` under the next-token cross-entropy over integer
labels [b, T] (``logits_divisor`` 1: the head's logits are the gemm's float32
accumulator, as the other language models' are). The embedding is drawn from
N(0, 1), ``nn.Embedding``'s own default, not by Xavier over 24,576 rows
(0.0086 a weight), under which the first attention's output, an average a
batch's tokens share, swamps every token's own row and leans the whole
batch's routing one way (``assumed.init`` of the file below has the
readings). Adam at 1e-5, for the
reason the Kimi Linear builder gives: at the zoo's 1e-3 from random weights a
router's every entry moves by 1e-3 a step and the tokens soon all choose the
same experts (``assumed.optimizer`` of the file below has this model's
readings). Every size is an argument; ``configs/mellum2_12b_a2_5b.json``
holds the published ones."""
from __future__ import annotations

#: the published ``layer_types`` -> the hybrid stack's mixer kinds
KINDS = {"sliding_attention": "window", "full_attention": "attention"}


def build(seed, vocab, hidden, layers, layer_types, heads, kv_heads, head_dim,
          window, rope_parameters, experts, experts_published,
          experts_per_token, moe_intermediate, norm_topk_prob, rms_norm_eps):
    from deeplearning4j_tpu import Adam
    from deeplearning4j_tpu.nn.conf import (NeuralNetConfiguration,
                                            NormalDistribution)
    from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                                   HybridBlockStack,
                                                   RnnOutputLayer)

    full = dict(rope_parameters["full_attention"])
    sliding = rope_parameters["sliding_attention"]
    full_theta = full.pop("rope_theta")
    if (sliding.get("rope_type", "default"), sliding["rope_theta"]) != (
            "default", full_theta):
        raise ValueError("the sliding layers' rotation is the plain one at "
                         "the full layers' base")
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-5)).activation("identity")
            .graph_builder().add_inputs("ids")
            .add_layer("embed", EmbeddingSequenceLayer(
                n_in=vocab, n_out=hidden, weight_init="distribution",
                dist=NormalDistribution(0.0, 1.0)), "ids")
            .add_layer("stack", HybridBlockStack(
                n_in=hidden, n_out=hidden,
                layer_types=[KINDS[t] for t in layer_types[:layers]],
                ffn_types=["experts"] * layers, eps=rms_norm_eps,
                num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
                rope_theta=full_theta,
                rope_scaling=None if full.get("rope_type") == "default"
                else full,
                window=window,
                num_experts=experts_published,
                experts_held=list(range(experts)),
                experts_per_token=experts_per_token,
                expert_hidden=moe_intermediate, shared_hidden=None,
                renormalize=norm_topk_prob, expert_score="softmax"), "embed")
            .add_layer("out", RnnOutputLayer(
                n_in=hidden, n_out=vocab, loss="sparse_mcxent",
                activation="softmax", has_bias=False, logits_divisor=1.0),
                "stack")
            .set_outputs("out").build())

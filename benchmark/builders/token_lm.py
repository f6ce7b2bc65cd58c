"""Builder of a decoder-only token language model from the layers the repo
has: the zoo's ``TransformerLM`` (``EmbeddingSequenceLayer`` over ids [b, T],
a gather and no one-hot; ``blocks`` pre-norm residual blocks of causal
``SelfAttentionLayer`` and a GELU FFN; a final norm; ``RnnOutputLayer``; Adam
1e-3) with the one thing changed that the zoo class fixes: its output layer's
``loss="mcxent"``, which wants one-hot labels, becomes ``sparse_mcxent`` over
integer labels [b, T]. It stands behind the rehearsal fixture of the
``token_ids`` / ``next_token_ids`` batch kind; no cell of ``BENCHMARK.json``
uses it."""
from __future__ import annotations


def build(seed, vocab, width, heads, blocks):
    from deeplearning4j_tpu.models import TransformerLM

    conf = TransformerLM(vocab_size=vocab, seed=seed, embed_dim=width,
                         num_heads=heads, num_blocks=blocks).conf()
    conf.vertices["out"].loss = "sparse_mcxent"
    return conf

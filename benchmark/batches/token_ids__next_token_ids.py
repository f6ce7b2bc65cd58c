"""Token ids with the next token's id as label: per batch one stream of ids
uniform in [0, ``features.vocab``) of shape [batch, ``seq_len`` + 1], features
its first ``seq_len`` columns and labels its last, both [batch, ``seq_len``].
``vocab`` is what the configuration holds here, so a sliced vocabulary draws
from the slice. No one-hot tensor is formed: at 8 x 4096 over 49,152 words
that would be 6.4 GB on each side, feeding an embedding by matmul.

int32, each side a contiguous array of its own. ``DataSet`` keeps a host
array as it is; ``fit`` and ``device_arrays()`` put it up with
``jnp.asarray``, which without x64 converts an int64 array to int32 on the
host on every put and copies a strided view; ``EmbeddingSequenceLayer`` and
``sparse_mcxent`` both start with ``astype(int32)``, which on int32 is no
op in the compiled step. Float ids, which both would also take, would cost a
convert pass per step and lose ids above 2**24.
"""
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet


def draw(rng, features, labels, n, batch, seq_len):
    vocab, width = int(features["vocab"]), int(seq_len) + 1
    out = []
    for _ in range(n):
        ids = rng.integers(0, vocab, size=(batch, width), dtype=np.int32)
        out.append(DataSet(np.ascontiguousarray(ids[:, :-1]),
                           np.ascontiguousarray(ids[:, 1:])))
    return out

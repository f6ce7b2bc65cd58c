"""One stream of symbols uniform over ``features.vocab``, [n, batch,
``seq_len`` + 1]: features its first ``seq_len`` columns and labels its last,
both one-hot float32 [batch, ``seq_len``, vocab], which is what a recurrent
layer without an embedding takes and ``mcxent`` compares."""
from deeplearning4j_tpu.datasets.dataset import DataSet

from benchmark.batches import one_hot


def draw(rng, features, labels, n, batch, seq_len):
    vocab = int(features["vocab"])
    ids = rng.integers(0, vocab, size=(n, batch, int(seq_len) + 1))
    f = one_hot(ids[:, :, :-1], vocab)
    l = one_hot(ids[:, :, 1:], vocab)
    return [DataSet(f[i], l[i]) for i in range(n)]

"""Standard-normal float32 features of ``features.shape`` (images, NCHW as a
user hands them over) with one class of ``labels.classes`` per example, drawn
uniformly and one-hot in float32."""
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet

from benchmark.batches import one_hot


def draw(rng, features, labels, n, batch, seq_len=None):
    shape = (batch,) + tuple(features["shape"])
    classes = int(labels["classes"])
    return [DataSet(rng.standard_normal(shape, dtype=np.float32),
                    one_hot(rng.integers(0, classes, batch), classes))
            for _ in range(n)]

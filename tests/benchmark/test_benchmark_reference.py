"""The plain references against the system at a tiny size on the CPU,
float32 on both sides: the same loss and the same gradients to 1e-4."""
import numpy as np
import jax
import jax.numpy as jnp

from benchmark import correct
from benchmark.builders.graves_lstm_charrnn import build
from benchmark.reference import graves_lstm_charrnn, resnet50_imagenet
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models import ResNet50
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def test_resnet50_reference_matches_the_zoo_model():
    class TwoStages(ResNet50):           # the same blocks, fewer and thinner
        STAGES = ((1, 4), (1, 8))

    net = ComputationGraph(TwoStages(
        num_classes=5, seed=3, input_shape=(3, 16, 16)).conf()).init()
    rng = np.random.default_rng(0)
    sample = DataSet(rng.standard_normal((4, 3, 16, 16), dtype=np.float32),
                     np.eye(5, dtype=np.float32)[rng.integers(0, 5, 4)])

    class Reference:
        TOLERANCE = resnet50_imagenet.TOLERANCE

        @staticmethod
        def loss(params, x, y):
            return resnet50_imagenet.loss(params, x, y, TwoStages.STAGES)

    ok, detail = correct.against_reference(net, Reference, sample, "float32")
    assert ok, detail
    assert resnet50_imagenet.TOLERANCE["float32"]["grads"] <= 1e-4


def test_graves_lstm_reference_matches_the_layers():
    net = MultiLayerNetwork(build(seed=5, vocab=12, width=16, layers=2,
                                  tbptt=5)).init()
    rng = np.random.default_rng(1)
    # peepholes start at 0: move everything so that they take part
    net.params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jnp.asarray(rng.standard_normal(x.shape), x.dtype),
        net.params)
    ids = rng.integers(0, 12, (3, 8))
    eye = np.eye(12, dtype=np.float32)
    ok, detail = correct.against_reference(
        net, graves_lstm_charrnn, DataSet(eye[ids[:, :-1]], eye[ids[:, 1:]]),
        "float32")
    assert ok, detail
    assert graves_lstm_charrnn.TOLERANCE["float32"]["grads"] <= 1e-4

"""MultiLayerNetwork end-to-end behavior tests (reference test analog:
``deeplearning4j-core/src/test/java/org/deeplearning4j/nn/multilayer/``)."""
import numpy as np
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                InputType, DataSet, ListDataSetIterator, Adam, Sgd,
                                WeightInit, BackpropType)
from deeplearning4j_tpu.nn.conf.layers import (DenseLayer, OutputLayer,
                                               ConvolutionLayer, SubsamplingLayer,
                                               BatchNormalization, LSTM,
                                               GravesLSTM, RnnOutputLayer,
                                               DropoutLayer, GlobalPoolingLayer,
                                               EmbeddingSequenceLayer, PoolingType)


def _toy_classification(n=256, d=10, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    w = rng.standard_normal((d, classes))
    y = np.argmax(x @ w + 0.1 * rng.standard_normal((n, classes)), axis=1)
    labels = np.eye(classes, dtype=np.float32)[y]
    return x, labels


class TestMLP:
    def test_fit_reduces_score_and_learns(self):
        x, labels = _toy_classification()
        conf = (NeuralNetConfiguration.builder()
                .seed(7).updater(Adam(learning_rate=1e-2))
                .list()
                .layer(DenseLayer(n_in=10, n_out=32, activation="relu"))
                .layer(OutputLayer(n_in=32, n_out=3, activation="softmax",
                                   loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        ds = DataSet(x, labels)
        initial = net.score(ds)
        net.fit(ListDataSetIterator([ds], batch_size=64), epochs=30)
        final = net.score(ds)
        assert final < initial * 0.5
        ev = net.evaluate(ListDataSetIterator([ds]))
        assert ev.accuracy() > 0.85

    def test_output_shape_and_softmax(self):
        x, labels = _toy_classification(n=8)
        conf = (NeuralNetConfiguration.builder().list()
                .layer(DenseLayer(n_in=10, n_out=16))
                .layer(OutputLayer(n_in=16, n_out=3, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        out = np.asarray(net.output(x))
        assert out.shape == (8, 3)
        assert np.allclose(out.sum(axis=1), 1.0, atol=1e-5)

    def test_params_flat_roundtrip(self):
        conf = (NeuralNetConfiguration.builder().seed(1).list()
                .layer(DenseLayer(n_in=4, n_out=5))
                .layer(OutputLayer(n_in=5, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        vec = net.params_flat()
        assert vec.size == net.num_params() == (4 * 5 + 5) + (5 * 2 + 2)
        x = np.random.default_rng(0).standard_normal((3, 4)).astype(np.float32)
        out1 = np.asarray(net.output(x))
        net2 = MultiLayerNetwork(conf).init()
        net2.set_params_flat(vec)
        out2 = np.asarray(net2.output(x))
        assert np.allclose(out1, out2, atol=1e-6)

    def test_l2_increases_score(self):
        x, labels = _toy_classification(n=32)
        base = (NeuralNetConfiguration.builder().seed(3).list()
                .layer(DenseLayer(n_in=10, n_out=8))
                .layer(OutputLayer(n_in=8, n_out=3, activation="softmax"))
                .build())
        reg = (NeuralNetConfiguration.builder().seed(3).l2(0.1).list()
               .layer(DenseLayer(n_in=10, n_out=8))
               .layer(OutputLayer(n_in=8, n_out=3, activation="softmax"))
               .build())
        n1 = MultiLayerNetwork(base).init()
        n2 = MultiLayerNetwork(reg).init()
        ds = DataSet(x, labels)
        assert n2.score(ds) > n1.score(ds)

    def test_frozen_global_config_defaults(self):
        conf = (NeuralNetConfiguration.builder()
                .activation("tanh").weight_init(WeightInit.ZERO).list()
                .layer(DenseLayer(n_in=3, n_out=4))
                .layer(OutputLayer(n_in=4, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        # zero weights + tanh -> dense output all zeros
        out = net.feed_forward(np.ones((2, 3), np.float32))
        assert np.allclose(out[1], 0.0)


class TestCNN:
    def test_lenet_mini_trains(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 1, 12, 12)).astype(np.float32)
        y = (x.mean(axis=(1, 2, 3)) > 0).astype(int)
        labels = np.eye(2, dtype=np.float32)[y]
        conf = (NeuralNetConfiguration.builder()
                .seed(11).updater(Adam(learning_rate=3e-3))
                .list()
                .layer(ConvolutionLayer(kernel_size=(3, 3), n_out=8,
                                        activation="relu"))
                .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(BatchNormalization())
                .layer(DenseLayer(n_out=16, activation="relu"))
                .layer(OutputLayer(n_out=2, activation="softmax"))
                .set_input_type(InputType.convolutional(12, 12, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        ds = DataSet(x, labels)
        s0 = net.score(ds)
        net.fit(ListDataSetIterator([ds], batch_size=32), epochs=20)
        assert net.score(ds) < s0
        ev = net.evaluate(ListDataSetIterator([ds]))
        assert ev.accuracy() > 0.8

    def test_bn_state_updates_in_training(self):
        x = np.random.default_rng(0).standard_normal((16, 1, 6, 6)).astype(np.float32) * 3 + 1
        labels = np.eye(2, dtype=np.float32)[np.zeros(16, int)]
        conf = (NeuralNetConfiguration.builder().list()
                .layer(ConvolutionLayer(kernel_size=(3, 3), n_out=4))
                .layer(BatchNormalization())
                .layer(OutputLayer(n_out=2, activation="softmax"))
                .set_input_type(InputType.convolutional(6, 6, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        mean_before = np.asarray(net.states["1"]["mean"]).copy()
        net.fit(DataSet(x, labels))
        mean_after = np.asarray(net.states["1"]["mean"])
        assert not np.allclose(mean_before, mean_after)


class TestRNN:
    def _seq_data(self, n=64, t=12, d=4, seed=0):
        # predict sign of running mean of feature 0, per timestep
        rng = np.random.default_rng(seed)
        x = rng.standard_normal((n, t, d)).astype(np.float32)
        cum = np.cumsum(x[:, :, 0], axis=1) / np.arange(1, t + 1)
        y = (cum > 0).astype(int)
        labels = np.eye(2, dtype=np.float32)[y]
        return x, labels

    def test_lstm_trains(self):
        x, labels = self._seq_data()
        conf = (NeuralNetConfiguration.builder()
                .seed(5).updater(Adam(learning_rate=1e-2))
                .list()
                .layer(LSTM(n_in=4, n_out=16, activation="tanh"))
                .layer(RnnOutputLayer(n_in=16, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        ds = DataSet(x, labels)
        s0 = net.score(ds)
        net.fit(ListDataSetIterator([ds], batch_size=32), epochs=25)
        assert net.score(ds) < s0 * 0.9
        out = np.asarray(net.output(x))
        assert out.shape == (64, 12, 2)

    def test_graves_lstm_has_peepholes(self):
        conf = (NeuralNetConfiguration.builder().list()
                .layer(GravesLSTM(n_in=3, n_out=5))
                .layer(RnnOutputLayer(n_in=5, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        assert "pi" in net.params["0"]
        assert net.params["0"]["W"].shape == (3, 20)

    def test_masking_changes_loss(self):
        x, labels = self._seq_data(n=8)
        mask = np.ones((8, 12), np.float32)
        mask[:, 6:] = 0
        conf = (NeuralNetConfiguration.builder().seed(5).list()
                .layer(LSTM(n_in=4, n_out=8))
                .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        full = net.score(DataSet(x, labels))
        masked = net.score(DataSet(x, labels, features_mask=mask, labels_mask=mask))
        assert masked < full  # half the timesteps contribute

    def test_rnn_time_step_matches_full_forward(self):
        x, _ = self._seq_data(n=4, t=6)
        conf = (NeuralNetConfiguration.builder().seed(5).list()
                .layer(LSTM(n_in=4, n_out=8))
                .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        full = np.asarray(net.output(x))
        net.rnn_clear_previous_state()
        steps = []
        for t in range(6):
            steps.append(np.asarray(net.rnn_time_step(x[:, t, :])))
        stepwise = np.stack(steps, axis=1)
        assert np.allclose(full, stepwise, atol=1e-4)

    def test_tbptt_runs(self):
        x, labels = self._seq_data(n=16, t=20)
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Adam(learning_rate=1e-2)).list()
                .layer(LSTM(n_in=4, n_out=8))
                .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax"))
                .backprop_type(BackpropType.TruncatedBPTT)
                .t_bptt_forward_length(5)
                .t_bptt_backward_length(5)
                .build())
        net = MultiLayerNetwork(conf).init()
        ds = DataSet(x, labels)
        s0 = net.score(ds)
        net.fit(ListDataSetIterator([ds]), epochs=10)
        assert net.score(ds) < s0

    def test_global_pooling_classifier(self):
        x, labels_seq = self._seq_data(n=32, t=10)
        labels = labels_seq[:, -1, :]  # sequence-level label
        conf = (NeuralNetConfiguration.builder().seed(2)
                .updater(Adam(learning_rate=1e-2)).list()
                .layer(LSTM(n_in=4, n_out=8))
                .layer(GlobalPoolingLayer(pooling_type=PoolingType.AVG))
                .layer(OutputLayer(n_in=8, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        out = np.asarray(net.output(x))
        assert out.shape == (32, 2)
        net.fit(DataSet(x, labels))


class TestEmbedding:
    def test_embedding_sequence(self):
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 20, size=(16, 8))
        labels = np.eye(2, dtype=np.float32)[(tokens.sum(axis=1) % 2)]
        conf = (NeuralNetConfiguration.builder().updater(Adam(learning_rate=1e-2))
                .list()
                .layer(EmbeddingSequenceLayer(n_in=20, n_out=6))
                .layer(GlobalPoolingLayer(pooling_type=PoolingType.AVG))
                .layer(OutputLayer(n_in=6, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        out = np.asarray(net.output(tokens))
        assert out.shape == (16, 2)
        net.fit(DataSet(tokens, labels))


class TestDropout:
    def test_dropout_only_in_training(self):
        x = np.ones((4, 10), np.float32)
        conf = (NeuralNetConfiguration.builder().seed(0).list()
                .layer(DropoutLayer(dropout=0.5))
                .layer(OutputLayer(n_in=10, n_out=2, activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        # inference: dropout inactive -> deterministic
        o1 = np.asarray(net.output(x))
        o2 = np.asarray(net.output(x))
        assert np.allclose(o1, o2)

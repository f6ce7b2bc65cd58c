"""Tests for activations, losses, weight init, updaters, schedules, config serde.

Mirrors the reference's unit-test strategy for these components (SURVEY.md §4.2).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.activations import get_activation, Activation
from deeplearning4j_tpu.nn.losses import get_loss, LossFunction
from deeplearning4j_tpu.nn.weights import (init_weight, WeightInit,
                                           NormalDistribution, UniformDistribution)
from deeplearning4j_tpu.nn.updaters import (Sgd, Adam, Nesterovs, RmsProp, AdaGrad,
                                            AdaDelta, Nadam, AdaMax, NoOp,
                                            StepSchedule, ExponentialSchedule,
                                            MapSchedule)
from deeplearning4j_tpu.nn.conf import (NeuralNetConfiguration,
                                        MultiLayerConfiguration)
from deeplearning4j_tpu.nn.conf.layers import (DenseLayer, OutputLayer,
                                               ConvolutionLayer, SubsamplingLayer,
                                               BatchNormalization, LSTM)
from deeplearning4j_tpu.nn.conf.inputs import InputType


class TestActivations:
    def test_known_values(self):
        x = jnp.array([-1.0, 0.0, 2.0])
        assert np.allclose(get_activation("relu")(x), [0, 0, 2])
        assert np.allclose(get_activation("identity")(x), [-1, 0, 2])
        assert np.allclose(get_activation("tanh")(x), np.tanh([-1, 0, 2]), atol=1e-6)
        assert np.allclose(get_activation("hardtanh")(x), [-1, 0, 1])

    def test_softmax_normalizes(self):
        x = jnp.array([[1.0, 2.0, 3.0]])
        y = get_activation("softmax")(x)
        assert np.allclose(np.sum(y), 1.0, atol=1e-6)

    def test_all_registered_run(self):
        x = jnp.linspace(-2, 2, 8).reshape(2, 4)
        for name in Activation.names():
            y = get_activation(name)(x)
            assert y.shape == x.shape
            assert np.all(np.isfinite(np.asarray(y)))

    def test_unknown_raises(self):
        with pytest.raises(ValueError):
            get_activation("nope")


class TestLosses:
    def test_mse(self):
        labels = jnp.array([[1.0, 0.0]])
        preout = jnp.array([[0.5, 0.5]])
        v = get_loss("mse")(labels, preout, "identity", None)
        assert np.allclose(v, 0.5)  # (0.25 + 0.25)

    def test_mcxent_softmax_fused_matches_unfused(self):
        labels = jnp.array([[0.0, 1.0, 0.0], [1.0, 0.0, 0.0]])
        preout = jnp.array([[0.1, 2.0, -1.0], [3.0, 0.0, 0.2]])
        fused = get_loss("mcxent")(labels, preout, "softmax", None)
        probs = jax.nn.softmax(preout, axis=-1)
        manual = -np.mean(np.sum(np.asarray(labels) * np.log(np.asarray(probs)), axis=1) * -1 * -1)
        assert np.allclose(fused, manual, atol=1e-5)

    def test_xent_sigmoid_stable_at_extremes(self):
        labels = jnp.array([[1.0], [0.0]])
        preout = jnp.array([[100.0], [-100.0]])
        v = get_loss("xent")(labels, preout, "sigmoid", None)
        assert np.isfinite(float(v))
        assert float(v) < 1e-6

    def test_mask_zeroes_contribution(self):
        labels = jnp.ones((2, 3, 4)) / 4
        preout = jnp.zeros((2, 3, 4))
        mask = jnp.array([[1, 1, 0], [1, 0, 0]], jnp.float32)
        full = get_loss("mcxent")(labels, preout, "softmax", None)
        masked = get_loss("mcxent")(labels, preout, "softmax", mask)
        # uniform per-step loss: 3 of 6 steps active -> masked is half of full
        # (denominator stays the minibatch size, reference semantics)
        assert np.allclose(float(masked), float(full) * 0.5, atol=1e-5)

    def test_all_losses_finite(self):
        labels = jnp.abs(jax.random.uniform(jax.random.PRNGKey(0), (4, 3))) + 0.1
        labels = labels / labels.sum(-1, keepdims=True)
        preout = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
        for name in LossFunction.names():
            if name == "sparse_mcxent":
                v = get_loss(name)(jnp.array([0, 1, 2, 0]), preout, "softmax", None)
            else:
                act = "sigmoid" if name in ("xent", "reconstruction_crossentropy") else "softmax"
                v = get_loss(name)(labels, preout, act, None)
            assert np.isfinite(float(v)), name


class TestWeightInit:
    def test_xavier_scale(self):
        rng = jax.random.PRNGKey(0)
        w = init_weight(rng, (1000, 500), 1000, 500, WeightInit.XAVIER)
        expected_std = np.sqrt(2.0 / 1500)
        assert abs(float(jnp.std(w)) - expected_std) < 0.1 * expected_std

    @pytest.mark.parametrize("chunk", [7, 1 << 17])
    def test_normal_sampled_in_chunks_is_the_one_call_stream(self, chunk,
                                                             monkeypatch):
        """Leaves are sampled a piece at a time (``weights._CHUNK``):
        the values are those of one ``standard_normal`` call over the whole
        shape, scaled and cast as before, to the bit."""
        from deeplearning4j_tpu.nn import weights
        monkeypatch.setattr(weights, "_CHUNK", chunk)
        rng = jax.random.PRNGKey(3)
        got = weights._normal(rng, (5, 4, 3), jnp.float32, scale=0.3,
                              shift=0.1)
        whole = (weights._np_rng(rng).standard_normal(size=(5, 4, 3)) * 0.3
                 + 0.1).astype(np.float32)
        np.testing.assert_array_equal(got, whole)
        assert got.dtype == jnp.float32 and got.shape == (5, 4, 3)

    def test_zero_ones_identity(self):
        rng = jax.random.PRNGKey(0)
        assert np.all(np.asarray(init_weight(rng, (3, 3), 3, 3, WeightInit.ZERO)) == 0)
        assert np.all(np.asarray(init_weight(rng, (3, 3), 3, 3, WeightInit.ONES)) == 1)
        assert np.allclose(init_weight(rng, (3, 3), 3, 3, WeightInit.IDENTITY), np.eye(3))

    def test_distribution(self):
        rng = jax.random.PRNGKey(0)
        w = init_weight(rng, (2000,), 1, 1, WeightInit.DISTRIBUTION,
                        NormalDistribution(mean=5.0, std=0.1))
        assert abs(float(jnp.mean(w)) - 5.0) < 0.02
        w = init_weight(rng, (2000,), 1, 1, WeightInit.DISTRIBUTION,
                        UniformDistribution(lower=2.0, upper=3.0))
        assert float(jnp.min(w)) >= 2.0 and float(jnp.max(w)) <= 3.0


class TestUpdaters:
    def _params(self):
        return {"W": jnp.ones((3, 2)), "b": jnp.zeros((2,))}

    def _grads(self):
        return {"W": jnp.full((3, 2), 0.5), "b": jnp.full((2,), 0.1)}

    def test_sgd(self):
        u = Sgd(learning_rate=0.1)
        s = u.init_state(self._params())
        upd, _ = u.apply(s, self._grads(), 0)
        assert np.allclose(upd["W"], 0.05)

    def test_noop(self):
        u = NoOp()
        s = u.init_state(self._params())
        upd, _ = u.apply(s, self._grads(), 0)
        assert np.all(np.asarray(upd["W"]) == 0)

    @pytest.mark.parametrize("cls", [Adam, Nesterovs, RmsProp, AdaGrad, AdaDelta,
                                     Nadam, AdaMax])
    def test_stateful_updaters_reduce_loss(self, cls):
        # quadratic bowl: f(w) = 0.5*||w||^2, grad = w
        u = cls()
        w = {"W": jnp.full((4,), 10.0)}
        s = u.init_state(w)
        for t in range(200):
            g = w
            upd, s = u.apply(s, g, t)
            w = jax.tree_util.tree_map(lambda p, du: p - du, w, upd)
        final = float(jnp.sum(w["W"] ** 2))
        assert np.isfinite(final)
        assert final < 4 * 10.0 ** 2  # strictly decreased toward 0

    def test_adam_bias_correction_first_step(self):
        u = Adam(learning_rate=0.001)
        w = {"W": jnp.ones((2,))}
        s = u.init_state(w)
        upd, _ = u.apply(s, {"W": jnp.full((2,), 0.3)}, 0)
        # first Adam step magnitude ≈ lr regardless of grad scale
        assert np.allclose(np.asarray(upd["W"]), 0.001, atol=1e-4)

    def test_schedules(self):
        s = StepSchedule(initial_value=1.0, decay_rate=0.5, step_size=10)
        assert float(s.value(0)) == 1.0
        assert abs(float(s.value(10)) - 0.5) < 1e-6
        e = ExponentialSchedule(initial_value=1.0, gamma=0.9)
        assert abs(float(e.value(2)) - 0.81) < 1e-6
        m = MapSchedule(values={0: 1.0, 5: 0.1})
        assert float(m.value(3)) == 1.0
        assert abs(float(m.value(7)) - 0.1) < 1e-6

    def test_lr_schedule_in_updater(self):
        u = Sgd(learning_rate=1.0,
                lr_schedule=StepSchedule(initial_value=1.0, decay_rate=0.1,
                                         step_size=5))
        s = u.init_state({"W": jnp.ones(2)})
        upd0, _ = u.apply(s, {"W": jnp.ones(2)}, 0)
        upd5, _ = u.apply(s, {"W": jnp.ones(2)}, 5)
        assert np.allclose(upd0["W"], 1.0)
        assert np.allclose(upd5["W"], 0.1)


class TestConfigDSL:
    def _build(self):
        return (NeuralNetConfiguration.builder()
                .seed(42)
                .updater(Adam(learning_rate=1e-3))
                .weight_init(WeightInit.XAVIER)
                .l2(1e-4)
                .list()
                .layer(ConvolutionLayer(kernel_size=(5, 5), n_out=20,
                                        activation="relu"))
                .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(BatchNormalization())
                .layer(DenseLayer(n_out=50, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
                .set_input_type(InputType.convolutional(28, 28, 1))
                .build())

    def test_shape_inference(self):
        conf = self._build()
        assert conf.layers[0].n_in == 1          # channels
        assert conf.layers[2].n_in == 20         # BN on conv output channels
        assert conf.layers[3].n_in == 12 * 12 * 20  # (28-5+1)/... = 24 pooled 12
        assert conf.layers[4].n_in == 50
        # preprocessor auto-inserted between conv stack and dense
        assert conf.preprocessor(3) is not None

    def test_json_roundtrip(self):
        conf = self._build()
        js = conf.to_json()
        conf2 = MultiLayerConfiguration.from_json(js)
        assert conf2.layers[3].n_in == conf.layers[3].n_in
        assert type(conf2.layers[0]).__name__ == "ConvolutionLayer"
        assert conf2.global_conf.seed == 42
        assert type(conf2.global_conf.updater).__name__ == "Adam"
        assert conf2.global_conf.updater.learning_rate == 1e-3
        # second roundtrip is stable
        assert conf2.to_json() == js

    def test_lstm_shape_inference(self):
        conf = (NeuralNetConfiguration.builder().list()
                .layer(LSTM(n_out=8))
                .layer(OutputLayer(n_out=3, activation="softmax"))
                .set_input_type(InputType.recurrent(5))
                .build())
        assert conf.layers[0].n_in == 5
        assert conf.layers[1].n_in == 8

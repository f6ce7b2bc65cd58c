"""Streaming ingestion/serving tests (reference ``dl4j-streaming``:
``NDArrayPublisherTests``, ``Dl4jServingRouteTest`` — embedded-broker
pattern)."""
import threading
import time

import numpy as np

from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                DataSet, Sgd)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.datasets.streaming import (NDArrayMessage,
                                                   StreamingBroker,
                                                   NDArrayPublisher,
                                                   NDArrayConsumer,
                                                   StreamingDataSetIterator,
                                                   ServingRoute)


def test_ndarray_message_roundtrip():
    rng = np.random.default_rng(0)
    arrays = [rng.normal(size=(3, 4)).astype(np.float32),
              rng.integers(0, 100, size=(5,)).astype(np.int64),
              (rng.random((2, 2, 2)) > 0.5),
              np.asarray(np.float32(3.5)),              # rank-0 scalar
              rng.integers(0, 9, size=(4,)).astype(np.int16)]
    back = NDArrayMessage.decode(NDArrayMessage.encode(arrays))
    assert len(back) == len(arrays)
    for a, b in zip(arrays, back):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_ndarray_message_rejects_unsupported_dtype():
    import pytest
    with pytest.raises(ValueError, match="unsupported dtype"):
        NDArrayMessage.encode([np.zeros(2, np.complex64)])


def test_publish_subscribe_roundtrip():
    """NDArrayPublisherTests pattern: publish arrays through the broker,
    consumer receives them bit-identical and in order."""
    broker = StreamingBroker()
    try:
        consumer = NDArrayConsumer(broker.address, "features", timeout=10.0)
        time.sleep(0.05)  # let SUB register before publishing
        pub = NDArrayPublisher(broker.address, "features")
        sent = [np.full((2, 3), i, np.float32) for i in range(4)]
        for a in sent:
            pub.publish(a)
        for i, a in enumerate(sent):
            got = consumer.receive()
            np.testing.assert_array_equal(got[0], a)
        pub.close()
        consumer.close()
    finally:
        broker.close()


def test_training_from_stream():
    """net.fit drives straight off a streamed (features, labels) topic —
    the streaming-ingestion seam the reference feeds from Kafka."""
    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(Sgd(learning_rate=0.1)).activation("tanh")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(1)
    f_all = rng.normal(size=(64, 4)).astype(np.float32)
    l_all = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)]
    s0 = net.score(DataSet(f_all, l_all))

    broker = StreamingBroker()
    try:
        consumer = NDArrayConsumer(broker.address, "train", timeout=10.0)
        time.sleep(0.05)

        def produce():
            pub = NDArrayPublisher(broker.address, "train")
            for _ in range(3):  # 3 epochs over 4 batches
                for s in range(0, 64, 16):
                    pub.publish([f_all[s:s + 16], l_all[s:s + 16]])
            pub.close()

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        it = StreamingDataSetIterator(consumer, num_batches=12)
        net.fit(it)
        t.join()
        consumer.close()
    finally:
        broker.close()
    s1 = net.score(DataSet(f_all, l_all))
    assert s1 < s0, f"streamed training did not converge: {s0} -> {s1}"
    assert net.iteration_count == 12


def test_serving_route_publishes_predictions():
    """Dl4jServingRouteTest pattern: features in on one topic, model
    predictions out on another."""
    conf = (NeuralNetConfiguration.builder().seed(4)
            .updater(Sgd(learning_rate=0.1))
            .list()
            .layer(OutputLayer(n_in=4, n_out=2, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    broker = StreamingBroker()
    try:
        feat_consumer = NDArrayConsumer(broker.address, "in", timeout=10.0)
        pred_consumer = NDArrayConsumer(broker.address, "out", timeout=10.0)
        time.sleep(0.05)
        route = ServingRoute(net, feat_consumer,
                             NDArrayPublisher(broker.address, "out"))
        route.start(max_messages=2)
        pub = NDArrayPublisher(broker.address, "in")
        x = np.random.default_rng(5).normal(size=(3, 4)).astype(np.float32)
        pub.publish(x)
        pub.publish(x * 2)
        got1 = pred_consumer.receive()
        got2 = pred_consumer.receive()
        want = np.asarray(net.output(x))
        np.testing.assert_allclose(got1[0], want, rtol=1e-5)
        assert got2[0].shape == (3, 2)
        np.testing.assert_allclose(got1[0].sum(axis=1), 1.0, rtol=1e-5)
    finally:
        broker.close()


def test_unbounded_stream_ends_on_publisher_close():
    """num_batches=None: the stream ends CLEANLY when the publisher closes
    (EOS control frame) — no timeout, no exception."""
    broker = StreamingBroker()
    try:
        consumer = NDArrayConsumer(broker.address, "u", timeout=10.0)
        time.sleep(0.05)
        pub = NDArrayPublisher(broker.address, "u")
        for i in range(3):
            pub.publish([np.full((4, 2), i, np.float32),
                         np.eye(2, dtype=np.float32)[[0, 1, 0, 1]]])
        pub.close()  # sends EOS
        it = StreamingDataSetIterator(consumer)  # unbounded
        seen = [ds for ds in it]
        assert len(seen) == 3
        consumer.close()
    finally:
        broker.close()


def test_consumer_timeout_raises_not_silent():
    import pytest
    broker = StreamingBroker()
    try:
        consumer = NDArrayConsumer(broker.address, "quiet", timeout=0.3)
        time.sleep(0.05)
        with pytest.raises(TimeoutError, match="stalled"):
            consumer.receive()
    finally:
        broker.close()


def _broker_has(broker, what, topic, n, timeout=10.0):
    """Wait until the broker has registered ``n`` subscribers (``_subs``) or
    publishers (``_pubs``) of ``topic``: a hello frame is handled on a
    thread of the broker's, some time after the client's constructor
    returns."""
    deadline = time.monotonic() + timeout
    while True:
        with broker._lock:
            seen = getattr(broker, what).get(topic, 0)
        if (seen if isinstance(seen, int) else len(seen)) == n:
            return
        assert time.monotonic() < deadline, f"{what}[{topic!r}] is {seen!r}"
        time.sleep(0.002)


def test_eos_waits_for_last_publisher():
    """EOS must not end the topic while another publisher still feeds it."""
    broker = StreamingBroker()
    try:
        consumer = NDArrayConsumer(broker.address, "multi", timeout=10.0)
        _broker_has(broker, "_subs", "multi", 1)
        p1 = NDArrayPublisher(broker.address, "multi")
        p2 = NDArrayPublisher(broker.address, "multi")
        _broker_has(broker, "_pubs", "multi", 2)
        p1.publish(np.ones((1,), np.float32))
        p1.close()                      # EOS from p1 — p2 still open
        # each publisher has a thread of its own in the broker: p2 speaks
        # only once p1's EOS has been handled, so the order is the test's
        _broker_has(broker, "_pubs", "multi", 1)
        p2.publish(np.full((1,), 2, np.float32))
        p2.close()                      # LAST publisher → EOS forwarded
        got = []
        while True:
            parts = consumer.receive()
            if parts is None:
                break
            got.append(float(parts[0][0]))
        assert got == [1.0, 2.0]
        consumer.close()
    finally:
        broker.close()


def test_serving_route_survives_idle_and_captures_errors():
    """Idle timeouts keep the route alive; a malformed request is captured on
    route.error instead of dying silently."""
    conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd())
            .list()
            .layer(OutputLayer(n_in=4, n_out=2, activation="softmax",
                               loss="mcxent")).build())
    net = MultiLayerNetwork(conf).init()
    broker = StreamingBroker()
    try:
        fc = NDArrayConsumer(broker.address, "rin", timeout=0.2)  # short idle
        pc = NDArrayConsumer(broker.address, "rout", timeout=10.0)
        time.sleep(0.05)
        from deeplearning4j_tpu.datasets.streaming import ServingRoute
        route = ServingRoute(net, fc, NDArrayPublisher(broker.address, "rout"))
        t = route.start()
        time.sleep(0.5)                 # several idle timeouts elapse
        assert t.is_alive()             # still serving
        pub = NDArrayPublisher(broker.address, "rin")
        x = np.zeros((2, 4), np.float32)
        pub.publish(x)
        assert pc.receive()[0].shape == (2, 2)
        pub.publish(np.zeros((2, 9), np.float32))  # wrong feature width
        t.join(timeout=10)
        assert route.error is not None
        import pytest
        with pytest.raises(Exception):
            route.check()
    finally:
        broker.close()

"""Dataset layer tests: IDX parsing, fetchers, record readers, normalizer use
(reference test families in ``deeplearning4j-core/src/test/.../datasets/``)."""
import os

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.fetchers import (read_idx, write_idx,
                                                  MnistDataFetcher,
                                                  CifarDataFetcher,
                                                  IrisDataFetcher)
from deeplearning4j_tpu.datasets.impl import (MnistDataSetIterator,
                                              IrisDataSetIterator,
                                              CifarDataSetIterator)
from deeplearning4j_tpu.datasets.records import (CSVRecordReader,
                                                 CollectionRecordReader,
                                                 CSVSequenceRecordReader,
                                                 RecordReaderDataSetIterator,
                                                 SequenceRecordReaderDataSetIterator)


def test_idx_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    arr = rng.integers(0, 255, size=(10, 28, 28)).astype(np.uint8)
    p = str(tmp_path / "imgs-idx3-ubyte")
    write_idx(p, arr)
    np.testing.assert_array_equal(read_idx(p), arr)
    pg = str(tmp_path / "imgs-idx3-ubyte.gz")
    write_idx(pg, arr)
    np.testing.assert_array_equal(read_idx(pg), arr)


def test_mnist_fetcher_reads_real_idx_files(tmp_path, monkeypatch):
    # lay out genuine IDX files → fetcher must read them, not synthesize
    monkeypatch.setenv("DL4J_TPU_DATA_DIR", str(tmp_path))
    d = tmp_path / "mnist"
    os.makedirs(d)
    rng = np.random.default_rng(1)
    imgs = rng.integers(0, 255, size=(50, 28, 28)).astype(np.uint8)
    labels = rng.integers(0, 10, size=(50,)).astype(np.uint8)
    write_idx(str(d / "train-images-idx3-ubyte"), imgs)
    write_idx(str(d / "train-labels-idx1-ubyte"), labels)
    f = MnistDataFetcher(train=True)
    assert not f.is_synthetic
    assert f.features.shape == (50, 784)
    np.testing.assert_allclose(f.features[0],
                               imgs[0].reshape(-1).astype(np.float32) / 255.0)
    assert np.argmax(f.labels[3]) == labels[3]


def test_mnist_synthetic_fallback_and_iterator():
    it = MnistDataSetIterator(batch=32, num_examples=128)
    assert it.fetcher.is_synthetic
    batches = list(it)
    assert len(batches) == 4
    assert batches[0].features.shape == (32, 784)
    assert batches[0].labels.shape == (32, 10)
    # deterministic across instantiations
    it2 = MnistDataSetIterator(batch=32, num_examples=128)
    np.testing.assert_array_equal(batches[0].features,
                                  next(iter(it2)).features)


def test_iris_iterator_trains():
    from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                    Adam)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    it = IrisDataSetIterator(batch=50)
    assert sum(ds.num_examples() for ds in it) == 150
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Adam(learning_rate=0.05)).activation("tanh")
            .list()
            .layer(DenseLayer(n_in=4, n_out=16))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.fit(it, epochs=30)
    ev = net.evaluate(IrisDataSetIterator(batch=150))
    assert ev.accuracy() > 0.8  # iris is nearly separable


def test_cifar_iterator_shapes():
    it = CifarDataSetIterator(batch=16, num_examples=64)
    ds = next(iter(it))
    assert ds.features.shape == (16, 3, 32, 32)
    assert ds.labels.shape == (16, 10)


def test_cifar_reads_binary_files(tmp_path, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_DATA_DIR", str(tmp_path))
    d = tmp_path / "cifar10"
    os.makedirs(d)
    rng = np.random.default_rng(2)
    for i in range(1, 6):
        rec = np.zeros((20, 3073), np.uint8)
        rec[:, 0] = rng.integers(0, 10, 20)
        rec[:, 1:] = rng.integers(0, 255, (20, 3072))
        with open(d / f"data_batch_{i}.bin", "wb") as fh:
            fh.write(rec.tobytes())
    f = CifarDataFetcher(train=True)
    assert not f.is_synthetic
    assert f.features.shape == (100, 3, 32, 32)


def test_csv_record_reader_classification(tmp_path):
    p = tmp_path / "data.csv"
    p.write_text("a,b,label\n1.0,2.0,0\n3.0,4.0,1\n5.0,6.0,2\n7.0,8.0,1\n")
    reader = CSVRecordReader(str(p), skip_lines=1)
    it = RecordReaderDataSetIterator(reader, batch_size=2, label_index=2,
                                     num_classes=3)
    batches = list(it)
    assert len(batches) == 2
    np.testing.assert_allclose(batches[0].features, [[1, 2], [3, 4]])
    np.testing.assert_allclose(batches[0].labels, [[1, 0, 0], [0, 1, 0]])


def test_record_reader_regression():
    reader = CollectionRecordReader([[1.0, 2.0, 10.0], [3.0, 4.0, 20.0]])
    it = RecordReaderDataSetIterator(reader, batch_size=2, label_index=2,
                                     regression=True)
    ds = next(iter(it))
    np.testing.assert_allclose(ds.labels, [[10.0], [20.0]])


def test_sequence_record_reader_padding(tmp_path):
    p1 = tmp_path / "s1.csv"
    p1.write_text("1.0,2.0,0\n3.0,4.0,1\n5.0,6.0,0\n")
    p2 = tmp_path / "s2.csv"
    p2.write_text("7.0,8.0,1\n")
    reader = CSVSequenceRecordReader([str(p1), str(p2)])
    it = SequenceRecordReaderDataSetIterator(reader, batch_size=2,
                                             num_classes=2, label_index=2)
    ds = next(iter(it))
    assert ds.features.shape == (2, 3, 2)
    assert ds.labels.shape == (2, 3, 2)
    np.testing.assert_allclose(ds.features_mask, [[1, 1, 1], [1, 0, 0]])
    np.testing.assert_allclose(ds.features[1, 0], [7.0, 8.0])


def test_lfw_tinyimagenet_fetchers_and_synthetic_flag(tmp_path, caplog):
    """LFW/TinyImageNet fetchers (VERDICT r2 item 10): local-or-synthetic
    pattern, NCHW shapes, and the loud synthetic marker on DataSets."""
    import logging
    from deeplearning4j_tpu.datasets.impl import (LFWDataSetIterator,
                                                  TinyImageNetDataSetIterator)
    with caplog.at_level(logging.WARNING,
                         logger="deeplearning4j_tpu.datasets.fetchers"):
        it = LFWDataSetIterator(batch=8, num_examples=16, image_size=32,
                                num_synthetic=16)
    assert any("SYNTHETIC" in r.message for r in caplog.records)
    ds = next(it)
    assert ds.synthetic is True
    assert ds.features.shape == (8, 3, 32, 32)
    assert ds.labels.shape[1] == it.fetcher.num_classes

    tin = TinyImageNetDataSetIterator(batch=4, num_examples=8, num_synthetic=8)
    ds2 = next(tin)
    assert ds2.synthetic is True
    assert ds2.features.shape == (4, 3, 64, 64)
    assert ds2.labels.shape == (4, 200)


def test_image_folder_fetcher_reads_local_files(tmp_path, monkeypatch):
    """With real class folders on disk, the fetchers read images (not
    synthetic) and the DataSet flag stays False."""
    from PIL import Image
    import numpy as np
    base = tmp_path / "lfw"
    for person in ("alice", "bob"):
        d = base / person
        d.mkdir(parents=True)
        for i in range(3):
            arr = (np.random.default_rng(i).random((40, 40, 3)) * 255
                   ).astype("uint8")
            Image.fromarray(arr).save(d / f"img_{i}.jpg")
    monkeypatch.setenv("DL4J_TPU_DATA_DIR", str(tmp_path))
    from deeplearning4j_tpu.datasets.impl import LFWDataSetIterator
    it = LFWDataSetIterator(batch=6, image_size=24)
    ds = next(it)
    assert ds.synthetic is False
    assert ds.features.shape == (6, 3, 24, 24)
    assert ds.labels.shape == (6, 2)
    assert it.fetcher.class_names == ["alice", "bob"]


def test_mnist_synthetic_flag_propagates():
    from deeplearning4j_tpu.datasets.impl import MnistDataSetIterator
    it = MnistDataSetIterator(batch=32, num_examples=64)
    ds = next(it)
    # zero-egress environment: no local MNIST → synthetic and flagged
    assert ds.synthetic == it.fetcher.is_synthetic


def test_cache_mode_device_same_results_and_cached_transfer():
    """CacheMode.DEVICE (reference ``nn/conf/CacheMode.java``): repeated fits
    of one DataSet reuse the HBM-resident copy (one transfer), and training
    results are identical to CacheMode.NONE."""
    import numpy as np
    from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                    DataSet, Sgd)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer

    def build(cache):
        b = (NeuralNetConfiguration.builder().seed(7)
             .updater(Sgd(learning_rate=0.1)).activation("tanh"))
        if cache:
            b = b.cache_mode("device")
        conf = (b.list()
                .layer(DenseLayer(n_in=4, n_out=8))
                .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    f = rng.normal(size=(16, 4)).astype(np.float32)
    l = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    ds = DataSet(f, l)
    net_a, net_b = build(True), build(False)
    for _ in range(5):
        net_a.fit(ds)
        net_b.fit(ds)
    for a, b in zip(__import__("jax").tree_util.tree_leaves(net_a.params),
                    __import__("jax").tree_util.tree_leaves(net_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # the device copy is cached — same tuple across calls
    assert ds.device_arrays() is ds.device_arrays()


def test_cache_mode_device_invalidated_by_normalizer_reassign():
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet

    ds = DataSet(np.ones((4, 3), np.float32),
                 np.eye(2, dtype=np.float32)[[0, 1, 0, 1]])
    first = ds.device_arrays()
    ds.features = ds.features * 2.0  # normalizers reassign, as transform does
    second = ds.device_arrays()
    assert first is not second
    np.testing.assert_allclose(np.asarray(second[0]), 2.0)


def test_cache_mode_device_computation_graph_caches_on_dataset():
    """CG fit(DataSet) must hit the cache stored on the caller's DataSet —
    the per-batch MultiDataSet wrapper is a fresh object each call."""
    import numpy as np
    from deeplearning4j_tpu import NeuralNetConfiguration, DataSet, Sgd
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    g = (NeuralNetConfiguration.builder().seed(3)
         .updater(Sgd(learning_rate=0.1)).activation("tanh")
         .cache_mode("device")
         .graph_builder().add_inputs("in"))
    g.add_layer("d", DenseLayer(n_in=4, n_out=8), "in")
    g.add_layer("out", OutputLayer(n_in=8, n_out=3, activation="softmax",
                                   loss="mcxent"), "d")
    g.set_outputs("out")
    net = ComputationGraph(g.build()).init()
    rng = np.random.default_rng(1)
    ds = DataSet(rng.normal(size=(8, 4)).astype(np.float32),
                 np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)])
    net.fit(ds)
    first = ds.device_arrays()
    net.fit(ds)
    assert ds.device_arrays() is first  # cached across fits, on the DataSet


# ---------------------------------------------------------------------------
# PR 6: high-throughput input pipeline — multi-worker prefetch,
# device-put-ahead, shape bucketing (datasets/prefetch.py, bucketing.py)
# ---------------------------------------------------------------------------
import threading
import time

import pytest

from deeplearning4j_tpu.datasets.dataset import (DataSetIterator,
                                                 ListDataSetIterator)
from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator
from deeplearning4j_tpu.datasets.prefetch import (PrefetchDataSetIterator,
                                                  wrap_for_training)
from deeplearning4j_tpu.datasets.bucketing import ShapeBucketingDataSetIterator
from deeplearning4j_tpu.datasets.dataset import DataSet


def _numbered(n, feat=3):
    """n single-example DataSets whose feature value IS the index."""
    return [DataSet(np.full((1, feat), i, np.float32),
                    np.eye(2, dtype=np.float32)[[i % 2]]) for i in range(n)]


def _dense_net(seed=7):
    from deeplearning4j_tpu import (NeuralNetConfiguration,
                                    MultiLayerNetwork, Sgd)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=0.1)).activation("tanh").list()
            .layer(DenseLayer(n_in=3, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=2, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


class TestPrefetch:
    def test_order_preserved_under_n_workers_across_epochs(self):
        pf = PrefetchDataSetIterator(ListDataSetIterator(_numbered(50)),
                                     workers=4)
        try:
            for _ in range(2):          # __iter__ resets: fresh epoch
                got = [float(ds.features[0, 0]) for ds in pf]
                assert got == [float(i) for i in range(50)]
        finally:
            pf.shutdown()

    def test_worker_exception_propagates_in_order(self):
        class Boom(ListDataSetIterator):
            def __next__(self):
                if self._pos == 5:
                    raise ValueError("boom")
                return super().__next__()

        pf = PrefetchDataSetIterator(Boom(_numbered(20)), workers=3)
        seen = []
        try:
            with pytest.raises(ValueError, match="boom"):
                for ds in pf:
                    seen.append(float(ds.features[0, 0]))
            # every batch BEFORE the failure was delivered, in order
            assert seen == [0.0, 1.0, 2.0, 3.0, 4.0]
        finally:
            pf.shutdown()

    def test_dead_workers_raise_instead_of_hanging(self):
        """All workers dying WITHOUT an end-of-stream marker (hard thread
        death) must surface as an error on the consumer, never a hang."""
        pf = PrefetchDataSetIterator(ListDataSetIterator(_numbered(4)),
                                     workers=2)
        pf._worker_loop = lambda ep: None       # dies instantly, marks nothing
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="workers died"):
            next(iter(pf))
        assert time.perf_counter() - t0 < 10
        pf.shutdown()

    def test_reset_mid_epoch_no_leaked_threads(self):
        base_threads = threading.active_count()
        pf = PrefetchDataSetIterator(ListDataSetIterator(_numbered(30)),
                                     workers=4)
        it = iter(pf)
        for _ in range(3):
            next(it)
        pf.reset()                          # mid-epoch: old workers joined
        got = [float(ds.features[0, 0]) for ds in pf]
        assert got == [float(i) for i in range(30)]  # clean fresh epoch
        pf.shutdown()
        deadline = time.time() + 5
        while (threading.active_count() > base_threads
               and time.time() < deadline):
            time.sleep(0.01)
        assert threading.active_count() <= base_threads

    def test_device_put_ahead_delivers_device_arrays_and_identical_fit(self):
        import os
        import jax
        rng = np.random.default_rng(0)
        batches = [DataSet(rng.normal(size=(8, 3)).astype(np.float32),
                           np.eye(2, dtype=np.float32)[
                               rng.integers(0, 2, 8)]) for _ in range(5)]
        pf = PrefetchDataSetIterator(ListDataSetIterator(list(batches)),
                                     workers=2, device_put=True)
        try:
            ds = next(iter(pf))
            assert isinstance(ds.features, jax.Array)   # put ahead of the step
            assert isinstance(ds.labels, jax.Array)
            # the caller's DataSet was NOT mutated (view, not in-place put)
            assert isinstance(batches[0].features, np.ndarray)
        finally:
            pf.shutdown()
        # prefetch+put-ahead is a transport change, not a math change:
        # training through it is bit-identical to the synchronous path
        old = os.environ.get("DL4J_TPU_PREFETCH_WORKERS")
        try:
            os.environ["DL4J_TPU_PREFETCH_WORKERS"] = "0"
            a = _dense_net()
            a.fit(ListDataSetIterator(list(batches)), epochs=2)
            os.environ["DL4J_TPU_PREFETCH_WORKERS"] = "3"
            b = _dense_net()
            b.fit(ListDataSetIterator(list(batches)), epochs=2)
        finally:
            if old is None:
                os.environ.pop("DL4J_TPU_PREFETCH_WORKERS", None)
            else:
                os.environ["DL4J_TPU_PREFETCH_WORKERS"] = old
        for x, y in zip(jax.tree_util.tree_leaves(a.params),
                        jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_concurrent_pull_overlaps_slow_source(self):
        """A pull-thread-safe source (declared via
        concurrent_pull_supported) is pulled by N workers at once — the
        only way a slow __next__ (decode/fetch) parallelizes."""
        class SlowSafe(ListDataSetIterator):
            def __init__(self, dsets):
                super().__init__(dsets)
                self._lock = threading.Lock()

            def __next__(self):
                with self._lock:
                    if self._pos >= len(self._data):
                        raise StopIteration
                    d = self._data[self._pos]
                    self._pos += 1
                time.sleep(0.01)
                return d

            def concurrent_pull_supported(self):
                return True

        pf = PrefetchDataSetIterator(SlowSafe(_numbered(30)), workers=6)
        try:
            t0 = time.perf_counter()
            got = [float(ds.features[0, 0]) for ds in pf]
            dt = time.perf_counter() - t0
        finally:
            pf.shutdown()
        assert sorted(got) == [float(i) for i in range(30)]   # none lost
        assert dt < 0.30 * 0.7      # serial floor is 30 × 10 ms = 300 ms

    def test_wrap_for_training_dials(self, monkeypatch):
        base = ListDataSetIterator(_numbered(4))
        monkeypatch.setenv("DL4J_TPU_PREFETCH_WORKERS", "0")
        it, owned = wrap_for_training(base)
        assert it is base and not owned            # 0 = fully synchronous
        monkeypatch.setenv("DL4J_TPU_PREFETCH_WORKERS", "3")
        it, owned = wrap_for_training(base)
        assert isinstance(it, PrefetchDataSetIterator) and owned
        assert it._workers == 3 and it._device_put
        it.shutdown()
        # never double-wrap an async iterator
        it2, owned2 = wrap_for_training(AsyncDataSetIterator(base))
        assert not owned2
        monkeypatch.setenv("DL4J_TPU_PUT_AHEAD", "0")
        it3, owned3 = wrap_for_training(base)
        assert owned3 and not it3._device_put
        it3.shutdown()

    def test_pipeline_metrics_populated(self):
        from deeplearning4j_tpu.monitor import get_registry, profile_report
        reg = get_registry()
        before = reg.counter("input_batches_total").value
        pf = PrefetchDataSetIterator(ListDataSetIterator(_numbered(6)),
                                     workers=2, device_put=True)
        try:
            list(pf)
        finally:
            pf.shutdown()
        assert reg.counter("input_batches_total").value == before + 6
        assert reg.counter("input_bytes_total").value > 0
        _, _, n = reg.histogram("input_wait_seconds").state()
        assert n >= 6
        pipe = profile_report()["pipeline"]
        assert pipe["batches"] >= 6
        assert pipe["wait_seconds"] is not None


class TestAsyncIteratorLiveness:
    """Satellite: AsyncDataSetIterator.next must never block forever on a
    dead worker (bounded-timeout get + liveness check)."""

    def test_dead_worker_raises_promptly(self, monkeypatch):
        # the deliberate thread death below would otherwise print through
        # threading.excepthook and trip pytest's unhandled-thread warning
        monkeypatch.setattr(threading, "excepthook", lambda args: None)
        it = AsyncDataSetIterator(ListDataSetIterator(_numbered(4)))

        def dying_worker(q, stop):        # hard death: no _exc, no _STOP
            raise SystemExit

        it._worker = dying_worker         # instance attr shadows the method
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="died without"):
            next(iter(it))
        assert time.perf_counter() - t0 < 10

    def test_worker_exception_reraised_on_consumer(self):
        class Boom(ListDataSetIterator):
            def __next__(self):
                if self._pos == 2:
                    raise OSError("decode failed")
                return super().__next__()

        it = AsyncDataSetIterator(Boom(_numbered(6)))
        seen = []
        with pytest.raises(OSError, match="decode failed"):
            for ds in it:
                seen.append(float(ds.features[0, 0]))
        assert seen == [0.0, 1.0]


class TestShapeBucketing:
    def test_batch_padding_shapes_and_masks(self):
        rng = np.random.default_rng(0)
        ds = DataSet(rng.normal(size=(5, 3)).astype(np.float32),
                     np.eye(2, dtype=np.float32)[rng.integers(0, 2, 5)])
        out = ShapeBucketingDataSetIterator(
            ListDataSetIterator([ds]), batch_buckets=(8, 16)).pad(ds)
        assert out.features.shape == (8, 3)
        assert out.labels.shape == (8, 2)
        np.testing.assert_array_equal(out.features[5:], 0.0)
        # padding never trains: zero mask on pad rows, real rows rescaled
        # by padded/real so the loss matches the unpadded batch
        np.testing.assert_allclose(out.labels_mask[:5], 8 / 5)
        np.testing.assert_array_equal(out.labels_mask[5:], 0.0)

    def test_time_and_batch_padding_for_sequences(self):
        rng = np.random.default_rng(1)
        ds = DataSet(rng.normal(size=(3, 5, 4)).astype(np.float32),
                     rng.normal(size=(3, 5, 2)).astype(np.float32),
                     features_mask=np.ones((3, 5), np.float32))
        it = ShapeBucketingDataSetIterator(ListDataSetIterator([ds]),
                                           batch_buckets=(4,),
                                           time_buckets=(4, 8))
        out = it.pad(ds)
        assert out.features.shape == (4, 8, 4)
        assert out.labels.shape == (4, 8, 2)
        assert out.features_mask.shape == (4, 8)
        np.testing.assert_array_equal(out.features_mask[:, 5:], 0.0)
        np.testing.assert_array_equal(out.features_mask[3:], 0.0)
        np.testing.assert_allclose(out.labels_mask[:3, :5], 4 / 3)

    def test_oversize_batch_rejected_loudly(self):
        ds = DataSet(np.zeros((32, 3), np.float32),
                     np.eye(2, dtype=np.float32)[[0] * 32])
        it = ShapeBucketingDataSetIterator(ListDataSetIterator([ds]),
                                           batch_buckets=(8, 16))
        with pytest.raises(ValueError, match="exceeds the largest"):
            it.pad(ds)

    def test_padded_fit_numerically_matches_unpadded(self):
        import jax
        rng = np.random.default_rng(2)
        ds = DataSet(rng.normal(size=(5, 3)).astype(np.float32),
                     np.eye(2, dtype=np.float32)[rng.integers(0, 2, 5)])
        a, b = _dense_net(), _dense_net()
        a.fit(ds)
        b.fit(ShapeBucketingDataSetIterator(ListDataSetIterator([ds]),
                                            batch_buckets=(8,)))
        for x, y in zip(jax.tree_util.tree_leaves(a.params),
                        jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-6)


class TestBucketingClosesJitSignatures:
    """Acceptance: a shape-churning stream through the bucketing iterator
    records exactly ``len(buckets)`` jit compiles (no retrace storm),
    while the unbucketed control feed trips the storm detector."""

    @pytest.fixture(autouse=True)
    def _clean_monitor_state(self):
        from deeplearning4j_tpu.monitor import (get_health,
                                                get_flight_recorder,
                                                get_jit_registry)
        get_health().reset()
        get_flight_recorder().clear()
        get_jit_registry().drain_storms()
        yield
        get_health().reset()
        get_flight_recorder().clear()
        get_jit_registry().drain_storms()

    @staticmethod
    def _churny_batches(sizes, seed=0):
        rng = np.random.default_rng(seed)
        return [DataSet(rng.normal(size=(b, 3)).astype(np.float32),
                        np.eye(2, dtype=np.float32)[rng.integers(0, 2, b)])
                for b in sizes]

    def test_bucketed_stream_compiles_exactly_len_buckets(self):
        from deeplearning4j_tpu.monitor import get_flight_recorder
        buckets = (8, 16)
        net = _dense_net()
        it = ShapeBucketingDataSetIterator(
            ListDataSetIterator(self._churny_batches((5, 6, 7, 9, 11, 13))),
            batch_buckets=buckets)
        net.fit(it)
        assert net._jit_step.compiles == len(buckets)
        storms = [e for e in get_flight_recorder().events()
                  if e["event"] == "retrace_storm"]
        assert not storms

    def test_unbucketed_control_trips_retrace_storm(self):
        from deeplearning4j_tpu.monitor import get_flight_recorder
        net = _dense_net()
        net.fit(ListDataSetIterator(self._churny_batches((5, 6, 7, 9))))
        assert net._jit_step.compiles == 4
        storms = [e for e in get_flight_recorder().events()
                  if e["event"] == "retrace_storm" and e["fn"] == "mln/step"]
        assert storms, "shape churn did not trip the retrace-storm detector"

    def test_concurrent_pull_never_loses_the_tail_batch(self):
        """Regression (review finding): when workers' next() calls
        complete out of claim order at stream end, the racing last item
        must still be delivered — exhaustion is only final once every
        in-flight pull has resolved. Stressed over many tiny epochs (the
        original bug lost a batch in ~1/600 epochs)."""
        class SafeIter(ListDataSetIterator):
            def __init__(self, dsets):
                super().__init__(dsets)
                self._lock = threading.Lock()

            def __next__(self):
                with self._lock:
                    if self._pos >= len(self._data):
                        raise StopIteration
                    d = self._data[self._pos]
                    self._pos += 1
                return d

            def concurrent_pull_supported(self):
                return True

        pf = PrefetchDataSetIterator(SafeIter(_numbered(7)), workers=4)
        try:
            for _ in range(300):
                got = sorted(float(ds.features[0, 0]) for ds in pf)
                assert got == [float(i) for i in range(7)], got
        finally:
            pf.shutdown()

    def test_dead_worker_with_queued_items_drains_before_raising(self):
        """TOCTOU regression (review finding): a worker that enqueued its
        final batch + stop token and exited must read as a normal end of
        stream, not a crash — the consumer drains the queue before
        declaring the dead worker an error."""
        import queue as _queue
        it = AsyncDataSetIterator(ListDataSetIterator(_numbered(1)))
        dead = threading.Thread(target=lambda: None)
        dead.start()
        dead.join()
        it._queue = _queue.Queue()
        it._queue.put(_numbered(1)[0])
        it._queue.put(it._STOP)
        it._thread = dead
        it._exc = None
        assert float(next(it).features[0, 0]) == 0.0
        with pytest.raises(StopIteration):
            next(it)

    def test_rank2_per_timestep_sparse_labels_pad_and_train(self):
        """Regression (review finding): [b, T] integer per-timestep labels
        (the keras sparse_categorical_crossentropy import shape) must pad
        their time dim with the features' and get a [b, T] mask — not a
        [b] mask that crashes broadcasting in the loss."""
        import jax
        from deeplearning4j_tpu import (NeuralNetConfiguration,
                                        MultiLayerNetwork, Sgd)
        from deeplearning4j_tpu.nn.conf.layers import LSTM, RnnOutputLayer
        rng = np.random.default_rng(3)
        ds = DataSet(rng.normal(size=(3, 5, 4)).astype(np.float32),
                     rng.integers(0, 2, size=(3, 5)))
        it = ShapeBucketingDataSetIterator(ListDataSetIterator([ds]),
                                           batch_buckets=(4,),
                                           time_buckets=(8,))
        out = it.pad(ds)
        assert out.features.shape == (4, 8, 4)
        assert out.labels.shape == (4, 8)          # time dim padded too
        assert out.labels_mask.shape == (4, 8)     # per-timestep mask
        np.testing.assert_array_equal(out.labels_mask[:, 5:], 0.0)
        np.testing.assert_array_equal(out.labels_mask[3:], 0.0)
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Sgd(learning_rate=0.05)).activation("tanh").list()
                .layer(LSTM(n_in=4, n_out=8))
                .layer(RnnOutputLayer(n_in=8, n_out=2, activation="softmax",
                                      loss="sparse_mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fit(it)                                # must not crash the step
        assert np.isfinite(float(net.score_))

    def test_pipeline_wait_stats_honest_quantiles_on_seconds_geometry(self):
        """ISSUE 10 supersedes the PR-6 exact-only workaround: with
        ``input_wait_seconds`` on the ``unit="s"`` bucket geometry the
        /profile wait block reports HONEST p50/p95 — 99 sub-100µs pops
        plus one 150 ms stall must yield a sub-millisecond median, not
        the one stall the old ms-geometry buckets degenerated to."""
        from deeplearning4j_tpu.monitor.jitwatch import _pipeline_block
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        # a registry of its own: the process's holds every wait an earlier
        # fit of this file observed, and one over 150 ms would be the max
        reg = MetricsRegistry()
        h = reg.histogram("input_wait_seconds", unit="s")
        for _ in range(99):
            h.observe(50e-6)
        h.observe(0.15)                            # one transient stall
        w = _pipeline_block(reg.snapshot())["wait_seconds"]
        assert w["max_s"] == pytest.approx(0.15)
        assert w["mean_s"] < 0.01
        assert w["p50_s"] < 1e-3                   # honest: the median is
        assert w["p95_s"] < 1e-3                   # the fast path, not the
        assert "p95_ms" not in w                   # worst stall; keys are
                                                   # unit-suffixed _s

"""CPU rehearsals of the harness at tiny shapes: the iterator that is the
traffic generator, one whole run of each kind of cell through ``run_cell``,
the kinds of batch found by name under ``batches/``, and the command's
refusal to run without a TPU."""
import hashlib
import json
import os
import subprocess
import sys
import time
import tracemalloc

import numpy as np
import pytest

from benchmark import batches, cells, correct, run
from benchmark.feed import Feed
from deeplearning4j_tpu.datasets.dataset import DataSet

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, "fixtures")
MANIFEST = cells.load_manifest(FIXTURES)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(autouse=True)
def _own_registry(monkeypatch):
    """The metrics registry is process-global and other tests read its
    input-pipeline histograms: a rehearsal's thousands of steps go to one of
    its own."""
    import deeplearning4j_tpu.monitor.registry as registry
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())


class _Loss:
    """Stands in for a device array: records that it was waited for."""

    def __init__(self, n, waited):
        self.n, self.waited = n, waited

    def block_until_ready(self):
        self.waited.append(self.n)
        return self


def _pool(n=3):
    return [DataSet(np.full((2, 1), i, np.float32), np.zeros((2, 1), np.float32))
            for i in range(n)]


def test_feed_cycles_the_pool_and_stops_at_the_count():
    feed = Feed(_pool(), lambda: None, run_ahead=2)
    feed.arm(batches=7)
    got = [int(ds.features[0, 0]) for ds in feed]
    assert got == [0, 1, 2, 0, 1, 2, 0] and feed.handed == 7
    feed.arm(batches=2)                  # a stream: the next window goes on
    assert [int(ds.features[0, 0]) for ds in feed] == [1, 2]


def test_feed_waits_for_the_lagged_loss_never_the_newest():
    waited, step = [], [0]

    def newest():
        step[0] += 1
        return _Loss(step[0], waited)

    feed = Feed(_pool(), newest, run_ahead=3)
    feed.arm(batches=6)
    list(feed)
    # batch n waits for the loss seen when batch n-3 was handed out
    assert waited == [1, 2, 3]


def test_feed_deadline_ends_on_a_whole_group():
    feed = Feed(_pool(), lambda: None, run_ahead=2, group=4)
    feed.arm(seconds=0.05)
    n = 0
    for _ in feed:
        n += 1
        time.sleep(0.004)
    assert n >= 4 and n % 4 == 0


@pytest.mark.parametrize("workload,trace", [
    ("charrnn_resident", 1), ("lenet_hostfed", 0), ("lenet_pw4", 1),
    ("token_lm_resident", 0), ("token_lm_resident", 1)])
def test_run_cell_rehearsal_prints_the_contracts_line(workload, trace, tmp_path):
    """One whole run on the CPU (four of its virtual devices for the
    data-parallel cell): the deadline iterator's count is the net's, nothing
    compiles in the window, the line holds the contract's keys and no other."""
    notes = []
    result = run.run_cell(MANIFEST, FIXTURES, workload, seed=4, seconds=0.5,
                          trace=bool(trace), rehearse=True, note=notes.append,
                          trace_root=str(tmp_path))
    result = json.loads(json.dumps(result))          # it is one JSON object
    assert set(result) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert result["correct"] is True, notes
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"     # stamped, not a chip's
    cell = cells.load_cell(MANIFEST, FIXTURES, workload)
    if trace:
        assert set(result["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        allowed = {m["name"] for m in cell.metrics["per_layer"]}
        assert "window_compiles" in result["metrics"]
        assert result["metrics"]["window_compiles"]["value"] == 0
    else:
        assert set(result["device"]) == DEVICE_KEYS
        allowed = {m["name"] for m in cell.metrics["end_to_end"]}
        assert set(result["metrics"]) == allowed     # never 0, all there
        assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["metrics"]) <= allowed
    assert all(set(m) == {"value", "unit"} for m in result["metrics"].values())
    assert any("iteration_count moved by" in n and "check iterations: ok" in n
               for n in notes)
    if workload == "lenet_pw4":
        assert result["device"]["count"] >= 4
        assert "scaling_efficiency" in result["metrics"]
        assert any("check trajectory: ok" in n for n in notes)
        assert any("check all_reduce: ok" in n for n in notes)


def _digest(pool):
    h = hashlib.sha256()
    for ds in pool:
        for a in (ds.features, ds.labels):
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
    return h.hexdigest()


#: taken on the parent commit a327426, where ``cells.make_batches`` drew both
#: kinds itself: the repo's two configurations under their ``rehearse``
#: blocks, 3 batches of 8 (sequences of 20), dtype, shape and bytes of every
#: features and labels array in order
PARENT_DIGESTS = {
    ("resnet50_b256_resident", 31):
        "eec6f7e8732fe4c6a0eeba41219c1a92f5a986f786ca453a6a624540cd98a77e",
    ("resnet50_b256_resident", 32):
        "8af104b02597b801224b672c1db62f73e5e49f129e6924e98e36150a72a7008c",
    ("charrnn_b64_t5000_tbptt50_pool20", 31):
        "6299123219070e243a3753201a2997d199b40be1dc0d00d9a66c91621a260bdd",
    ("charrnn_b64_t5000_tbptt50_pool20", 32):
        "1835ff2ce4332b70204280fbf3831d1463f89e4658283081ff65bb031cbd73ba",
}


@pytest.mark.parametrize("workload,seed", sorted(PARENT_DIGESTS))
def test_the_old_kinds_draw_the_parents_bytes(workload, seed):
    """Moving a kind into ``batches/`` changed no byte of what its cells
    train on: same generator, same calls in the same order."""
    cell = cells.load_cell(cells.load_manifest(ROOT), ROOT, workload,
                           rehearse=True)
    pool = cells.make_batches(cell.config, seed, 3, 8,
                              20 if cell.seq_len else None)
    assert _digest(pool) == PARENT_DIGESTS[workload, seed]


TOKENS = {"features": {"kind": "token_ids", "vocab": 48},
          "labels": {"kind": "next_token_ids"}}


def test_token_ids_are_a_shifted_stream_of_int32_and_never_one_hot():
    pool = cells.make_batches(TOKENS, 31, 4, 8, 24)
    again = cells.make_batches(TOKENS, 31, 4, 8, 24)
    other = cells.make_batches(TOKENS, 32, 4, 8, 24)
    assert len(pool) == 4
    for ds, same in zip(pool, again):
        for a in (ds.features, ds.labels):
            assert a.shape == (8, 24) and a.dtype == np.int32
            assert a.flags["C_CONTIGUOUS"] and a.min() >= 0 and a.max() < 48
        assert np.array_equal(ds.labels[:, :-1], ds.features[:, 1:])
        assert np.array_equal(ds.features, same.features)
        assert np.array_equal(ds.labels, same.labels)
    assert not np.array_equal(pool[0].features, pool[1].features)
    assert not np.array_equal(pool[0].features, other[0].features)
    assert {int(v) for ds in pool for v in ds.features.ravel()} == set(range(48))
    # at Ouro's sizes the largest array made is the int32 stream [8, 4097]
    # and all that lives at once is it and its two sides: one one-hot side
    # alone would be 8 x 4096 x 49152 x 4 bytes = 6.4 GB
    big = {"features": {"kind": "token_ids", "vocab": 49152},
           "labels": {"kind": "next_token_ids"}}
    tracemalloc.start()
    try:
        ds, = cells.make_batches(big, 31, 1, 8, 4096)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert ds.features.shape == ds.labels.shape == (8, 4096)
    assert int(ds.labels.max()) > 48 and int(ds.labels.max()) < 49152
    assert peak < 3.5 * 8 * 4097 * 4, peak


def test_a_batch_kind_is_one_new_file(tmp_path, monkeypatch):
    """The door is open: a module dropped beside the others is drawn by its
    name, with the seeded generator, and ``cells.py`` is not touched."""
    (tmp_path / "ramp__parity.py").write_text(
        "import numpy as np\n"
        "from deeplearning4j_tpu.datasets.dataset import DataSet\n"
        "def draw(rng, features, labels, n, batch, seq_len=None):\n"
        "    start = rng.integers(0, 1000, n)\n"
        "    x = [np.arange(s, s + batch, dtype=np.float32)[:, None]"
        " * features['step'] for s in start]\n"
        "    return [DataSet(v, (v.astype(np.int32) % 2)) for v in x]\n")
    monkeypatch.setattr(batches, "__path__",
                        list(batches.__path__) + [str(tmp_path)])
    config = {"features": {"kind": "ramp", "step": 2.0},
              "labels": {"kind": "parity"}}
    try:
        pool = cells.make_batches(config, 7, 2, 4)
        again = cells.make_batches(config, 7, 2, 4)
    finally:
        sys.modules.pop("benchmark.batches.ramp__parity", None)
    assert [ds.features.shape for ds in pool] == [(4, 1), (4, 1)]
    assert np.array_equal(np.diff(pool[0].features[:, 0]), [2.0, 2.0, 2.0])
    assert not pool[0].labels.any()
    assert np.array_equal(pool[1].features, again[1].features)


def test_an_unknown_batch_kind_exits_with_the_kinds_found():
    config = {"features": {"kind": "token_ids", "vocab": 48},
              "labels": {"kind": "one_hot", "classes": 48}}
    with pytest.raises(SystemExit) as err:
        cells.make_batches(config, 1, 1, 8, 24)
    message = str(err.value)
    assert "'token_ids'" in message and "'one_hot'" in message
    for found in ("normal__one_hot", "one_hot_sequence__next_in_sequence",
                  "token_ids__next_token_ids"):
        assert found in message


class _PlainTokenLM:
    """The fixture's token LM from its equations in float32 ``jax.numpy``: a
    row of the embedding per id, pre-norm residual blocks of causal softmax
    attention and a GELU FFN, cross-entropy of the integer label summed over
    the positions and averaged over the batch. Knows the parameters' names."""
    TOLERANCE = {"float32": {"loss": 1e-4, "grads": 1e-4}}
    HEADS = 2

    @staticmethod
    def _norm(p, x):
        import jax.numpy as jnp
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-5) * p["gain"] + p["bias"]

    @classmethod
    def _attention(cls, p, x):
        import jax
        import jax.numpy as jnp
        b, t, e = x.shape
        q, k, v = (jnp.reshape(x @ p[w], (b, t, cls.HEADS, e // cls.HEADS))
                   for w in ("Wq", "Wk", "Wv"))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (e // cls.HEADS) ** 0.5
        logits = jnp.where(jnp.tril(jnp.ones((t, t), bool)), logits, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
        return jnp.reshape(o, (b, t, e)) @ p["Wo"] + p["b"]

    @classmethod
    def loss(cls, params, ids, labels):
        import jax
        import jax.numpy as jnp
        assert ids.dtype == labels.dtype == jnp.int32     # they came intact
        x = params["embed"]["W"][ids]
        for i in range(sum(k.endswith("-attn") for k in params)):
            x = x + cls._attention(params[f"b{i}-attn"],
                                   cls._norm(params[f"b{i}-ln-a"], x))
            h = cls._norm(params[f"b{i}-ln-f"], x) @ params[f"b{i}-ffn"]["W"] \
                + params[f"b{i}-ffn"]["b"]
            h = 0.5 * h * (1 + jnp.tanh((2 / jnp.pi) ** 0.5
                                        * (h + 0.044715 * h ** 3)))
            x = x + h @ params[f"b{i}-proj"]["W"] + params[f"b{i}-proj"]["b"]
        x = cls._norm(params["ln-final"], x)
        logp = jax.nn.log_softmax(x @ params["out"]["W"] + params["out"]["b"])
        picked = jnp.take_along_axis(logp, labels[..., None], -1)
        return -jnp.sum(picked) / ids.shape[0]


def test_integer_ids_and_labels_reach_a_plain_reference_intact():
    """``correct.against_reference`` hands a token LM's features and labels
    to ``loss(params, features, labels)`` as they were drawn, and the system
    (gather, attention, ``sparse_mcxent``) agrees with the plain model."""
    cell = cells.load_cell(MANIFEST, FIXTURES, "token_lm_resident")
    net = cells.build_net(cell, seed=5)
    sample, = cells.make_batches(cell.config, 6, 1, 4, 12)
    ok, detail = correct.against_reference(net, _PlainTokenLM, sample,
                                           "float32")
    assert ok, detail
    assert "gradients rel L2" in detail


def test_command_refuses_to_run_without_a_tpu():
    name = cells.load_manifest(ROOT)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "not a TPU" in p.stderr
    assert p.stdout == ""                            # no result line

"""CPU rehearsals of the harness at tiny shapes: the iterator that is the
traffic generator, one whole run of each kind of cell through ``run_cell``,
and the command's refusal to run without a TPU."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import cells, run
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
    ("charrnn_resident", 1), ("lenet_hostfed", 0), ("lenet_pw4", 1)])
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

"""What a cell is made of, read from data: the manifest's entry, the
configuration's file and the traffic mix's file; and the two things built
from them, the network and the pool of batches, both from ``--seed``.

A configuration file (``configs/<name>.json``) holds ``builder``
(``zoo:<Class>`` of ``deeplearning4j_tpu.models`` or
``file:<module>:<function>`` under this directory), ``builder_kwargs``,
``global_conf`` (attributes set on the configuration's ``global_conf``),
``features`` / ``labels`` (how a batch is drawn: their ``kind``s name the
module ``batches/<features.kind>__<labels.kind>.py``), ``unit``,
``reference`` and ``opcount`` (module names under ``reference/`` and
``opcount/``; left out, that part of the check or that metric is left out),
``correct_sample`` and the record of its origin (``source``, ``published``,
``reduced``, ``assumed``). A traffic file (``traffic/<name>.json``) holds ``batch``,
``seq_len`` (sequences only), ``feed`` (``resident`` | ``hostfed``),
``pool`` (distinct batches cycled), ``entry`` (``fit`` |
``parallel_wrapper``), ``run_ahead`` (how many handed-out batches the
iterator lets the host lead the device by), ``warmup_batches`` and
``trace_batches``. Either file may carry a ``rehearse`` block of overrides
for the CPU rehearsal.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import pkgutil

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = os.path.basename(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: dict      # {"end_to_end": [entries], "per_layer": [entries]}

    @property
    def batch(self):
        return int(self.traffic["batch"])

    @property
    def seq_len(self):
        return self.traffic.get("seq_len")

    @property
    def units_per_batch(self):
        """Work units (the configuration's ``unit``) in one batch."""
        return self.batch * int(self.seq_len or 1)

    @property
    def group(self):
        """Batches the entry point consumes per dispatched step."""
        return self.chips if self.traffic["entry"] == "parallel_wrapper" else 1

    @property
    def iterations_per_step(self):
        """What one dispatched step adds to ``net.iteration_count``: one per
        truncated-BPTT segment."""
        tbptt = self.config.get("builder_kwargs", {}).get("tbptt")
        if tbptt and self.seq_len and self.seq_len > tbptt:
            return -(-int(self.seq_len) // int(tbptt))
        return 1


def _read_json(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _rehearsal(doc):
    """``doc`` with its ``rehearse`` block laid over it, one level deep."""
    out = {k: v for k, v in doc.items() if k != "rehearse"}
    for k, v in doc.get("rehearse", {}).items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def load_manifest(root):
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def applies(entry, workload):
    """Whether a metric's entry of the manifest is reported in ``workload``."""
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(manifest, root, workload, rehearse=False):
    """The cell ``workload`` of ``manifest``, its files found by name under
    ``root``: the configuration's at its ``file``, the traffic mix's at
    ``<paths[0]>/traffic/<traffic>.json``."""
    try:
        entry = next(w for w in manifest["workloads"] if w["name"] == workload)
    except StopIteration:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {known})") from None
    conf = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = _read_json(os.path.join(root, conf["file"]))
    traffic = _read_json(os.path.join(root, manifest["paths"][0], "traffic",
                                      entry["traffic"] + ".json"))
    if rehearse:
        config, traffic = _rehearsal(config), _rehearsal(traffic)
    e2e = [m for m in manifest["end_to_end"] if applies(m, workload)]
    names = {m["name"] for m in e2e}
    # a per-layer metric is reported only where the metric it moves is
    per_layer = [m for m in manifest["per_layer"]
                 if applies(m, workload) and m["moves"] in names]
    return Cell(workload, int(entry["chips"]), config, traffic,
                {"end_to_end": e2e, "per_layer": per_layer})


def module(kind, name):
    """The module ``<kind>/<name>.py`` of this directory (``layer_metrics``,
    ``opcount``, ``reference``, ``builders``, ``batches``), or None if there
    is none."""
    full = f"{PACKAGE}.{kind}.{name}"
    if importlib.util.find_spec(full) is None:
        return None
    return importlib.import_module(full)


# ---------------------------------------------------------------- network
def build_net(cell, seed):
    """The cell's network, initialised from ``seed`` through the program's
    own builders and ``init()``."""
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    config = cell.config
    kind, _, what = config["builder"].partition(":")
    kwargs = dict(config.get("builder_kwargs", {}))
    if kind == "zoo":
        import deeplearning4j_tpu.models as zoo
        if "input_shape" in kwargs:
            kwargs["input_shape"] = tuple(kwargs["input_shape"])
        conf = getattr(zoo, what)(seed=seed, **kwargs).conf()
    elif kind == "file":
        mod, _, fn = what.partition(":")
        conf = getattr(importlib.import_module(f"{PACKAGE}.{mod}"),
                       fn)(seed=seed, **kwargs)
    else:
        raise SystemExit(f"builder {config['builder']!r}: want zoo:<Class> "
                         f"or file:<module>:<function>")
    for key, value in config.get("global_conf", {}).items():
        setattr(conf.global_conf, key, value)
    if cell.traffic["feed"] == "resident":
        conf.global_conf.cache_mode = "device"
    net = (MultiLayerNetwork(conf) if isinstance(conf, MultiLayerConfiguration)
           else ComputationGraph(conf))
    return net.init()


# ------------------------------------------------------------------- data
def make_batches(config, seed, n, batch, seq_len=None):
    """``n`` distinct batches as the configuration's ``features`` /
    ``labels`` describe them, drawn from ``seed`` by the module
    ``batches/<features.kind>__<labels.kind>.py``: on the host, as a user's
    iterator would hand them over."""
    feats, labels = config["features"], config["labels"]
    kind = module("batches", f"{feats['kind']}__{labels['kind']}")
    if kind is None:
        from benchmark import batches
        found = sorted(m.name for m in pkgutil.iter_modules(batches.__path__))
        raise SystemExit(f"no generator for features {feats['kind']!r} with "
                         f"labels {labels['kind']!r} under batches/ (found: "
                         f"{', '.join(found)})")
    rng = np.random.default_rng([int(seed), 0xDA7A])
    return kind.draw(rng, feats, labels, n, batch, seq_len)

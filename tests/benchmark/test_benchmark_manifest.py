"""``BENCHMARK.json`` against the contract it was written to, and every cell's
files found by the names in it: what the driver refuses before a single run,
checked here at no chip time."""
import json
import os
import re

import pytest

from benchmark import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = cells.load_manifest(ROOT)
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}\Z")
#: a layer is a plain name too (it may start with '_'), and PERF.md §3 lists it
LAYER = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
PERF_MD = open(os.path.join(ROOT, "PERF.md"), encoding="utf-8").read()
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: a width may never be listed as reduced: a hidden, intermediate, latent,
#: state or projection size, a key that ends in ``_dim`` or ``_rank``, a head
#: size, an expansion factor, the number of experts per token. A *count*
#: (layers, heads, routed experts, rows of the vocabulary) may be the chip's
#: share (``model-configs`` guide, section 4), so ``hidden`` and ``head``
#: match only as sizes. ``sliding_window`` is covered: the guide lists
#: "window and state sizes" among the widths that are never cut (a shorter
#: window is a cheaper attention, not a share of the published one);
#: ``max_window_layers``, a count of layers, is not.
WIDTH = re.compile(r"hidden_size|hidden_dim|intermediate|latent|state|proj|"
                   r"_dim$|_rank$|head_?dim|d_head|head_size|expan|"
                   r"experts_per|width|sliding_windows?$|window_size")


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert len(MANIFEST["command"]) <= 32
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells has to fit into 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in MANIFEST[key]]
    assert all(NAME.match(n) for n in names), names
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        group = [x["name"] for x in MANIFEST[key]]
        assert len(group) == len(set(group)), key


def test_configs():
    assert 1 <= len(MANIFEST["configs"]) <= 24
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert doc["name"] == c["name"] and doc["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if WIDTH.search(k)]


@pytest.mark.parametrize("key", [
    "num_hidden_layers", "n_routed_experts", "vocab_size",
    "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
    "max_window_layers"])
def test_a_count_may_be_reduced(key):
    assert not WIDTH.search(key)


@pytest.mark.parametrize("key", [
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "kv_lora_rank", "mamba_d_state", "mamba_d_head", "num_experts_per_tok",
    "sliding_window", "sliding_window_size", "head_dim", "mamba_headdim",
    "mamba_expand", "ffn_hidden_size"])
def test_a_width_may_not_be_reduced(key):
    assert WIDTH.search(key)


def test_workloads():
    cells_ = MANIFEST["workloads"]
    assert 2 <= len(cells_) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells_]
    assert len(pairs) == len(set(pairs))
    four = [w for w in cells_ if w["chips"] == 4]
    assert len(four) <= max(1, len(cells_) // 4)
    for w in cells_:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_metrics():
    e2e, per_layer = MANIFEST["end_to_end"], MANIFEST["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per_layer) <= 128
    assert "setup_s" in {m["name"] for m in e2e}
    known = {w["name"] for w in MANIFEST["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in per_layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert LAYER.match(m["layer"]), m["layer"]
        assert f"`{m['layer']}`" in PERF_MD, m["layer"]
        assert m["moves"] in {x["name"] for x in e2e}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + per_layer:
        assert m["better"] in ("higher", "lower")
        assert set(m.get("workloads", [])) <= known


@pytest.mark.parametrize("workload",
                         [w["name"] for w in MANIFEST["workloads"]])
def test_cell_files_resolve_by_name(workload):
    cell = cells.load_cell(MANIFEST, ROOT, workload)
    for rehearsal in (False, True):
        c = cells.load_cell(MANIFEST, ROOT, workload, rehearse=rehearsal)
        for key in ("batch", "feed", "pool", "entry", "run_ahead",
                    "warmup_batches", "trace_batches"):
            assert key in c.traffic, key
        assert c.traffic["feed"] in ("resident", "hostfed")
    # every cell reports setup_s, another end-to-end metric and a layer's
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.metrics["per_layer"]
    kind, _, what = cell.config["builder"].partition(":")
    if kind == "zoo":
        import deeplearning4j_tpu.models as zoo
        assert hasattr(zoo, what)
    else:
        assert cells.module(*what.partition(":")[0].split(".")) is not None
    for c in (cell, cells.load_cell(MANIFEST, ROOT, workload, rehearse=True)):
        assert callable(cells.module("batches", "{}__{}".format(
            c.config["features"]["kind"], c.config["labels"]["kind"])).draw)
    for kind in ("reference", "opcount"):       # optional; named, they exist
        if kind in cell.config:
            assert cells.module(kind, cell.config[kind]) is not None
    if cell.traffic["entry"] == "parallel_wrapper":
        assert cell.chips == 4 and "one_device_seconds" in cell.traffic


@pytest.mark.parametrize("metric",
                         [m["name"] for m in MANIFEST["per_layer"]])
def test_every_layer_metric_has_a_reader_of_its_name(metric):
    reader = cells.module("layer_metrics", metric)
    assert reader is not None and callable(reader.read)
    assert reader.__doc__                  # says what it reads, and where


#: device time per step of the step program and of its Pallas kernels, ms, as
#: the ledger's newest lines have them (PR 24, change side)
MEASURED_MS = {"resnet50_b256_resident": (95.483, None),
               "charrnn_b64_t5000_tbptt50_pool20": (52.289, 19.944),
               "resnet50_pw4_b1024_resident": (100.46, None)}


@pytest.mark.parametrize("workload", sorted(MEASURED_MS))
def test_no_floor_is_above_what_the_chip_has_done(workload):
    """A roofline share over 100 % is a count of work that is not needed: the
    least times from ``opcount/`` lie under the times the ledger holds."""
    from benchmark import device
    cell = cells.load_cell(MANIFEST, ROOT, workload)
    opcount = cells.module("opcount", cell.config["opcount"])
    peaks = device.peaks("TPU v5 lite")
    step_ms, kernel_ms = MEASURED_MS[workload]

    def least_ms(work):
        return 1e3 * max(work["flops"] / peaks["flops_bf16"],
                         work["bytes"] / peaks["hbm_bytes_per_s"])
    step = opcount.step_work(cell.config, cell.traffic)
    assert 0.25 * step_ms < least_ms(step) <= step_ms   # batch is per chip
    if kernel_ms is not None:
        kernels = opcount.kernel_work(cell.config, cell.traffic)
        assert 0.25 * kernel_ms < least_ms(kernels) <= kernel_ms
        assert kernels["flops"] < step["flops"]

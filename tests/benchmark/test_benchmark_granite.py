"""The ``granite_4_0_h_micro`` configuration and its cell at no chip time:
the configuration's file against the published ``config.json``, the operation
count against a count by hand, the system against the plain reference on
seeded weights in float32, the cell's whole control flow through ``run_cell``
at rehearsal size (the reference check in it), the control and the two planted
faults that the chip's limits stand between, and the readers it brings."""
import gzip
import importlib.util
import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmark import cells, correct, device, run, xplane
from benchmark.opcount import granite_4_0_h_micro as opcount
from benchmark.reference import granite_4_0_h_micro as reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = cells.load_manifest(ROOT)
CELL = "granite_l10_b1_t8192_resident"
NEW_METRICS = ["ssm_ms_per_step", "ssd_ms_per_step", "ssm_ms_per_block",
               "ssd_roofline"]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
#: every key of ``ibm-granite/granite-4.0-h-micro``'s config.json, as the
#: catalog beside the ``model-configs`` guide has it (``layer_types`` apart:
#: four periods of PERIOD)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


@pytest.fixture(autouse=True)
def _own_registry(monkeypatch):
    """A rehearsal's steps go to a metrics registry of their own (as in
    ``test_benchmark_harness``)."""
    import deeplearning4j_tpu.monitor.registry as registry
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())


def test_the_configuration_holds_every_published_number_but_the_two_counts():
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    cut = {"num_hidden_layers": 10, "vocab_size": 12544}
    for key, value in PUBLISHED.items():
        if key in cut:
            assert config[key] == cut[key], key
            assert config["published"][key] == value, key
            assert config["reduced_why"][key]
        else:
            assert config[key] == value, key
    assert config["layer_types"] == PERIOD * 4
    assert cut["vocab_size"] * 8 == PUBLISHED["vocab_size"]     # the floor
    kw = config["builder_kwargs"]     # what is built is what is published
    assert {k: kw[k] for k in kw if k != "layer_types"} == {
        "vocab": config["vocab_size"], "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "intermediate": config["shared_intermediate_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "attention_multiplier": config["attention_multiplier"],
        "embedding_multiplier": config["embedding_multiplier"],
        "residual_multiplier": config["residual_multiplier"],
        "logits_scaling": config["logits_scaling"],
        "rms_norm_eps": config["rms_norm_eps"],
        "mamba_heads": config["mamba_n_heads"],
        "mamba_head_dim": config["mamba_d_head"],
        "mamba_state": config["mamba_d_state"],
        "mamba_conv": config["mamba_d_conv"],
        "mamba_chunk": config["mamba_chunk_size"]}
    assert kw["layer_types"] == config["layer_types"]
    assert kw["layer_types"][:kw["layers"]] == PERIOD        # one whole period
    assert kw["mamba_heads"] * kw["mamba_head_dim"] == (
        config["mamba_expand"] * config["hidden_size"])
    assert config["features"]["vocab"] == config["vocab_size"]
    assert set(config["assumed"]) >= {"init", "optimizer", "data",
                                      "precision", "loss_reduction"}
    sample = config["correct_sample"]
    assert sample["seq_len"] == cell.seq_len == 8192
    assert sample["examples"] == 1
    assert sample["why"] and sample["holds"] and sample["does_not_hold"]
    # the reference's defaults are the file's values
    defaults = dict(zip(
        ("heads", "attention_multiplier", "embedding_multiplier",
         "residual_multiplier", "logits_scaling", "eps"),
        reference.loss.__defaults__))
    assert defaults == {
        "heads": kw["heads"], "eps": kw["rms_norm_eps"],
        **{k: kw[k] for k in ("attention_multiplier", "embedding_multiplier",
                              "residual_multiplier", "logits_scaling")}}
    # and the rehearsal keeps them: only widths, depth and rows are toys,
    # and its cut of the period still holds three runs of both kinds
    toy = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True).config
    tkw = toy["builder_kwargs"]
    assert {k: tkw[k] for k in defaults if k in tkw} == {
        k: kw[k] for k in defaults if k in kw}
    assert (tkw["heads"], tkw["kv_heads"], tkw["mamba_conv"]) == (32, 8, 4)
    assert tkw["layer_types"][:tkw["layers"]] == PERIOD[:7]


def test_opcount_at_the_published_sizes_is_the_hand_count():
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    mixer = 2048 * 8512 + 4352 * 5 + 192 + 4096 + 4096 * 2048
    mlp = 3 * 2048 * 8192
    mamba_layer = mixer + mlp + 4096
    attention_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp + 4096
    assert (mixer, mlp, mamba_layer, attention_layer) == (
        25_847_232, 50_331_648, 76_182_976, 60_821_504)
    params = 9 * mamba_layer + attention_layer + 12544 * 2048 + 2048
    assert opcount.params(cell.config) == params == 772_160_448
    tokens = 8192
    forward = (9 * (2 * (2048 * 8512 + 4096 * 2048) + 4 * 64 * 64 * 128
                    + 2 * mlp)
               + 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * 8192 * 2048
               + 2 * mlp + 2 * 2048 * 12544)
    assert round(forward / 1e6) == 1596
    work = opcount.step_work(cell.config, cell.traffic)
    assert work["flops"] == 3 * tokens * forward == 39_228_083_798_016
    assert work["bytes"] == 24 * params + 2 * tokens * 4
    kernels = opcount.kernel_work(cell.config, cell.traffic)
    # 2 causal [T, T, 64] products forward and 5 backward a query head, in
    # the one attention layer; a causal product is half of 2·T·T·64
    assert kernels == {"flops": 7 * 8192 * 8192 * 64 * 32, "bytes": 0,
                       "calls": 3}
    scan = opcount.ssd_work(cell.config, cell.traffic)
    assert scan == {"flops": 3 * tokens * 9 * 4 * 64 * 64 * 128, "bytes": 0}
    assert 0.45e12 < scan["flops"] < 0.47e12 < work["flops"]
    # the least times lie under what the chip has done (my chip runs, PR 33)
    peaks = device.peaks("TPU v5 lite")
    assert 1e3 * work["flops"] / peaks["flops_bf16"] < STEP_MS
    assert 1e3 * scan["flops"] / peaks["flops_bf16"] < SSD_MS
    assert 1e3 * kernels["flops"] / peaks["flops_bf16"] < PALLAS_MS


#: device milliseconds per step of the step program, of the ops under
#: ``ssd`` and of the flash kernels (my chip runs, PR 33, traced)
STEP_MS, SSD_MS, PALLAS_MS = 536.90, 51.42, 21.91


def _sample(cell, seed=8):
    spec = cell.config["correct_sample"]
    return cells.make_batches(cell.config, seed, 1, spec["examples"],
                              spec["seq_len"])[0]


def test_the_system_agrees_with_the_reference_in_float32():
    """Seeded random weights at rehearsal size: the loss and every gradient
    leaf to 1e-4, a sample that is no multiple of the chunk."""
    import jax
    cell = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True)
    net = cells.build_net(cell, seed=7)
    sample = _sample(cell)
    assert sample.features.shape[1] % cell.config["builder_kwargs"][
        "mamba_chunk"]
    grads, loss = net.compute_gradient_and_score(sample)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref = jax.value_and_grad(reference.loss)(
            net.params, sample.features, sample.labels)
    assert loss == pytest.approx(float(ref_loss), rel=1e-5)
    distance, worst, own = correct.grad_distance(
        jax.tree_util.tree_map(np.asarray, grads), ref)
    assert distance < 1e-5 and worst[0] < 1e-4, worst
    assert len(own) == 37 and max(own.values()) < 1e-4
    assert {"['embed']['W']", "['stack']['r0.A_log']",
            "['stack']['r0.dt_bias']", "['stack']['r1.Wk']"} <= set(own)


@pytest.mark.parametrize("trace", [0, 1])
def test_run_cell_rehearsal_of_the_granite_cell(trace, tmp_path):
    """The cell's own files at rehearsal size through one whole run on the
    CPU, the reference check among its checks; the program's gauges are set
    where the step is built and traced."""
    from deeplearning4j_tpu.monitor import get_registry
    notes = []
    result = run.run_cell(MANIFEST, ROOT, CELL, seed=2**31 + 11, seconds=0.5,
                          trace=bool(trace), rehearse=True, note=notes.append,
                          trace_root=str(tmp_path))
    result = json.loads(json.dumps(result))
    assert result["correct"] is True, notes
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert any(n.startswith("check reference: ok") for n in notes), notes
    gauges = get_registry().snapshot()
    assert {row["labels"]["kind"]: row["value"]
            for row in gauges["hybrid_blocks"]} == {"mamba": 6,
                                                    "attention": 1}
    assert {row["value"] for row in gauges["ssm_chunks"]} == {3}   # 24 / 8
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    if trace:
        allowed = {m["name"] for m in cell.metrics["per_layer"]}
        assert set(NEW_METRICS) | {
            "remat_ms_per_step", "flash_kernels_roofline",
            "pallas_ms_per_step", "mfu", "train_step_roofline",
            "scoped_device_time_share"} <= allowed
        assert result["metrics"]["window_compiles"]["value"] == 0
        # off the chip there is no device plane: the new readers say nothing
        assert not set(NEW_METRICS) & set(result["metrics"])
    else:
        allowed = {m["name"] for m in cell.metrics["end_to_end"]}
        assert set(result["metrics"]) == allowed == {"throughput_per_chip",
                                                     "setup_s"}
    assert set(result["metrics"]) <= allowed


def _rehearse_with_a_fault(tmp_path):
    notes = []
    result = run.run_cell(MANIFEST, ROOT, CELL, seed=2**31 + 11, seconds=0.2,
                          trace=False, rehearse=True, note=notes.append,
                          trace_root=str(tmp_path))
    failed = [n for n in notes if n.startswith("check ") and "FAILED" in n]
    assert result["correct"] is False
    assert len(failed) == 1 and failed[0].startswith(
        "check reference: FAILED"), notes
    # the run trains and its state is finite: only the reference tells
    assert result["failed"] == 0 and result["attempted"] > 0


def test_a_state_not_carried_across_a_chunk_boundary_is_not_correct(
        monkeypatch, tmp_path):
    """The first planted fault of the chip's limits
    (``reference/granite_4_0_h_micro.py`` has its readings there): every
    chunk starts from an empty state, as if the scan's chunks were separate
    sequences."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.layers import mamba

    def forgetful(local, decay, first):
        return jnp.zeros_like(local), first

    monkeypatch.setattr(mamba, "carried_states", forgetful)
    _rehearse_with_a_fault(tmp_path)


def test_a_head_cut_from_the_embedding_is_not_correct(monkeypatch, tmp_path):
    """The second planted fault: head and embedding as two leaves. The head
    reads the embedding's values but its gradient goes nowhere, as if the
    head were a leaf of its own that the embedding's gradient leaves out."""
    import jax
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    sound = ComputationGraph._params_of

    def cut(self, params, name):
        out = sound(self, params, name)
        if "tied_W" in out:
            out = {**out, "tied_W": jax.lax.stop_gradient(out["tied_W"])}
        return out

    monkeypatch.setattr(ComputationGraph, "_params_of", cut)
    _rehearse_with_a_fault(tmp_path)


def fp8_operands_reference():
    """The control that the gradients' limits on the chip stand under: the
    plain reference put in the program's place with the operands of every
    product (the projections, the MLPs' gemms, attention's two, the head's)
    rounded to float8's three mantissa bits (e4m3; the exponent left alone,
    as a scaled cast would), the precision below the bfloat16 the
    configuration states. The recurrence, the convolution and the norms are
    elementwise and stay float32, as they do in the program. Forward
    operands only: the cotangents pass unrounded, so each backward product
    has one rounded operand. (``test_benchmark_ouro.py`` has the looped
    LM's.)"""
    import jax.numpy as jnp
    from jax import lax

    def e4m3(x):
        bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        kept = lax.bitcast_convert_type(
            (bits + jnp.uint32(0x00080000)) & jnp.uint32(0xFFF00000),
            jnp.float32)
        return x + lax.stop_gradient(kept - x)

    class Rounded:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def dot(a, w, precision=None):
            return jnp.dot(e4m3(a), e4m3(w), precision=precision)

        @staticmethod
        def einsum(spec, a, b, precision=None):
            return jnp.einsum(spec, e4m3(a), e4m3(b), precision=precision)

    spec = importlib.util.find_spec(reference.__name__)
    control = importlib.util.module_from_spec(spec)    # a second instance
    spec.loader.exec_module(control)
    control.jnp = Rounded()
    return control


def test_fp8_operands_where_the_configuration_says_bf16_are_not_correct():
    """The control at rehearsal size: the reference with float8 operands in
    the program's place fails the reference check's gradient comparison by
    the float32 limit and by the chip's limit on all gradients (0.062
    against 0.05; the two held leaves read 0.049 and 0.055 at these widths,
    at their limits' edge: on the chip at the cell's size all three limits
    refuse it, 0.26 / 0.26 / 0.25-0.29, ``reference/granite_4_0_h_micro.py``
    has the readings)."""
    import jax
    cell = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True)
    net = cells.build_net(cell, seed=7)
    sample = _sample(cell)
    args = (net.params, sample.features, sample.labels)
    ref = jax.grad(reference.loss)(*args)
    control = jax.grad(fp8_operands_reference().loss)(*args)
    again = correct.grad_distance(jax.tree_util.tree_map(np.asarray, ref),
                                  ref)[0]
    distance, _, own = correct.grad_distance(
        jax.tree_util.tree_map(np.asarray, control), ref)
    assert again == 0.0
    chip = reference.TOLERANCE["bfloat16"]
    assert distance > chip["grads"] == 0.05
    assert all(own[path] > 0.5 * limit
               for path, limit in chip["leaves"].items())
    assert distance > 100 * reference.TOLERANCE["float32"]["grads"]


def test_the_chips_limits_name_leaves_the_network_has():
    cell = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True)
    net = cells.build_net(cell, seed=7)
    import jax
    paths = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(net.params)[0]}
    held = set(reference.TOLERANCE["bfloat16"]["leaves"])
    assert held <= paths
    assert "['embed']['W']" in held                       # the tied leaf
    assert any(".A_log']" in p or ".dt_bias']" in p for p in held)


# ------------------------------------------------------------ the readers
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The char-RNN trace recorded on a v5e with its program's text (PR 23):
    a program with ``updater`` and layer scopes and none of this model's."""
    fixtures = os.path.join(HERE, "fixtures")
    path = tmp_path_factory.mktemp("granite") / "charrnn.xplane.pb"
    with gzip.open(os.path.join(
            fixtures, "charrnn_v5e_3fits_pr23.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(
            fixtures, "charrnn_v5e_3fits_pr23.jit_scanned.hlo.txt.gz"),
            "rt") as fh:
        text = fh.read()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "graves_lstm_charrnn.json")) as fh:
        config = json.load(fh)
    return xplane.load(str(path)), text, config


def _run(trace, config, steps=3):
    return types.SimpleNamespace(
        trace=trace, trace_window=types.SimpleNamespace(steps=steps),
        cell=types.SimpleNamespace(config=config, traffic={}), extras={},
        devices=None)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_new_readers_say_nothing_where_there_is_nothing(metric, recorded,
                                                            monkeypatch):
    """On a program without the hybrid stack's scopes (the parent's, any
    other cell's) and on a run without a trace a reader returns None and
    does not raise."""
    trace, text, config = recorded
    monkeypatch.setattr(device, "live_program_texts", lambda devices: [text])
    reader = cells.module("layer_metrics", metric)
    bare = _run(None, config)
    bare.peaks, bare.opcount = None, None
    assert reader.read(bare) is None
    there = _run(trace, config)
    there.peaks = device.peaks("TPU v5 lite")
    there.opcount = cells.module("opcount", "resnet50_imagenet")  # no ssd_work
    assert reader.read(there) is None


def test_the_scans_readers_divide_by_the_gauge_and_by_the_needed_work(
        monkeypatch):
    from benchmark.layer_metrics import (ssd_ms_per_step, ssd_roofline,
                                         ssm_ms_per_block, ssm_ms_per_step)
    from deeplearning4j_tpu.monitor import get_registry
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    monkeypatch.setattr(ssm_ms_per_step, "read", lambda run: 180.0)
    assert ssm_ms_per_block.read(None) is None            # no gauge
    get_registry().gauge("hybrid_blocks", network="cg", kind="mamba").set(9)
    get_registry().gauge("hybrid_blocks", network="cg",
                         kind="attention").set(1)
    assert ssm_ms_per_block.read(None) == 180.0 / 9
    monkeypatch.setattr(ssd_ms_per_step, "read", lambda run: 50.0)
    run_ = types.SimpleNamespace(cell=cell, opcount=opcount,
                                 peaks=device.peaks("TPU v5 lite"))
    least_ms = 1e3 * 3 * 8192 * 9 * 4 * 64 * 64 * 128 / 197e12
    assert ssd_roofline.read(run_) == pytest.approx(100 * least_ms / 50.0)
    assert 2.3 < least_ms < 2.4
    run_.opcount = cells.module("opcount", "ouro_2_6b")   # no ssd_work
    assert ssd_roofline.read(run_) is None

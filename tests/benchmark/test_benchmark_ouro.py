"""The ``ouro_2_6b`` configuration and its cell at no chip time: the
configuration's file against the published ``config.json``, the operation
count against a count by hand, the cell's whole control flow through
``run_cell`` at rehearsal size (the reference check in it: the published 16
heads and 4 passes over toy widths), and the readers it brings on a trace
recorded on a v5e."""
import gzip
import json
import os
import shutil
import types

import pytest

from benchmark import cells, device, op_scopes, program_trace, run, xplane
from benchmark.opcount import ouro_2_6b as opcount
from benchmark.reference import ouro_2_6b as reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = cells.load_manifest(ROOT)
CELL = "ouro_l4_ut4_b2_t4096_resident"
#: every number of ``ByteDance/Ouro-2.6B``'s config.json, as the catalog
#: beside the ``model-configs`` guide has it
PUBLISHED = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
             "max_position_embeddings": 65536, "max_window_layers": 48,
             "num_attention_heads": 16, "num_hidden_layers": 48,
             "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
             "rope_theta": 1000000, "total_ut_steps": 4,
             "early_exit_threshold": 1, "vocab_size": 49152}


@pytest.fixture(autouse=True)
def _own_registry(monkeypatch):
    """A rehearsal's steps go to a metrics registry of their own (as in
    ``test_benchmark_harness``)."""
    import deeplearning4j_tpu.monitor.registry as registry
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())


def test_the_configuration_holds_every_published_number_but_the_depth():
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert config[key] == 4 and config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["tie_word_embeddings"] is False
    assert config["hidden_act"] == "silu" and config["model_type"] == "ouro"
    kw = config["builder_kwargs"]     # what is built is what is published
    assert (kw["vocab"], kw["hidden"], kw["heads"], kw["head_dim"],
            kw["intermediate"], kw["blocks"], kw["passes"]) == (
        config["vocab_size"], config["hidden_size"],
        config["num_attention_heads"], config["head_dim"],
        config["intermediate_size"], config["num_hidden_layers"],
        config["total_ut_steps"])
    assert kw["rope_theta"] == config["rope_theta"]
    assert kw["rms_norm_eps"] == config["rms_norm_eps"]
    assert config["features"]["vocab"] == config["vocab_size"]
    assert set(config["assumed"]) >= {"entropy_weight", "exit_gate",
                                      "pass_to_pass_state", "biases",
                                      "optimizer"}
    assert config["correct_sample"]["seq_len"] == cell.seq_len == 4096
    # the reference's defaults are the file's values
    defaults = dict(zip(("heads", "passes", "theta", "eps", "beta"),
                        reference.loss.__defaults__))
    assert defaults == {"heads": kw["heads"], "passes": kw["passes"],
                        "theta": kw["rope_theta"], "eps": kw["rms_norm_eps"],
                        "beta": kw["entropy_weight"]}
    # and the rehearsal keeps them: only widths and depth are toys
    toy = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True).config
    assert toy["builder_kwargs"]["blocks"] >= 2
    assert {k: toy["builder_kwargs"][k] for k in
            ("heads", "passes", "rope_theta", "rms_norm_eps",
             "entropy_weight")} == {k: kw[k] for k in
                                    ("heads", "passes", "rope_theta",
                                     "rms_norm_eps", "entropy_weight")}


def test_opcount_at_the_published_sizes_is_the_hand_count():
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    block = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert block == 51_380_224
    tokens, passes, blocks = 2 * 4096, 4, 4
    work = opcount.step_work(cell.config, cell.traffic)
    assert work["flops"] == 3 * tokens * (
        passes * blocks * (2 * block + 2 * 4096 * 2048)
        + passes * 2 * 2048 * 49152) == 66_795_331_387_392
    params = 2 * 49152 * 2048 + blocks * (block + 4 * 2048) + 2048 + 2049
    assert opcount.params(cell.config) == params == 406_884_353
    assert work["bytes"] == 24 * params + 2 * tokens * 4
    kernels = opcount.kernel_work(cell.config, cell.traffic)
    # 2 causal [T, T, 128] products forward and 5 backward, per head and
    # block application; a causal product is half of 2·T·T·128
    assert kernels["flops"] == 7 * 4096 * 4096 * 128 * 16 * 2 * 16
    assert kernels["bytes"] == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_run_cell_rehearsal_of_the_ouro_cell(trace, tmp_path):
    """The cell's own files at rehearsal size through one whole run on the
    CPU, the reference check among its checks."""
    notes = []
    result = run.run_cell(MANIFEST, ROOT, CELL, seed=2**31 + 11, seconds=0.5,
                          trace=bool(trace), rehearse=True, note=notes.append,
                          trace_root=str(tmp_path))
    result = json.loads(json.dumps(result))
    assert result["correct"] is True, notes
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert any(n.startswith("check reference: ok") for n in notes), notes
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    if trace:
        allowed = {m["name"] for m in cell.metrics["per_layer"]}
        assert {"loop_blocks_ms_per_step", "loop_head_ms_per_step",
                "remat_ms_per_step", "flash_kernels_roofline",
                "loop_ms_per_block_application", "mfu",
                "train_step_roofline", "pallas_ms_per_step"} <= allowed
        assert result["metrics"]["window_compiles"]["value"] == 0
        # off the chip there is no device plane: the new readers say nothing
        assert not {"loop_blocks_ms_per_step", "loop_head_ms_per_step",
                    "remat_ms_per_step", "flash_kernels_roofline",
                    "loop_ms_per_block_application"} & set(result["metrics"])
    else:
        allowed = {m["name"] for m in cell.metrics["end_to_end"]}
        assert set(result["metrics"]) == allowed == {"throughput_per_chip",
                                                     "setup_s"}
    assert set(result["metrics"]) <= allowed


def test_bf16_logits_where_the_configuration_says_float32_are_not_correct(
        monkeypatch, tmp_path):
    """PR 28's control (the head's logits and softmax statistics rounded to
    bfloat16), planted in the rehearsal: the same run that is ``correct``
    above is not, by the reference check and by it alone. That is float32
    against float32: on the chip, where the system's own bf16 operands
    read as much, no limit holds it (16 seeds, PR 32; the readings are in
    ``reference/ouro_2_6b.py``), and the control the limits stand under is
    the float8 one below."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.layers.output import LoopLMOutputImpl

    sound = LoopLMOutputImpl._logits
    monkeypatch.setattr(
        LoopLMOutputImpl, "_logits",
        lambda self, params, h: sound(self, params, h).astype(jnp.bfloat16))
    notes = []
    result = run.run_cell(MANIFEST, ROOT, CELL, seed=2**31 + 11, seconds=0.2,
                          trace=False, rehearse=True, note=notes.append,
                          trace_root=str(tmp_path))
    assert result["correct"] is False
    failed = [n for n in notes if n.startswith("check ") and "FAILED" in n]
    assert len(failed) == 1 and failed[0].startswith(
        "check reference: FAILED"), notes
    assert result["failed"] == 0         # the state is finite: only wrong


def test_half_of_the_tokens_left_out_of_the_loss_is_not_correct(
        monkeypatch, tmp_path):
    """The fault read on the chip beside the float8 control (gradients 0.54
    and more, ``reference/ouro_2_6b.py``), planted under a whole rehearsal:
    the loss sums over the first half of every sequence only, as if half of
    the batch had been left out. The run trains, its state is finite, and
    the reference check alone says that it is wrong."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.layers.output import LoopLMOutputImpl

    sound = LoopLMOutputImpl.loss_on

    def first_half(self, params, state, x, labels, mask=None, **kwargs):
        keep = jnp.arange(labels.shape[1]) < labels.shape[1] // 2
        return sound(self, params, state, x, labels,
                     mask=jnp.broadcast_to(keep, labels.shape).astype(
                         jnp.float32), **kwargs)

    monkeypatch.setattr(LoopLMOutputImpl, "loss_on", first_half)
    notes = []
    result = run.run_cell(MANIFEST, ROOT, CELL, seed=2**31 + 11, seconds=0.2,
                          trace=False, rehearse=True, note=notes.append,
                          trace_root=str(tmp_path))
    assert result["correct"] is False
    failed = [n for n in notes if n.startswith("check ") and "FAILED" in n]
    assert len(failed) == 1 and failed[0].startswith(
        "check reference: FAILED"), notes
    assert result["failed"] == 0 and result["attempted"] > 0


def fp8_operands_reference():
    """The control that the gradients' limit on the chip stands under (PR
    32): the plain reference put in the program's place with the operands
    of every product (the blocks' seven gemms, attention's two, the head's,
    the gate's) rounded to float8's three mantissa bits (e4m3; the exponent
    left alone, as a scaled cast would), the precision below the bfloat16
    the configuration states. Forward operands only: the cotangents pass
    unrounded, so each backward product has one rounded operand."""
    import importlib.util

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
    the float32 limit and by the chip's (readings on the chip at the
    cell's size: ``reference/ouro_2_6b.py``)."""
    import jax
    import numpy as np

    from benchmark import correct
    cell = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True)
    net = cells.build_net(cell, seed=7)
    sample, = cells.make_batches(cell.config, 8, 1, 2, 16)
    args = (net.params, sample.features, sample.labels)
    ref = jax.grad(reference.loss)(*args)
    control = jax.grad(fp8_operands_reference().loss)(*args)
    again = correct.grad_distance(jax.tree_util.tree_map(np.asarray, ref),
                                  ref)[0]
    distance, _, own = correct.grad_distance(
        jax.tree_util.tree_map(np.asarray, control), ref)
    assert again == 0.0
    chip = reference.TOLERANCE["bfloat16"]
    assert distance > chip["grads"] == 0.09
    assert all(own[path] > limit for path, limit in chip["leaves"].items())
    assert distance > 100 * reference.TOLERANCE["float32"]["grads"]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The char-RNN trace recorded on a v5e with its program's text (PR 23):
    a program with ``updater`` and layer scopes and none of the looped LM's."""
    fixtures = os.path.join(HERE, "fixtures")
    path = tmp_path_factory.mktemp("ouro") / "charrnn.xplane.pb"
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
        cell=types.SimpleNamespace(config=config), extras={}, devices=None)


def test_time_by_token_agrees_with_the_scope_readers(recorded, monkeypatch):
    trace, text, config = recorded
    monkeypatch.setattr(device, "live_program_texts", lambda devices: [text])
    run_ = _run(trace, config)
    # "optimizer" is, by program_trace.kind, every op under ``updater``
    assert op_scopes.ms_per_step(run_, "updater") == pytest.approx(
        program_trace.scoped_ms_per_step(run_, [text])["optimizer"])
    layers = op_scopes.ms_per_step(run_, "0", "1", "2", "loss")
    found = program_trace.scoped_ms_per_step(run_, [text])
    assert layers == pytest.approx(found["forward"] + found["backward"])


def test_time_per_block_application_reads_the_programs_gauge(monkeypatch):
    from benchmark.layer_metrics import (loop_blocks_ms_per_step,
                                         loop_ms_per_block_application)
    from deeplearning4j_tpu.monitor import get_registry
    monkeypatch.setattr(loop_blocks_ms_per_step, "read", lambda run: 1060.0)
    assert loop_ms_per_block_application.read(None) is None   # no gauge
    get_registry().gauge("looped_block_applications", network="cg").set(16)
    assert loop_ms_per_block_application.read(None) == 1060.0 / 16


@pytest.mark.parametrize("metric", ["loop_blocks_ms_per_step",
                                    "loop_head_ms_per_step",
                                    "remat_ms_per_step",
                                    "flash_kernels_roofline",
                                    "loop_ms_per_block_application"])
def test_the_new_readers_say_nothing_where_there_is_nothing(metric, recorded,
                                                            monkeypatch):
    """On a program without the looped LM's scopes (the parent's, any other
    cell's) and on a run without a trace a reader returns None and does
    not raise."""
    trace, text, config = recorded
    monkeypatch.setattr(device, "live_program_texts", lambda devices: [text])
    reader = cells.module("layer_metrics", metric)
    bare = _run(None, config)
    bare.peaks, bare.opcount = None, None
    assert reader.read(bare) is None
    there = _run(trace, config)
    there.peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    there.opcount = cells.module("opcount", "resnet50_imagenet")  # no kernels
    assert reader.read(there) is None

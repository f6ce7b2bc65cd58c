"""The ``mellum2_12b_a2_5b`` configuration and its cell at no chip time: the
configuration's file against the published ``config.json``, the operation
count against a count by hand and against the built network, the system
against the plain reference on seeded weights in float32, the cell's whole
control flow through ``run_cell`` at rehearsal size (the reference check in
it), the control and the planted faults that the chip's limits stand between,
and the readers it brings."""
import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmark import cells, correct, device, run, xplane
from benchmark.builders import mellum2_12b_a2_5b as builder
from benchmark.opcount import mellum2_12b_a2_5b as opcount
from benchmark.reference import mellum2_12b_a2_5b as reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = cells.load_manifest(ROOT)
CELL = "mellum2_l4_e16_b1_t8192_resident"
#: readers this configuration brings; ``BENCHMARK.json`` names none of them
#: yet (``test_benchmark_setup_spans`` pins its ``per_layer`` at 46 entries
#: and is the benchmark's to change: PERF.md section 7)
NEW_METRICS = ["swa_ms_per_step", "window_flash_roofline"]
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
#: every key of ``JetBrains/Mellum2-12B-A2.5B-Instruct``'s config.json, as
#: the catalog beside the ``model-configs`` guide has it
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
CUT = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 24576}


@pytest.fixture(autouse=True)
def _own_registry(monkeypatch):
    """A rehearsal's steps go to a metrics registry of their own (as in
    ``test_benchmark_harness``)."""
    import deeplearning4j_tpu.monitor.registry as registry
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())


def test_the_configuration_holds_every_published_number_but_the_three_counts():
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    config = cell.config
    assert config["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        if key in CUT:
            assert config[key] == CUT[key], key
            assert config["published"][key] == value, key
            assert config["reduced_why"][key]
        else:
            assert config[key] == value, key
    # the floors of the model-configs guide: a whole period, at least four
    # layers, 8 experts, an eighth of the vocabulary (a quarter here)
    assert CUT["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert CUT["num_experts"] * 4 == PUBLISHED["num_experts"]
    assert PUBLISHED["layer_types"][:CUT["num_hidden_layers"]] == PERIOD
    kw = config["builder_kwargs"]     # what is built is what is published
    assert kw == {
        "vocab": config["vocab_size"], "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "layer_types": config["layer_types"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "window": config["sliding_window"],
        "rope_parameters": config["rope_parameters"],
        "experts": config["num_experts"],
        "experts_published": config["published"]["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "moe_intermediate": config["moe_intermediate_size"],
        "norm_topk_prob": config["norm_topk_prob"],
        "rms_norm_eps": config["rms_norm_eps"]}
    assert set(config["mlp_layer_types"]) == {"sparse"}
    assert config["features"]["vocab"] == config["vocab_size"]
    assert set(config["assumed"]) >= {
        "deployment", "init", "optimizer", "data", "precision",
        "loss_reduction", "window", "rope", "no_qk_norm", "no_mtp_head",
        "no_balancing_loss", "unread_keys", "expert_walk"}
    sample = config["correct_sample"]
    assert sample["seq_len"] == cell.seq_len == 8192
    assert sample["examples"] == 1
    assert sample["why"] and sample["holds"] and sample["does_not_hold"]
    # the reference's defaults are the file's values
    defaults = dict(zip(("experts_per_token", "window", "head_dim", "eps",
                         "layer_types", "rope"), reference.loss.__defaults__))
    assert defaults == {
        "experts_per_token": kw["experts_per_token"],
        "window": kw["window"], "head_dim": kw["head_dim"],
        "eps": kw["rms_norm_eps"],
        "layer_types": tuple(kw["layer_types"][:kw["layers"]]),
        "rope": kw["rope_parameters"]}
    # and the rehearsal keeps them: only widths, heads and rows are toys
    toy = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True).config
    tkw = toy["builder_kwargs"]
    assert {k: tkw[k] for k in ("layers", "layer_types", "head_dim",
                                "window", "rope_parameters", "experts",
                                "experts_per_token", "norm_topk_prob",
                                "rms_norm_eps")} == {
        k: kw[k] for k in ("layers", "layer_types", "head_dim", "window",
                           "rope_parameters", "experts",
                           "experts_per_token", "norm_topk_prob",
                           "rms_norm_eps")}
    assert tkw["experts_published"] > tkw["experts"]
    # the check's sample crosses the window: two blocks of the reference's
    # queries, the second banded
    assert toy["correct_sample"]["seq_len"] == 2 * reference.QBLOCK \
        > tkw["window"]


def test_opcount_at_the_published_sizes_is_the_hand_count():
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    d, T, V = 2304, 8192, 24576
    attention = d * 4096 + 2 * d * 512 + 4096 * d
    router, expert = d * 64, 3 * d * 896
    assert (attention, router, expert) == (21_233_664, 147_456, 6_193_152)
    layer = attention + router + 16 * expert + 2 * d
    assert layer == 120_476_160
    params = 4 * layer + 2 * V * d + d
    assert opcount.params(cell.config) == params == 595_153_152
    # the band: 960.06 keys a query where the triangle has 4096.5
    band, triangle = opcount.cells(T, 1024), opcount.cells(T)
    assert band == 1024 * 1025 // 2 + 7168 * 1024 == 7_864_832
    assert round(band / T, 2) == 960.06 and triangle / T == 4096.5
    assert opcount.cells(T, 8192) == opcount.cells(T, 10 ** 6) == triangle
    forward = (4 * (2 * attention + 2 * router + (8 * 16 / 64) * 2 * expert)
               + 2 * d * V + 4 * 128 * 32 * (3 * band + triangle) / T)
    assert round(forward / 1e6) == 498
    work = opcount.step_work(cell.config, cell.traffic)
    assert work["flops"] == pytest.approx(3 * T * forward, rel=1e-12)
    assert work["bytes"] == 24 * params + 2 * T * 4
    # ISSUE 41's shares of the forward: projections 34, head 23, held
    # experts 20, full attention 13, the three windowed layers 9 (per cent)
    share = lambda part: round(100 * part / forward)
    assert (share(4 * 2 * attention), share(2 * d * V),
            share(4 * 4 * expert), share(4 * 128 * 32 * triangle / T),
            share(3 * 4 * 128 * 32 * band / T)) == (34, 23, 20, 13, 9)
    kernels = opcount.kernel_work(cell.config, cell.traffic)
    assert kernels == {"flops": 9 * 2 * 128 * 32 * (3 * band + triangle),
                       "bytes": 0, "calls": 12}
    windowed = opcount.window_kernel_work(cell.config, cell.traffic)
    assert windowed == {"flops": 9 * 2 * 128 * 32 * 3 * band, "bytes": 0,
                        "calls": 9}
    least_ms = lambda w: 1e3 * w["flops"] / device.peaks(
        "TPU v5 lite")["flops_bf16"]
    assert 8.5 < least_ms(windowed) < 9.0        # the band's floor, 3 layers
    assert 21 < least_ms(kernels) < 22


def test_the_built_network_has_the_counted_parameters():
    """595,153,152 at the published sizes, from shapes alone
    (``jax.eval_shape``: nothing is drawn or placed); no bias state."""
    import jax
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    conf = builder.build(seed=1, **cell.config["builder_kwargs"])
    assert conf.global_conf.updater.learning_rate == 1e-5
    # the embedding at unit scale, so that no batch's routing leans one way
    embed = conf.vertices["embed"]
    assert (embed.weight_init, embed.dist.mean, embed.dist.std) == (
        "distribution", 0.0, 1.0)
    stack = conf.vertices["stack"]
    assert stack.layer_types == ["window"] * 3 + ["attention"]
    assert (stack.window, stack.rope_theta, stack.experts_per_token) == (
        1024, 500000, 8)
    assert stack.rope_scaling["rope_type"] == "yarn"
    assert stack.expert_score == "softmax" and stack.shared_hidden is None
    net = ComputationGraph(conf)
    params, states = jax.eval_shape(
        lambda: (lambda n: (n.params, n.states))(net.init()))
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(params))
    assert count == opcount.params(cell.config) == 595_153_152
    stack = params["stack"]
    assert stack["r0.We_gate"].shape == (3, 16, 2304, 896)
    assert stack["r0.Wk"].shape == (3, 2304, 512)
    assert stack["r1.Wq"].shape == (1, 2304, 4096)
    assert stack["r1.Wr"].shape == (1, 2304, 64)
    assert not states.get("stack")
    toy = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True)
    assert cells.build_net(toy, seed=3).num_params() == opcount.params(
        toy.config)


@pytest.fixture(scope="module")
def rehearsal():
    """The rehearsal cell with its check's sample, built once."""
    cell = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True)
    spec = cell.config["correct_sample"]
    sample = cells.make_batches(cell.config, 8, 1, spec["examples"],
                                spec["seq_len"])[0]
    return cell, sample


def _check(cell, sample):
    """The comparison that decides ``correct``, on the check's own network."""
    net = cells.build_net(cell, seed=7)
    return correct.against_reference(net, reference, sample, "float32")


def test_the_check_passes_the_sound_program(rehearsal):
    """Seeded random weights at rehearsal size in float32: the loss and all
    gradients to 1e-4 over 2048 tokens, half of them banded."""
    ok, detail = _check(*rehearsal)
    assert ok, detail


@pytest.mark.parametrize("trace", [0, 1])
def test_run_cell_rehearsal_of_the_mellum2_cell(trace, tmp_path):
    """The cell's own files at rehearsal size through one whole run on the
    CPU, the reference check among its checks; the program's gauges are set
    where the step is built and traced."""
    from deeplearning4j_tpu.monitor import get_registry
    notes = []
    result = run.run_cell(MANIFEST, ROOT, CELL, seed=2**31 + 17, seconds=0.5,
                          trace=bool(trace), rehearse=True, note=notes.append,
                          trace_root=str(tmp_path))
    result = json.loads(json.dumps(result))
    assert result["correct"] is True, notes
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert any(n.startswith("check reference: ok") for n in notes), notes
    gauges = get_registry().snapshot()
    assert {row["labels"]["kind"]: row["value"]
            for row in gauges["hybrid_blocks"]} == {"window": 3,
                                                    "attention": 1}
    assert {row["value"] for row in gauges["attention_window"]} == {1024}
    assert {row["labels"]["which"]: row["value"]
            for row in gauges["moe_experts"]} == {"held": 16, "published": 32}
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    if trace:
        allowed = {m["name"] for m in cell.metrics["per_layer"]}
        assert {"remat_ms_per_step", "flash_kernels_roofline",
                "pallas_ms_per_step", "mfu", "train_step_roofline",
                "scoped_device_time_share"} <= allowed
        assert not {"ssm_ms_per_step", "ssd_roofline"} & allowed
        assert result["metrics"]["window_compiles"]["value"] == 0
        assert not set(NEW_METRICS) & allowed
        kimi = cells.load_cell(MANIFEST, ROOT,
                               "kimi_linear_l5_e8_b1_t8192_resident")
        assert allowed == {m["name"] for m in kimi.metrics["per_layer"]}
    else:
        allowed = {m["name"] for m in cell.metrics["end_to_end"]}
        assert set(result["metrics"]) == allowed == {"throughput_per_chip",
                                                     "setup_s"}
    assert set(result["metrics"]) <= allowed


# ------------------------------------------------ the control and the faults
def _without_window(mha):
    def full(*args, window=None, **kw):
        return mha(*args, **kw)
    return full


def _plain_rotation(rope):
    def plain(x, theta, start=0, scaling=None):
        return rope(x, theta, start)
    return plain


def _no_attention_factor(yarn):
    def unscaled(*args, **kw):
        return yarn(*args, **kw)[0], 1.0
    return unscaled


def _renormalised_over_held(impl):
    """``RoutedExpertsImpl.route`` with the chosen weights normalised over
    the held choices only, as a layer that knew of no other chip would."""
    import jax.numpy as jnp
    sound = impl.route

    def route(self, x, w_router, bias):
        chosen, weights = sound(self, x, w_router, bias)
        held = jnp.isin(chosen, jnp.asarray(self.held))
        kept = jnp.where(held, weights, 0.0)
        total = jnp.sum(weights, axis=-1, keepdims=True)
        return chosen, jnp.where(
            held, weights * total / (jnp.sum(kept, -1, keepdims=True)
                                     + 1e-20), weights)
    return route


FAULTS = ["window_not_applied", "yarn_replaced_by_default_rotary",
          "attention_factor_dropped", "renormalised_over_held"]


def plant(monkeypatch, fault):
    from deeplearning4j_tpu.nn.layers import attention, moe
    if fault == "window_not_applied":
        monkeypatch.setattr(attention, "mha", _without_window(attention.mha))
    elif fault == "yarn_replaced_by_default_rotary":
        monkeypatch.setattr(attention, "rope",
                            _plain_rotation(attention.rope))
    elif fault == "attention_factor_dropped":
        monkeypatch.setattr(attention, "yarn",
                            _no_attention_factor(attention.yarn))
    else:
        monkeypatch.setattr(moe.RoutedExpertsImpl, "route",
                            _renormalised_over_held(moe.RoutedExpertsImpl))


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(fault, monkeypatch, rehearsal):
    """The faults ISSUE 41 names, each planted in the program and held to
    the comparison that decides ``correct`` (``reference/mellum2_12b_a2_5b.py``
    has their readings on the chip). The sample's 2048 tokens pass the
    window by 1024 and YaRN's ramp by far."""
    plant(monkeypatch, fault)
    ok, detail = _check(*rehearsal)
    assert not ok, detail
    assert "gradients rel L2" in detail


def fp8_operands_reference():
    """The control the chip's limits stand under: the plain reference in the
    program's place with the operands of every product (the projections,
    the experts' gemms, attention's two, the router's, the head's) rounded
    to float8's three mantissa bits (e4m3; the exponent left alone, as a
    scaled cast would), the precision below the bfloat16 the configuration
    states. Forward operands only: the cotangents pass unrounded
    (``test_benchmark_kimi_linear.py`` has the same)."""
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


def test_fp8_operands_where_the_configuration_says_bf16_are_not_correct(
        rehearsal):
    """The control at rehearsal size fails the float32 limit and the chip's
    limit on all gradients."""
    import jax
    cell, sample = rehearsal
    net = cells.build_net(cell, seed=7)
    args = (net.params, sample.features, sample.labels)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(jax.grad(reference.loss))(*args)
        control = jax.jit(jax.grad(fp8_operands_reference().loss))(*args)
    distance = correct.grad_distance(
        jax.tree_util.tree_map(np.asarray, control), ref)[0]
    assert distance > reference.TOLERANCE["bfloat16"]["grads"]
    assert distance > 100 * reference.TOLERANCE["float32"]["grads"]


def test_the_chips_limits_name_leaves_the_network_has(rehearsal):
    import jax
    cell, _ = rehearsal
    net = cells.build_net(cell, seed=7)
    paths = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(net.params)[0]}
    assert set(reference.TOLERANCE["bfloat16"].get("leaves", {})) <= paths


# ------------------------------------------------------------ the readers
def _trace(*ops):
    """A device trace of one chip holding ``ops``: (name, ms, pallas?)."""
    events, t = [], 0.0
    for name, ms, pallas in ops:
        text = (f"%{name} = bf16[32,8192,128]{{2,1,0}} custom-call(%p), "
                f'custom_call_target="tpu_custom_call"' if pallas
                else f"%{name} = f32[8] fusion(%p), kind=kLoop")
        events.append(xplane.event(text, t, t + ms * 1e6))
        t += ms * 1e6
    return xplane.Trace([xplane.Device(0, events, [], [])], [])


def _run(trace, steps=2):
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    return types.SimpleNamespace(
        trace=trace, trace_window=types.SimpleNamespace(steps=steps),
        cell=cell, extras={}, devices=None,
        peaks=device.peaks("TPU v5 lite"), opcount=opcount)


def test_the_banded_kernels_roofline_reads_only_calls_with_a_window():
    from benchmark.layer_metrics import (pallas_ms_per_step,
                                         window_flash_roofline)
    trace = _trace(("flash_fwd_q1024_k1024_w1024.3", 4.0, True),
                   ("flash_dq_q1024_k1024_w1024.4", 5.0, True),
                   ("flash_dkv_q1024_k1024_w1024.5", 7.0, True),
                   ("flash_fwd_q1024_k1024.6", 6.0, True),
                   ("fusion.7", 30.0, False))
    run_ = _run(trace)
    assert window_flash_roofline.seconds_per_step(run_) == pytest.approx(
        8e-3)
    least = 1e3 * opcount.window_kernel_work(
        run_.cell.config, run_.cell.traffic)["flops"] / 197e12
    assert window_flash_roofline.read(run_) == pytest.approx(
        100 * least / 8.0)
    # every Mosaic call, windowed or not, is pallas_ms_per_step's
    assert pallas_ms_per_step.read(run_) == pytest.approx(11.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_new_readers_say_nothing_where_there_is_nothing(metric,
                                                            monkeypatch):
    """On a run without a trace, on a trace of causal calls only (the
    parent's program, any other cell's), on a program without the scope,
    and under an opcount without the band's work, a reader returns None and
    does not raise."""
    from benchmark import op_scopes
    reader = cells.module("layer_metrics", metric)
    assert reader.read(_run(None)) is None
    causal = _run(_trace(("flash_fwd_q1024_k1024.6", 6.0, True)))
    monkeypatch.setattr(op_scopes, "_tokens_seconds", lambda run: [
        (frozenset({"jit", "step", "blocks", "attn"}), 0.3)])
    assert reader.read(causal) is None
    banded = _run(_trace(("flash_fwd_q1024_k1024_w1024.3", 4.0, True)))
    banded.opcount = cells.module("opcount", "kimi_linear_48b_a3b")
    if metric == "window_flash_roofline":
        assert reader.read(banded) is None


def test_swa_ms_per_step_sums_its_scope(monkeypatch):
    from benchmark import op_scopes
    from benchmark.layer_metrics import swa_ms_per_step
    scopes = lambda *names: frozenset({"jit", "step", "blocks", *names})
    monkeypatch.setattr(op_scopes, "_tokens_seconds", lambda run: [
        (scopes("swa"), 0.06), (scopes("attn"), 0.02),
        (scopes("moe", "dispatch"), 0.5)])
    assert swa_ms_per_step.read(_run(object(), steps=3)) == pytest.approx(
        20.0)

"""The ``kimi_linear_48b_a3b`` configuration and its cell at no chip time: the
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

from benchmark import cells, correct, device, run
from benchmark.builders import kimi_linear_48b_a3b as builder
from benchmark.opcount import kimi_linear_48b_a3b as opcount
from benchmark.reference import kimi_linear_48b_a3b as reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = cells.load_manifest(ROOT)
CELL = "kimi_linear_l5_e8_b1_t8192_resident"
#: readers this configuration brings; ``BENCHMARK.json`` names none of them
#: yet (``test_benchmark_setup_spans`` pins its ``per_layer`` at 46 entries
#: and is the benchmark's to change: PERF.md section 7)
NEW_METRICS = ["kda_ms_per_step", "kda_rule_ms_per_step", "kda_rule_roofline",
               "mla_ms_per_step", "moe_ms_per_step"]
KDA_LAYERS = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23,
              25, 26]
#: every key of ``moonshotai/Kimi-Linear-48B-A3B-Instruct``'s config.json, as
#: the catalog beside the ``model-configs`` guide has it
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": KDA_LAYERS, "num_heads": 32,
        "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
CUT = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}


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
    # the floors of the model-configs guide, met exactly
    assert CUT["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CUT["num_experts"] == 8 and CUT["num_hidden_layers"] == 1 + 4
    linear = config["linear_attn_config"]
    kw = config["builder_kwargs"]     # what is built is what is published
    assert kw == {
        "vocab": config["vocab_size"], "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "kda_layers": linear["kda_layers"],
        "first_k_dense": config["first_k_dense_replace"],
        "intermediate": config["intermediate_size"],
        "heads": config["num_attention_heads"],
        "kda_heads": linear["num_heads"], "kda_head_dim": linear["head_dim"],
        "kda_conv": linear["short_conv_kernel_size"],
        "kda_chunk": 64,                                  # assumed
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_head_dim": config["qk_nope_head_dim"],
        "qk_rope_head_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "experts": config["num_experts"],
        "experts_published": config["published"]["num_experts"],
        "experts_per_token": config["num_experts_per_token"],
        "moe_intermediate": config["moe_intermediate_size"],
        "shared_experts": config["num_shared_experts"],
        "routed_scaling_factor": config["routed_scaling_factor"],
        "rms_norm_eps": config["rms_norm_eps"]}
    # layers 1-5 of the published order: a dense first layer, one whole
    # period of three KDA to one latent attention among the four that follow
    assert builder.layer_kinds(kw["layers"], kw["kda_layers"],
                               kw["first_k_dense"]) == (
        ["kda", "kda", "kda", "mla", "kda"],
        ["dense", "experts", "experts", "experts", "experts"])
    assert sorted(linear["kda_layers"] + linear["full_attn_layers"]) == list(
        range(1, PUBLISHED["num_hidden_layers"] + 1))
    assert config["features"]["vocab"] == config["vocab_size"]
    assert set(config["assumed"]) >= {"init", "optimizer", "data",
                                      "precision", "loss_reduction",
                                      "kda_chunk", "score_correction_bias",
                                      "expert_walk"}
    sample = config["correct_sample"]
    assert sample["seq_len"] == cell.seq_len == 8192
    assert sample["examples"] == 1
    assert sample["why"] and sample["holds"] and sample["does_not_hold"]
    # the reference's defaults are the file's values
    defaults = dict(zip(("experts_per_token", "routed_scaling_factor", "eps"),
                        reference.loss.__defaults__))
    assert defaults == {"experts_per_token": kw["experts_per_token"],
                        "routed_scaling_factor": kw["routed_scaling_factor"],
                        "eps": kw["rms_norm_eps"]}
    # and the rehearsal keeps them: only widths and rows are toys, the five
    # layers, the 8 held experts and the 8 chosen a token stay
    toy = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True).config
    tkw = toy["builder_kwargs"]
    assert {k: tkw[k] for k in defaults if k in tkw} == {
        k: kw[k] for k in defaults if k in kw}
    assert (tkw["layers"], tkw["experts"], tkw["experts_per_token"],
            tkw["kda_conv"]) == (5, 8, 8, 4)
    assert tkw["experts_published"] > tkw["experts"]


def test_opcount_at_the_published_sizes_is_the_hand_count():
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    d = 2304
    kda = (3 * d * 4096 + 4096 * d + 3 * 4096 * 4
           + 2 * (d * 128 + 128 * 4096) + d * 32 + 32 + 4096 + 128)
    mla = d * 6144 + d * 576 + 512 + 512 * 8192 + 4096 * d
    dense, expert = 3 * d * 9216, 3 * d * 1024
    experts = d * 256 + expert + 8 * expert
    assert (kda, mla, dense, expert, experts) == (
        39_514_272, 29_114_880, 63_700_992, 7_077_888, 64_290_816)
    params = ((kda + dense + 2 * d) + 3 * (kda + experts + 2 * d)
              + (mla + experts + 2 * d) + 2 * 20480 * d + d)
    assert opcount.params(cell.config) == params == 602_433_408
    tokens = 8192
    kda_forward = 2 * (kda - 3 * 4096 * 4 - 32 - 4096 - 128) + 7 * 32 * 128 * 128
    mla_forward = 2 * (mla - 512) + tokens * 32 * (192 + 128)
    experts_forward = 2 * d * 256 + 2 * expert + (8 * 8 / 256) * 2 * expert
    forward = (4 * kda_forward + mla_forward + 2 * dense
               + 4 * experts_forward + 2 * d * 20480)
    assert round(forward / 1e6) == 770
    work = opcount.step_work(cell.config, cell.traffic)
    assert work["flops"] == 3 * tokens * forward == 18_917_451_890_688
    assert work["bytes"] == 24 * params + 2 * tokens * 4
    # the shares ISSUE 39 sized the cell by: the new mechanisms are 71 %
    share = lambda part: round(100 * part / forward)
    assert (share(4 * kda_forward), share(mla_forward), share(2 * dense),
            share(2 * d * 20480), share(4 * experts_forward)) == (
        43, 18, 17, 12, 10)
    kernels = opcount.kernel_work(cell.config, cell.traffic)
    # a causal [T, T, D] product is half of 2 T T D: forward the scores at
    # 192 and the values at 128, backward four at 192 and three at 128
    assert kernels == {
        "flops": (192 + 128 + 4 * 192 + 3 * 128) * 8192 * 8192 * 32,
        "bytes": 0, "calls": 3}
    rule = opcount.kda_work(cell.config, cell.traffic)
    assert rule == {"flops": 3 * tokens * 4 * 7 * 32 * 128 * 128, "bytes": 0}
    # the least times lie under what the chip has done (my chip runs, PR 39)
    peaks = device.peaks("TPU v5 lite")
    least_ms = lambda w: 1e3 * w["flops"] / peaks["flops_bf16"]
    assert least_ms(work) < STEP_MS
    assert least_ms(rule) < KDA_RULE_MS
    assert least_ms(kernels) < PALLAS_MS


#: device milliseconds per step of the step program, of the ops under
#: ``kda_rule`` and of the flash kernels (my chip runs, PR 39, traced)
STEP_MS, KDA_RULE_MS, PALLAS_MS = 651.21, 252.27, 24.67


def test_the_built_network_has_the_counted_parameters():
    """602,433,408 at the published sizes, from shapes alone
    (``jax.eval_shape``: nothing is drawn or placed)."""
    import jax
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    conf = builder.build(seed=1, **cell.config["builder_kwargs"])
    # not the zoo's 1e-3, under which the routers collapse in three steps
    assert conf.global_conf.updater.learning_rate == 1e-5
    net = ComputationGraph(conf)
    params, states = jax.eval_shape(
        lambda: (lambda n: (n.params, n.states))(net.init()))
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(params))
    assert count == opcount.params(cell.config) == 602_433_408
    stack = params["stack"]
    assert stack["r1.We_gate"].shape == (2, 8, 2304, 1024)
    assert stack["r2.Wkv_b"].shape == (1, 512, 32 * 256)
    assert stack["r0.W_fb"].shape == (1, 128, 4096)
    # the score-correction bias is state, over the published experts
    assert {k: v.shape for k, v in states["stack"].items()} == {
        "r1.b": (2, 256), "r2.b": (1, 256), "r3.b": (1, 256)}
    toy = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True)
    assert cells.build_net(toy, seed=3).num_params() == opcount.params(
        toy.config)


@pytest.fixture(scope="module")
def rehearsal():
    """The rehearsal cell with its network and sample, built once."""
    cell = cells.load_cell(MANIFEST, ROOT, CELL, rehearse=True)
    spec = cell.config["correct_sample"]
    sample = cells.make_batches(cell.config, 8, 1, spec["examples"],
                                spec["seq_len"])[0]
    return cell, sample


def _check(cell, sample):
    """The comparison that decides ``correct``, on the check's own network."""
    net = cells.build_net(cell, seed=7)
    return correct.against_reference(net, reference, sample, "float32")


def test_the_system_agrees_with_the_reference_in_float32(rehearsal):
    """Seeded random weights at rehearsal size: the loss and every gradient
    leaf to 1e-4, a sample that is no multiple of the chunk."""
    import jax
    cell, sample = rehearsal
    net = cells.build_net(cell, seed=7)
    assert sample.features.shape[1] % cell.config["builder_kwargs"][
        "kda_chunk"]
    grads, loss = net.compute_gradient_and_score(sample)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref = jax.jit(jax.value_and_grad(reference.loss))(
            net.params, sample.features, sample.labels)
    assert loss == pytest.approx(float(ref_loss), rel=1e-5)
    distance, worst, own = correct.grad_distance(
        jax.tree_util.tree_map(np.asarray, grads), ref)
    assert distance < 1e-4 and worst[0] < 1e-4, worst
    assert len(own) == 85 and max(own.values()) < 1e-4
    assert {"['embed']['W']", "['out']['W']", "['stack']['r0.A_log']",
            "['stack']['r1.We_down']", "['stack']['r1.Wr']",
            "['stack']['r2.Wkv_b']", "['stack']['r2.gc']"} <= set(own)


@pytest.mark.parametrize("trace", [0, 1])
def test_run_cell_rehearsal_of_the_kimi_linear_cell(trace, tmp_path):
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
            for row in gauges["hybrid_blocks"]} == {"kda": 4, "mla": 1}
    # the last trace is the check's: 40 tokens in chunks of 32
    assert {row["value"] for row in gauges["kda_chunks"]} == {2}
    assert {row["labels"]["which"]: row["value"]
            for row in gauges["moe_experts"]} == {"held": 8, "published": 32}
    # its 2 x 40 tokens' 8 choices and 8 groups' padding to tiles of 128
    # rows; the walk stands at twice the 160 rows uniform routing sends the
    # eight held experts (3 tiles) and a tile each
    assert {row["value"] for row in gauges["moe_rows_sized"]} == {1664}
    assert {row["value"] for row in gauges["moe_rows_standing"]} == {1408}
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    if trace:
        allowed = {m["name"] for m in cell.metrics["per_layer"]}
        assert {
            "remat_ms_per_step", "flash_kernels_roofline",
            "pallas_ms_per_step", "mfu", "train_step_roofline",
            "scoped_device_time_share"} <= allowed
        assert not {"ssm_ms_per_step", "ssd_roofline"} & allowed
        assert result["metrics"]["window_compiles"]["value"] == 0
        # the new readers wait for their entries (NEW_METRICS)
        assert not set(NEW_METRICS) & allowed
    else:
        allowed = {m["name"] for m in cell.metrics["end_to_end"]}
        assert set(result["metrics"]) == allowed == {"throughput_per_chip",
                                                     "setup_s"}
    assert set(result["metrics"]) <= allowed


# ------------------------------------------------ the control and the faults
def forgetful_carried_states(carried):
    """``kda.carried_states`` with every chunk started from nought."""
    import jax.numpy as jnp

    def forgetful(S, *chunks):
        out = [carried(jnp.zeros_like(S), *(c[i:i + 1] for c in chunks[:-1]),
                       chunks[-1]) for i in range(chunks[0].shape[0])]
        return out[-1][0], tuple(jnp.concatenate([o[1][j] for o in out])
                                 for j in range(2))
    return forgetful


def decay_per_head(rule):
    """``kda.delta_rule_chunked`` handed one decay a head: the mean of its
    key channels' (a gated delta rule of the earlier kind)."""
    import jax.numpy as jnp

    def per_head(q, k, v, g, beta, chunk, compute_dtype):
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        return rule(q, k, v, g, beta, chunk, compute_dtype)
    return per_head


def dropped_expert_rows(tables):
    """``moe.routing_tables`` with the rows of the first held expert given
    no weight: its tokens' choice of it is dropped."""
    import jax.numpy as jnp

    def dropping(local, weights, held, tile):
        row_token, row_weight, tile_expert, tiles = tables(local, weights,
                                                           held, tile)
        of_first = jnp.repeat(tile_expert == 0, tile)
        return (row_token, jnp.where(of_first, 0, row_weight), tile_expert,
                tiles)
    return dropping


def renormalised_over_held(impl):
    """``RoutedExpertsImpl.route`` with the chosen scores normalised over the
    held choices only, as a layer that knew of no other chip would."""
    import jax
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


FAULTS = ["state_not_carried", "decay_per_head", "expert_rows_dropped",
          "renormalised_over_held"]


def plant(monkeypatch, fault):
    from deeplearning4j_tpu.nn.layers import kda, moe
    if fault == "state_not_carried":
        monkeypatch.setattr(kda, "carried_states",
                            forgetful_carried_states(kda.carried_states))
    elif fault == "decay_per_head":
        monkeypatch.setattr(kda, "delta_rule_chunked",
                            decay_per_head(kda.delta_rule_chunked))
    elif fault == "expert_rows_dropped":
        monkeypatch.setattr(moe, "routing_tables",
                            dropped_expert_rows(moe.routing_tables))
    else:
        monkeypatch.setattr(moe.RoutedExpertsImpl, "route",
                            renormalised_over_held(moe.RoutedExpertsImpl))


def test_the_check_passes_the_sound_program(rehearsal):
    ok, detail = _check(*rehearsal)
    assert ok, detail


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(fault, monkeypatch, rehearsal):
    """The faults ISSUE 39 names, each planted in the program and held to
    the comparison that decides ``correct``
    (``reference/kimi_linear_48b_a3b.py`` has their readings on the chip).
    The sample's 40 tokens are two chunks of 32, so one state crosses."""
    plant(monkeypatch, fault)
    ok, detail = _check(*rehearsal)
    assert not ok, detail
    assert "gradients rel L2" in detail


def test_a_fault_fails_the_whole_rehearsal_by_the_reference_alone(
        monkeypatch, tmp_path):
    """One of them through the cell's whole run: it trains, its state is
    finite, and only the reference check tells."""
    plant(monkeypatch, "decay_per_head")
    notes = []
    result = run.run_cell(MANIFEST, ROOT, CELL, seed=2**31 + 11, seconds=0.2,
                          trace=False, rehearse=True, note=notes.append,
                          trace_root=str(tmp_path))
    failed = [n for n in notes if n.startswith("check ") and "FAILED" in n]
    assert result["correct"] is False
    assert len(failed) == 1 and failed[0].startswith(
        "check reference: FAILED"), notes
    assert result["failed"] == 0 and result["attempted"] > 0


def fp8_operands_reference():
    """The control that the gradients' limit on the chip stands under: the
    plain reference put in the program's place with the operands of every
    product (the projections, the MLPs' and experts' gemms, attention's two,
    the head's) rounded to float8's three mantissa bits (e4m3; the exponent
    left alone, as a scaled cast would), the precision below the bfloat16 the
    configuration states. The recurrence, the convolutions, the norms and
    the router's comparison of scores are elementwise and stay float32, as
    they do in the program. Forward operands only: the cotangents pass
    unrounded. (``test_benchmark_granite.py`` has the hybrid LM's.)"""
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
    """The control at rehearsal size: the reference with float8 operands in
    the program's place fails the gradient comparison by the float32 limit
    and by the chip's limit on all gradients."""
    import jax
    cell, sample = rehearsal
    net = cells.build_net(cell, seed=7)
    args = (net.params, sample.features, sample.labels)
    ref = jax.grad(reference.loss)(*args)
    control = jax.grad(fp8_operands_reference().loss)(*args)
    again = correct.grad_distance(jax.tree_util.tree_map(np.asarray, ref),
                                  ref)[0]
    distance, _, own = correct.grad_distance(
        jax.tree_util.tree_map(np.asarray, control), ref)
    assert again == 0.0
    # 1.38 and 1.40 at these toy widths, where float8 scores route many
    # tokens otherwise; 0.41 and 0.64 on the chip at the cell's
    # (``reference/kimi_linear_48b_a3b.py`` has the readings)
    chip = reference.TOLERANCE["bfloat16"]
    assert distance > chip["grads"] == 0.15
    assert all(own[path] > limit for path, limit in chip["leaves"].items())
    assert distance > 100 * reference.TOLERANCE["float32"]["grads"]


def test_the_chips_limits_name_leaves_the_network_has(rehearsal):
    import jax
    cell, _ = rehearsal
    net = cells.build_net(cell, seed=7)
    paths = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(net.params)[0]}
    assert set(reference.TOLERANCE["bfloat16"].get("leaves", {})) <= paths


# ------------------------------------------------------------ the readers
def _run(trace, config, steps=3):
    return types.SimpleNamespace(
        trace=trace, trace_window=types.SimpleNamespace(steps=steps),
        cell=types.SimpleNamespace(config=config, traffic={}), extras={},
        devices=None, peaks=None, opcount=None)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_new_readers_say_nothing_where_there_is_nothing(metric,
                                                            monkeypatch):
    """On a run without a trace, and on a program without the scopes (the
    parent's, any other cell's: every scope sum is nought), a reader returns
    None and does not raise."""
    from benchmark import op_scopes
    reader = cells.module("layer_metrics", metric)
    config = cells.load_cell(MANIFEST, ROOT, CELL).config
    assert reader.read(_run(None, config)) is None
    there = _run(object(), config)
    there.peaks = device.peaks("TPU v5 lite")
    there.opcount = cells.module("opcount", "granite_4_0_h_micro")
    monkeypatch.setattr(op_scopes, "_tokens_seconds", lambda run: [
        (frozenset({"jit", "step", "blocks", "ssm", "ssd"}), 0.3)])
    assert reader.read(there) is None


def test_the_readers_sum_their_scopes_and_divide_by_the_needed_work(
        monkeypatch):
    from benchmark import op_scopes
    from benchmark.layer_metrics import (kda_ms_per_step, kda_rule_ms_per_step,
                                         kda_rule_roofline, mla_ms_per_step,
                                         moe_ms_per_step)
    cell = cells.load_cell(MANIFEST, ROOT, CELL)
    scopes = lambda *names: frozenset({"jit", "step", "blocks", *names})
    monkeypatch.setattr(op_scopes, "_tokens_seconds", lambda run: [
        (scopes("kda"), 0.30), (scopes("kda", "kda_rule"), 0.45),
        (scopes("mla"), 0.06), (scopes("moe", "router"), 0.003),
        (scopes("moe", "dispatch"), 0.006), (scopes("moe", "experts"), 0.03),
        (scopes("moe", "shared"), 0.015), (scopes("ffn"), 0.09)])
    run_ = types.SimpleNamespace(
        cell=cell, opcount=opcount, peaks=device.peaks("TPU v5 lite"),
        trace_window=types.SimpleNamespace(steps=3))
    assert kda_ms_per_step.read(run_) == pytest.approx(250.0)
    assert kda_rule_ms_per_step.read(run_) == pytest.approx(150.0)
    assert mla_ms_per_step.read(run_) == pytest.approx(20.0)
    assert moe_ms_per_step.read(run_) == pytest.approx(18.0)
    rule_ms = 1e3 * 3 * 8192 * 4 * 7 * 32 * 128 * 128 / 197e12
    assert kda_rule_roofline.read(run_) == pytest.approx(
        100 * rule_ms / 150.0)
    assert 1.8 < rule_ms < 1.9
    run_.opcount = cells.module("opcount", "ouro_2_6b")   # no such work
    assert kda_rule_roofline.read(run_) is None

"""The train steps of the benchmark's configurations, lowered at ``rehearse``
size on the CPU, against the text they had before the hybrid language model
came (PR 33): new options of shared layers (``num_kv_heads``,
``attention_scale``, the embedding's ``scale``, the output layer's ``tied_to``
and ``logits_divisor``) emit nothing where they are left at their defaults,
and the tie's lookup in ``ComputationGraph`` adds no op (PR 30's method:
StableHLO without locations, so a moved line does not count). The block
stacks' checkpoint policy (PR 34) emits nothing either where no flash kernel
runs, as here, off the TPU. The state-space mixer's differentiation rule
and the barrier before its out-projection (PR 35, ``mamba.split_conv_silu``,
``Mamba2Impl.forward``) are the hybrid model's alone: its step's text moved
with them, the other three stayed. The looped stack as one scan over its
block applications (PR 36, ``looped.LoopedBlockStackImpl.forward``) is the
looped model's alone: its step's text moved, the other three stayed. The
looped block's name for what its last norm reads (PR 38,
``looped.LoopedBlockStackImpl.block``, kept by the one policy of
``base.block_checkpoint``) is the looped model's alone too: its step's text
moved (one more stack kept forward, one product fewer backward), and the
hybrid model's, which shares the policy and names nothing, stayed with the
other two. The delta rule's in-chunk matrices kept by name across its
segments' checkpoint and the blocks' (PR 40, ``kda.delta_rule_segment``, one
more name in the same policy) are the linear-attention expert model's alone:
its step's text moved (17063 lines before, 16090 since: the segments'
recompute lost the weights and the inverse's substitution; it had no pin,
and has one since), the other four, of which the looped and the hybrid model share the
policy and give nothing that name, stayed. The sliding window, the rotary scaling, the softmax score and the
window mixer (PR 41: ``flash_attention.causal_pairs(window=)``,
``attention.rope(scaling=)``, ``RoutedExpertsLayer.score``, the ``window``
kind of ``HybridBlockStack``) emit nothing at their defaults: the five steps
above kept their text, and the sliding-window expert model's step has a pin
of its own. The routed experts' walk written in row order and each token's
rows added by one gather a slot (``moe.grouped_ffn``,
``moe.token_rows``) moved both expert models' steps, the only ones with
routed experts: at this size both layers combine (the kimi layer at its
full size has too many slots for its walk and keeps the scatter-add, and
its full-size step lowers to the parent's text), and the four steps above
kept their text. The delta rule's diagonal blocks formed in two bands of
rows forward and in one pass over the mirrored weights backward
(``kda.within_block`` and its rule) moved the linear-attention expert
model's step alone (17249 lines before): no other cell runs that mixer,
and the five other steps kept their text.

A PR that means to change one of these steps replaces its line count and
digest here, and says so; one that does not and fails here has changed a
program it did not mean to touch."""
import hashlib
import os

import pytest

import jax
import jax.numpy as jnp

from benchmark import cells

ROOT = os.path.dirname(cells.HERE)
#: cell -> (lines, sha256) of its step's lowered text at commit 16227cc
#: (the hybrid language model's as PR 35 left it: 5237 lines before; the
#: looped language model's as PR 38 left it: 2116 lines before PR 36, 2293
#: before PR 38)
PARENT = {
    "resnet50_b256_resident": (
        11415,
        "171fe2c5c06046af35f903336e551e011f434ff78c331ad0b902e7300d8c7cf6"),
    "charrnn_b64_t5000_tbptt50_pool20": (
        1258,
        "8b7af6b349c565dcdea9798a6d74f1a5014e6e683580b4439915763419a4bd74"),
    "ouro_l4_ut4_b2_t4096_resident": (
        2305,
        "e0b355cda69a34005275dbcef5ed5178f6f82a1d429e8387e8db20c69ecf5e47"),
    "granite_l10_b1_t8192_resident": (
        5376,
        "5aa7ce691b442dfccae6a6db713b75bc64d85db2ff8550cf0ee78ade81ebff43"),
}


#: the two expert language models' steps with the experts' walk in row
#: order (16090 and 4443 lines before), the linear-attention one's with its
#: diagonal blocks formed in bands (17249 lines before)
PINNED = dict(PARENT, kimi_linear_l5_e8_b1_t8192_resident=(
    17596,
    "6c410e25d686bf1a39a8089b89c3d3979b76001f30b6b685198ec9adc5a66afd"),
    mellum2_l4_e16_b1_t8192_resident=(
    5244,
    "3cf29bb1873483d3efe91ebb05ad48d6bc08fc7576419bb358f81444a27251d7"))


def lowered_step(workload, seed=5):
    """The text of the cell's raw train step (one truncated-BPTT segment
    with its carried state where the cell has segments) on its own seeded
    network and batch."""
    manifest = cells.load_manifest(ROOT)
    cell = cells.load_cell(manifest, ROOT, workload, rehearse=True)
    net = cells.build_net(cell, seed)
    ds = cells.make_batches(cell.config, seed, 1, cell.batch, cell.seq_len)[0]
    f, l, fm, lm = net._batch_streams(ds)
    segments = cell.iterations_per_step > 1
    args = [net.params, net.states, net.updater_state, jnp.int32(0),
            net._next_rng(), f, l, fm, lm]
    if segments:
        k = int(cell.config["builder_kwargs"]["tbptt"])
        args[5:7] = [f[:, :k], l[:, :k]]
        args.append(net._init_rnn_state(int(f.shape[0])))
    return jax.jit(net._raw_step(segments)).lower(*args).as_text()


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_the_step_is_the_parents_text(workload):
    text = lowered_step(workload)
    lines, digest = PINNED[workload]
    assert len(text.splitlines()) == lines
    assert hashlib.sha256(text.encode()).hexdigest() == digest

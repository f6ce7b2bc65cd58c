"""The train steps of the benchmark's configurations, lowered at ``rehearse``
size on the CPU, against the text each had when a change last moved it on
purpose: a line count and a digest of the StableHLO, printed without
locations so that a moved source line does not count. ``PARENT`` holds the
steps as they were before the hybrid language model came, ``PINNED`` adds
the steps moved since and the configurations that came later.

Why it holds: options of shared layers left at their defaults must emit
nothing, and a mixer, a layer kind or a name in the block checkpoint's
policy that one model alone uses must leave every other model's step as it
was. Off the TPU no flash kernel runs, so what a policy keeps for the
kernels emits nothing here. Which change moved which step, and by how many
lines, is in ``CHANGES.md``.

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

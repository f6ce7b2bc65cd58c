"""Device milliseconds of one application of a block of a looped stack,
forward, backward and the recomputed forward together:
``loop_blocks_ms_per_step`` over the block applications per step (passes x
blocks), which the program says in its registry gauge
``looped_block_applications`` when it builds the step. Comparable between
depths and numbers of passes where the time per step is not. Nothing where
the program has no such scope or no such gauge."""
from benchmark.layer_metrics import loop_blocks_ms_per_step


def read(run):
    from deeplearning4j_tpu.monitor import get_registry

    ms = loop_blocks_ms_per_step.read(run)
    applications = sum(row["value"] for row in get_registry().snapshot().get(
        "looped_block_applications", []))
    return ms / applications if ms and applications else None

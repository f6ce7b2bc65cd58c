"""Device milliseconds of one state-space block's mixer, forward, backward
and the recomputed forward together: ``ssm_ms_per_step`` over the state-space
blocks of the network's hybrid stacks, which the program says in its registry
gauge ``hybrid_blocks{kind="mamba"}`` when it builds the step. Comparable
between depths where the time per step is not. Nothing where the program has
no such scope or no such gauge."""
from benchmark.layer_metrics import ssm_ms_per_step


def read(run):
    from deeplearning4j_tpu.monitor import get_registry

    ms = ssm_ms_per_step.read(run)
    blocks = sum(row["value"] for row in get_registry().snapshot().get(
        "hybrid_blocks", []) if row["labels"].get("kind") == "mamba")
    return ms / blocks if ms and blocks else None

"""The delta rule's share of its roofline in per cent: the least time the
chip could take for what the recurrences of one step need (operations over
peak FLOP/s; ``opcount/<config>.py: kda_work`` counts the recurrence itself,
7·H·K·K a token and layer forward, and no bytes, its operands being values
inside the program) over the device time per step under the ``kda_rule``
scope (``kda_rule_ms_per_step``). It reads by scope and not by kind of op, so
it measures the same work whether XLA fusions or a later kernel do it.

Its ceiling is far under 100, as ``ssd_roofline``'s is: the chunked form
spends several times the recurrence's operations in its products, the decays
are exponentials on the vector unit, the triangular solve is a loop over a
chunk's rows, and the time holds two recomputed forwards (the block's and the
segment's checkpoint). Nothing where the program has no such scope or the
configuration's opcount no ``kda_work``."""
from benchmark.layer_metrics import kda_rule_ms_per_step


def read(run):
    ms = kda_rule_ms_per_step.read(run)
    if not ms or run.peaks is None or not hasattr(run.opcount, "kda_work"):
        return None
    work = run.opcount.kda_work(run.cell.config, run.cell.traffic)
    least = max(work["flops"] / run.peaks["flops_bf16"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)

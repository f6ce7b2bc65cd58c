"""The state-space scan's share of its roofline in per cent: the least time
the chip could take for what the recurrences of one step need (operations
over peak FLOP/s; ``opcount/<config>.py: ssd_work`` counts the recurrence
itself, 4·H·P·N a token and layer forward, and no bytes, its operands being
values inside the program) over the device time per step under the ``ssd``
scope (``ssd_ms_per_step``). It reads by scope and not by kind of op, so it
measures the same work whether XLA fusions or a later kernel do it.

Its ceiling is far under 100. The needed operations are those of a
multiply-add recurrence the MXU cannot run as such; the chunked form that
can spends about twice as many in its products (scores, masked-decay
weights, chunk states, read-out), the decays are exponentials and masks on
the vector unit over a [chunks, heads, chunk, chunk] tensor, and the time
holds the recomputed forward, none of which the needed operations count.
Nothing where the program has no such scope or the configuration's opcount
no ``ssd_work``."""
from benchmark.layer_metrics import ssd_ms_per_step


def read(run):
    ms = ssd_ms_per_step.read(run)
    if not ms or run.peaks is None or not hasattr(run.opcount, "ssd_work"):
        return None
    work = run.opcount.ssd_work(run.cell.config, run.cell.traffic)
    least = max(work["flops"] / run.peaks["flops_bf16"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)

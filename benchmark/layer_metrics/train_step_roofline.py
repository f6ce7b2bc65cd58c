"""The step program's share of its roofline in per cent: the least time the
chip could take for what a step needs (the larger of needed operations over
peak FLOP/s and needed bytes over peak bytes/s, ``opcount/<config>.py``)
over the time the device spent in the step program (``device_step_ms``).
Needed bytes are those no program could keep out of HBM, so the share cannot
pass 100."""
from benchmark.layer_metrics import device_step_ms


def read(run):
    step_ms = device_step_ms.read(run)
    if run.opcount is None or run.peaks is None or not step_ms:
        return None
    work = run.opcount.step_work(run.cell.config, run.cell.traffic)
    least = max(work["flops"] / run.peaks["flops_bf16"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (step_ms * 1e-3)

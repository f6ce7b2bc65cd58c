"""The Pallas LSTM kernels' share of their roofline in per cent: the least
time the chip could take for what they need per step (the larger of
operations over peak FLOP/s and bytes over peak HBM bytes/s, from shapes,
``opcount/<config>.py: kernel_work``) over their device time per step. Only
bytes that have to cross HBM count, so the share cannot pass 100: where a
kernel's streams are values inside a program, which the compiler may keep on
the chip, ``kernel_work`` counts none and the floor is the MXU's."""
from benchmark.layer_metrics import pallas_ms_per_step


def read(run):
    ms = pallas_ms_per_step.read(run)
    if not ms or run.peaks is None or not hasattr(run.opcount, "kernel_work"):
        return None
    work = run.opcount.kernel_work(run.cell.config, run.cell.traffic)
    least = max(work["flops"] / run.peaks["flops_bf16"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)

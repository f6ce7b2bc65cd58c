"""Model FLOP/s utilisation in per cent: the operations the forward and
backward passes need per step (``opcount/<config>.py``, from shapes; nothing
recomputed counts) times the steps per second of the measured window, over
the chips' published bf16 peak."""


def read(run):
    if run.opcount is None or run.peaks is None or not run.window.steps:
        return None
    work = run.opcount.step_work(run.cell.config, run.cell.traffic)
    per_chip_step = work["flops"] * run.window.batches / run.cell.chips
    return 100.0 * per_chip_step / run.window.seconds / run.peaks["flops_bf16"]

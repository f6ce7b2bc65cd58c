"""The banded flash kernels' share of their roofline in per cent: the least
time the chip could take for the products the sliding-window layers of one
step need (``opcount/<config>.py: window_kernel_work``: the pairs inside the
band only, w (w + 1) / 2 + (T - w) w of them a head, and no bytes, the
kernels' operands being values inside the program) over the device time per
step of the Mosaic calls whose names carry a window (``flash_fwd_q1024_k1024_w1024``;
``ops/flash_attention.py: _name``). The time holds the cells of the blocks
that straddle the band's two edges, which the needed products leave out: the
share reads low by them. Nothing where no call carries a window or the
configuration's opcount has no ``window_kernel_work``."""
import re

from benchmark import xplane

#: a banded kernel's name as the trace has it (``.<n>`` after it)
BANDED = re.compile(r"^flash_[a-z]+_q\d+_k\d+_w\d+\b")


def seconds_per_step(run):
    """Device seconds per step inside banded flash calls, averaged over the
    devices; None where there are none."""
    if run.trace is None or not run.trace.devices or \
            not run.trace_window.steps:
        return None
    per = [sum(ev.seconds for ev in d.ops
               if xplane.is_pallas(ev) and BANDED.match(ev.name))
           for d in run.trace.devices]
    seconds = sum(per) / len(per)
    return seconds / run.trace_window.steps if seconds else None


def read(run):
    seconds = seconds_per_step(run)
    if not seconds or run.peaks is None or \
            not hasattr(run.opcount, "window_kernel_work"):
        return None
    work = run.opcount.window_kernel_work(run.cell.config, run.cell.traffic)
    least = max(work["flops"] / run.peaks["flops_bf16"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds

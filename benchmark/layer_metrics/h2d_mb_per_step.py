"""Megabytes per step the runtime moved from host to device inside the
traced window: the ``size`` of every ``tpu::System::TransferToDevice`` event
of the trace's host threads. (The program's ``input_bytes_total`` counts
host bytes through the pipeline whether or not they travel, so it reads the
same in a resident cell.)"""
from benchmark import xplane


def read(run):
    if run.trace is None or not run.trace_window.steps:
        return None
    moved = xplane.h2d_bytes(run.trace)
    return None if moved is None else moved / run.trace_window.steps / 1e6

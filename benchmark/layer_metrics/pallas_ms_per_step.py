"""Device milliseconds per step inside Mosaic custom calls (the Pallas
kernels; anonymous ``tpu_custom_call`` ops today). Nothing where the trace
holds none."""
from benchmark import xplane


def read(run):
    if run.trace is None or not run.trace_window.steps:
        return None
    seconds, calls = xplane.pallas_seconds(run.trace)
    if not calls:
        return None
    return seconds / run.trace_window.steps * 1e3

"""Share of the traced window in which no op ran on the device, in per cent,
averaged over the cell's chips: 1 - union of the ``XLA Ops`` intervals over
the time from the first op to the last."""
from benchmark import xplane


def read(run):
    if run.trace is None:
        return None
    span = xplane.window(run.trace)
    if span is None:
        return None
    return 100.0 * (1.0 - xplane.busy_seconds(run.trace)
                    / ((span[1] - span[0]) * 1e-9))

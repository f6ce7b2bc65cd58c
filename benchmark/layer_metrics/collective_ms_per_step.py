"""Milliseconds per step in which a collective was in flight on a device
(synchronous ops, and asynchronous ones from start to done), averaged over
the chips. Nothing where the trace holds none."""
from benchmark import xplane


def read(run):
    if run.trace is None or not run.trace_window.steps:
        return None
    flight, _ = xplane.collective_seconds(run.trace)
    return flight / run.trace_window.steps * 1e3 if flight else None

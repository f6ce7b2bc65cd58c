"""The part of ``collective_ms_per_step`` during which no compute ran on
that device: what the all-reduce costs the step."""
from benchmark import xplane


def read(run):
    if run.trace is None or not run.trace_window.steps:
        return None
    flight, exposed = xplane.collective_seconds(run.trace)
    return exposed / run.trace_window.steps * 1e3 if flight else None

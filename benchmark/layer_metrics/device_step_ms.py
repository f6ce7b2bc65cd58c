"""Milliseconds the device spent in one execution of the step program: the
mean duration of the longest-running program of the ``XLA Modules`` line."""
from benchmark import xplane


def read(run):
    if run.trace is None:
        return None
    modules = xplane.module_seconds(run.trace)
    if not modules:
        return None
    _, seconds = max(modules.values(), key=lambda cs: cs[0] * cs[1])
    return seconds * 1e3

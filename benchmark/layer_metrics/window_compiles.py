"""Compiles inside the measured window; must be 0. The largest of what
jitwatch, the persistent cache's request counter and the backend's compile
timer saw, so that a compile counts whoever asked for it and whoever served
it."""
from benchmark.counters import window_compiles


def read(run):
    return window_compiles(run.window.counters)

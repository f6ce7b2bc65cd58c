"""Seconds jitwatch charged to first calls of the program's jitted functions
during set-up: tracing plus the XLA compile, or the read from the cache."""


def read(run):
    return run.setup["jit_compile_seconds"]

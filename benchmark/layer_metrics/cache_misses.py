"""Compile requests of set-up that the persistent cache could not serve, so
XLA compiled (``/jax/compilation_cache/cache_misses``)."""


def read(run):
    return run.setup["cache_misses"]

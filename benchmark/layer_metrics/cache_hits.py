"""Compile requests of set-up that the persistent cache served
(``/jax/compilation_cache/cache_hits``)."""


def read(run):
    return run.setup["cache_hits"]

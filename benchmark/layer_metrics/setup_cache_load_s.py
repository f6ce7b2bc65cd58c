"""Seconds of set-up in the backend's part of jax's compiles
(``jax/backend_compile``, which holds ``jax/cache_retrieval``) on the
threads that ran an ``init`` or the first call of a monitored step, outside
``init`` (what it compiles itself is inside ``setup_init_s``): the read
from the persistent cache and the executable's load where every request
hits (``cache_misses`` 0), the XLA compile where one misses."""
from benchmark import setup_trace


def read(run):
    return setup_trace.phase_seconds(run, setup_trace.BACK)

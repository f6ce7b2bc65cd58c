"""Seconds of set-up that jax spent tracing functions to jaxprs and
lowering them to MLIR modules (``jax/trace`` + ``jax/lower``, jitwatch's
``jax.monitoring`` listeners) on the threads that ran an ``init`` or the
first call of a monitored step: what a process pays to find out which
program it needs, even where that program is already on disk. The eager
one-op programs are among them. A span nested in another counts once; a
program that is compiled while another is traced counts under
``setup_cache_load_s`` for its backend's part, and what ``init`` itself
compiles under ``setup_init_s``: the three are disjoint."""
from benchmark import setup_trace


def read(run):
    return setup_trace.phase_seconds(run, setup_trace.FRONT,
                                     less=setup_trace.BACK)

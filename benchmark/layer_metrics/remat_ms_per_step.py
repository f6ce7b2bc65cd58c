"""Device milliseconds per step, self time, of the step program's ops whose
``op_name`` carries jax's ``rematted_computation``: the forward work that a
``jax.checkpoint`` makes the backward pass do again (the looped stack's
blocks; the looped head recomputes nothing since PR 31, its cross-entropy
has a differentiation rule of its own). ``backward_ms_per_step`` holds it too:
``program_trace.kind`` reads it as backward because it sits inside
``transpose(``. Nothing where the program checkpoints nothing
(``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "rematted_computation")

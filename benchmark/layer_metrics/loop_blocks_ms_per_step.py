"""Device milliseconds per step, self time, of the step program's ops under a
looped stack's ``blocks`` scope (``nn/layers/looped.py``): every application
of a block, forward, backward and the recomputed forward together
(``loop_ms_per_block_application`` is the time of one).
Nothing where the program has no such scope (``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "blocks")

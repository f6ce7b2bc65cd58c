"""Device milliseconds per step, self time, of the step program's ops under a
hybrid stack's ``moe`` scope (``nn/layers/hybrid.py``): every expert block's
feed-forward half with its pre-norm and residual (``router``, ``dispatch``,
``experts``, ``shared``), forward, backward and the recomputed forward
together. Nothing where the program has no such scope
(``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "moe")

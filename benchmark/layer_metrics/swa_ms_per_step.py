"""Device milliseconds per step, self time, of the step program's ops under a
hybrid stack's ``swa`` scope (``nn/layers/hybrid.py``): every sliding-window
block's mixer with its pre-norm and residual (the four projections, the
rotation, the banded flash kernels), forward, backward and the recomputed
forward together. Nothing where the program has no such scope
(``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "swa")

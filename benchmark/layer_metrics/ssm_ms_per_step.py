"""Device milliseconds per step, self time, of the step program's ops under a
hybrid stack's ``ssm`` scope (``nn/layers/hybrid.py``): every state-space
block's mixer with its pre-norm and residual (projections, convolution, the
scan under ``ssd``, the gated norm), forward, backward and the recomputed
forward together. Nothing where the program has no such scope
(``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "ssm")

"""Device milliseconds per step, self time, of the step program's ops under a
hybrid stack's ``kda`` scope (``nn/layers/hybrid.py``): every KDA block's
mixer with its pre-norm and residual (projections, convolutions, the decay's
and the gate's low-rank pairs, the rule under ``kda_rule``, the gated output
norm), forward, backward and the recomputed forward together. Nothing where
the program has no such scope (``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "kda")

"""Device milliseconds per step, self time, of the step program's ops under
the ``kda_rule`` scope of a KDA mixer (``nn/layers/kda.py``): the delta rule
alone (the decays' sums and exponentials, the in-chunk products and the
triangular solve, the state carried from chunk to chunk, the read-out),
forward, backward and the recomputed forwards together; the projections, the
convolutions and the gates are ``kda`` outside it. Nothing where the program
has no such scope (``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "kda_rule")

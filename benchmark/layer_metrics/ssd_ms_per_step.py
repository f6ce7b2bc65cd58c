"""Device milliseconds per step, self time, of the step program's ops under
the ``ssd`` scope of a state-space mixer (``nn/layers/mamba.py``): the scan
alone (step sizes and decays, the in-chunk products, the state carried from
chunk to chunk, the read-out), forward, backward and the recomputed forward
together; the projections, the convolution and the gated norm are ``ssm``
outside it. Nothing where the program has no such scope
(``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "ssd")

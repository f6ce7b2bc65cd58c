"""Device milliseconds per step, self time, of the step program's ops under a
hybrid stack's ``mla`` scope (``nn/layers/hybrid.py``): every latent-attention
block's mixer with its pre-norm and residual (the query and latent
projections, the latent's norm, the flash kernels, the output projection),
forward, backward and the recomputed forward together. Nothing where the
program has no such scope (``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "mla")

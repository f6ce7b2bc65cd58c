"""Device milliseconds per step, self time averaged over the chips, of the
step program's ops whose ``op_name`` carries a layer's, a vertex's or the
``loss`` scope inside ``transpose(``: the backward pass (under
rematerialisation its recomputed forward too). A fusion counts whole under
its root's ``op_name`` (``program_trace.scoped_ms_per_step``)."""
from benchmark import program_trace


def read(run):
    found = program_trace.scoped_ms_per_step(run)
    return None if found is None else found["backward"]

"""Milliseconds per step in which device 0 ran nothing while the fit thread
sat in a ``pw/resolve_score`` span: what ``ParallelWrapper``'s deferred
``float(loss)`` costs the chips."""
from benchmark import program_trace


def read(run):
    if run.trace is None or not run.trace_window.steps:
        return None
    if not program_trace.spans(run.trace, ("pw/resolve_score",)):
        return None
    found = program_trace.idle_under(run.trace, ("pw/resolve_score",),
                                     fit_thread=True)
    return None if found is None else found[0] * 1e-6 / run.trace_window.steps

"""Of device 0's idle time in the traced window that the benchmark's own
``bench/input_next`` / ``bench/run_ahead_barrier`` spans do not claim, the
share in per cent during which the fit thread was inside one of the
program's spans other than ``epoch`` (``program_trace.PROGRAM_SPANS``): how
much of the idle time the program can name. A worker thread's span claims
nothing: they overlap everything."""
from benchmark import program_trace


def read(run):
    if run.trace is None or not program_trace.spans(
            run.trace, program_trace.PROGRAM_SPANS):
        return None
    found = program_trace.idle_under(
        run.trace, program_trace.PROGRAM_SPANS, fit_thread=True,
        less=program_trace.OWN_SPANS)
    return 100.0 * found[0] / found[1] if found and found[1] else None

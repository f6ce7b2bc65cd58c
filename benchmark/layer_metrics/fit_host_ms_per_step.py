"""Host milliseconds per step the fit thread needs to keep the device fed:
the program's ``fit/prepare`` and ``step`` spans (batch to device arrays and
the small programs around them; the dispatch of the jitted step) in the
traced window, over its steps. The waits (``fit/next_batch``,
``fit/resolve``) are left out, and so is the runtime's execute call inside
``step``, where a device with a full queue holds the host
(``program_trace.runtime_hold``). The cell is host-bound when this nears
``device_step_ms``."""
from benchmark import program_trace


def read(run):
    return program_trace.ms_per_step(run, program_trace.FIT_WORK,
                                     fit_thread=True)

"""Host milliseconds per step the prefetch workers spend handing a batch to
the runtime: the ``input/transform`` and ``input/put_ahead`` spans of every
worker thread in the traced window, over its steps. Against the step's
device time times the workers it is the head-room that an
``input_wait_ms_per_step`` of 0 cannot show."""
from benchmark import program_trace


def read(run):
    return program_trace.ms_per_step(run, program_trace.INPUT_WORK)

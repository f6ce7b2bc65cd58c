"""Host milliseconds per step ``ParallelWrapper`` needs to keep the chips
fed: its ``pw/group`` (one batch per chip from the iterator),
``pw/global_batch`` (merge and shard, or the device cache's hit) and
``pw/step`` (dispatch) spans in the traced window, over its steps, less the
runtime's execute call inside ``pw/step`` (``program_trace.runtime_hold``).
``pw/resolve_score`` is a wait that lasts about a step and is left out
(``pw_resolve_idle_ms_per_step`` says what it costs)."""
from benchmark import program_trace


def read(run):
    return program_trace.ms_per_step(run, program_trace.PW_WORK,
                                     fit_thread=True)

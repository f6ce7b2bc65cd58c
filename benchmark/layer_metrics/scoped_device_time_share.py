"""Share in per cent of the device's self time in the traced window whose op
carries one of the program's scopes (a layer or vertex, ``loss``,
``updater``): the coverage of the program's ``jax.named_scope``s.
``forward_ms_per_step`` + ``backward_ms_per_step`` +
``optimizer_ms_per_step`` is this share of the busy time per step; the rest
is ops with no scope or no metadata (copies the compiler inserts, the scan's
slicing) and the small programs beside the step."""
from benchmark import program_trace


def read(run):
    found = program_trace.scoped_ms_per_step(run)
    if found is None:
        return None
    total = sum(found.values())
    return 100.0 * (total - found["unscoped"]) / total

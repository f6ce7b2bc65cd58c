"""Device self time of the step program's ops by the tokens of their
``op_name``, for readers that ask about a scope ``program_trace.kind`` does
not tell apart (a sub-scope such as ``blocks``, ``head``; jax's own
``rematted_computation``). Same lookup as
``program_trace.scoped_ms_per_step``: an op of the trace is found in the live
step program's text by its HLO value name, a fusion counts whole under its
root's ``op_name``. On a program whose text carries none of the tokens every
sum is 0 and the readers return ``None``."""
from __future__ import annotations

import re

from benchmark import device, program_trace, xplane


def _tokens_seconds(run):
    """``[(tokens of the op_name, self seconds averaged over the chips)]`` of
    the ops that ran inside the step program; None without a device trace."""
    trace = run.trace
    if trace is None or not trace.devices or not run.trace_window.steps:
        return None
    if "op_scopes" not in run.extras:            # several readers, one pass
        ran = program_trace._module_intervals(trace.devices[0])
        if not ran:
            return None
        module = max(ran, key=lambda k: xplane.measure(ran[k]))
        maps = [program_trace.op_names(t)
                for t in device.live_program_texts(run.devices)
                if re.match(rf"HloModule {re.escape(module)}\b", t)]
        per_dev = [program_trace._self_seconds(
            d, xplane.union(program_trace._module_intervals(d)[module]))[0]
            for d in trace.devices]
        names = max(maps, default={}, key=lambda m: sum(
            sec for ops in per_dev for name, sec in ops.items() if name in m))
        run.extras["op_scopes"] = [
            (frozenset(re.split(r"[/()]", names.get(name, "").split(";")[0])),
             sec / len(trace.devices))
            for ops in per_dev for name, sec in ops.items()]
    return run.extras["op_scopes"]


def ms_per_step(run, *tokens):
    """Device milliseconds per step of the step program's ops whose
    ``op_name`` carries one of ``tokens`` between its slashes; None where
    none does."""
    found = _tokens_seconds(run)
    if found is None:
        return None
    seconds = sum(sec for have, sec in found if have & set(tokens))
    return seconds / run.trace_window.steps * 1e3 if seconds else None

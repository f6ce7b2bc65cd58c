"""What the program says about itself in a trace, for the readers under
``layer_metrics/`` that are ``program_span`` metrics or read the program's
scopes.

Host side: the program's spans (``deeplearning4j_tpu/monitor/tracer.py``;
the table in docs/OBSERVABILITY.md "Span Tracer") are ``TraceAnnotation``
events of the host plane like the benchmark's own ``bench/fit``. A span is
host time; the two that are waits are named as such and kept out of every
sum of host work.

Device side: an ``XLA Ops`` event carries its HLO text but none of its
metadata (read by hand on a v5e trace, PR 23: the stats are
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale Multiplier``).
The program's scopes (``jax.named_scope`` per layer / vertex, ``loss``,
``updater``; the kernels' ``name=``) are in the ``op_name`` of the compiled
program's text, which ``device.live_program_texts`` fetches: an op of the
trace is looked up there by its HLO value name. A fusion is one instruction
and counts whole under the ``op_name`` of its root.

On a program that has none of this (the commit before PR 23) every function
here finds nothing and the readers return ``None``.
"""
from __future__ import annotations

import collections
import importlib
import re

from benchmark import device, xplane

#: host work on the fit thread that keeps one device fed
FIT_WORK = ("fit/prepare", "step")
#: the same under ``ParallelWrapper``
PW_WORK = ("pw/group", "pw/global_batch", "pw/step")
#: host work of the prefetch workers
INPUT_WORK = ("input/transform", "input/put_ahead")
#: the spans that are waits for the device or the iterator, named as such
WAITS = ("fit/next_batch", "fit/resolve", "pw/resolve_score")
#: every span of the program's table other than ``epoch``, which only says
#: "somewhere in fit"
PROGRAM_SPANS = FIT_WORK + PW_WORK + INPUT_WORK + WAITS + ("pw/place_model",)
#: the benchmark's own spans, whose idle time is the benchmark's
OWN_SPANS = ("bench/input_next", "bench/run_ahead_barrier")
#: the span the benchmark puts around the program's entry point
FIT = "bench/fit"
#: the spans that dispatch the jitted step, and the runtime's execute call
#: (its name in the PJRT C API) as the host plane has it
DISPATCH = ("step", "pw/step")
RUNTIME_EXECUTE = "PJRT_LoadedExecutable_Execute"


# ------------------------------------------------------------------ host
def spans(trace, names, fit_thread=False):
    """The host events called one of ``names``, from every thread or only
    from those that run ``bench/fit``."""
    return [ev for _, evs in trace.host
            if not fit_thread or any(ev.name == FIT for ev in evs)
            for ev in evs if ev.name in names]


def ms_per_step(run, names, fit_thread=False):
    """Summed duration of the spans ``names`` in the traced window over its
    steps, in ms, less what the runtime's execute call took of a dispatch
    span among them; None where the trace holds no such span."""
    if run.trace is None or not run.trace_window.steps:
        return None
    found = spans(run.trace, names, fit_thread)
    if not found:
        return None
    held = runtime_hold(run.trace)
    return sum(ev.end - ev.start - xplane.overlap(held, ev.start, ev.end)
               for ev in found) * 1e-6 / run.trace_window.steps


def runtime_hold(trace):
    """Merged intervals of the runtime's execute calls that ran inside a
    dispatch span (``step``, ``pw/step``). That call is where a device with
    a full queue holds the host: in the char-RNN cell 61 and 69 ms of it
    under two ``step`` spans against 0.27 and 0.35 ms under the two before
    the host was a step ahead (v5e trace, PR 23). It is the device's time,
    not host work, so the sums of host work leave the call out whole."""
    dispatch = xplane.union((ev.start, ev.end)
                            for ev in spans(trace, DISPATCH))
    return xplane.union(
        (ev.start, ev.end) for ev in spans(trace, (RUNTIME_EXECUTE,))
        if xplane.overlap(dispatch, ev.start, ev.end) > 0)


def idle_under(trace, names, fit_thread=False, less=()):
    """``(ns of device 0's idle time under a span of names, ns of its idle
    time in all)`` between its first op and its last, both after the spans
    ``less`` took their part; None where the trace holds no device op."""
    if not trace.devices or not trace.devices[0].ops:
        return None
    dev = trace.devices[0]
    idle = xplane.gaps(xplane.busy_intervals(dev), dev.ops[0].start,
                       max(ev.end for ev in dev.ops))
    if less:
        idle = xplane.subtract(idle, xplane.union(
            (ev.start, ev.end) for ev in spans(trace, less)))
    under = xplane.union((ev.start, ev.end)
                         for ev in spans(trace, names, fit_thread))
    return (sum(xplane.overlap(under, s, e) for s, e in idle),
            xplane.measure(idle))


# ---------------------------------------------------------------- device
def layer_scopes(config):
    """The names the program's forward loops give their ``named_scope``s:
    the vertex names of a graph, the layer indices of a layer list. Built
    from the configuration as ``cells.build_net`` builds it, without
    weights."""
    kind, _, what = config["builder"].partition(":")
    kwargs = dict(config.get("builder_kwargs", {}))
    if kind == "zoo":
        import deeplearning4j_tpu.models as zoo
        if "input_shape" in kwargs:
            kwargs["input_shape"] = tuple(kwargs["input_shape"])
        conf = getattr(zoo, what)(seed=0, **kwargs).conf()
    else:
        mod, _, fn = what.partition(":")
        conf = getattr(importlib.import_module(f"benchmark.{mod}"),
                       fn)(seed=0, **kwargs)
    if hasattr(conf, "vertices"):
        return frozenset(conf.vertices)
    return frozenset(str(i) for i in range(len(conf.layers)))


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")     # as xplane reads it
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def op_names(text):
    """{HLO value name: op_name} of a program's text. An instruction has the
    ``op_name`` of its metadata; a fusion that of the root of the
    computation it calls (of the first element with one, where the root is
    a tuple): the compiler stamps a fusion with the name of one op inside
    it, often a matrix product whose epilogue is another scope's work."""
    names, roots, fusions, comp = {}, {}, {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        root, name, rest = m.groups()
        found = _OP_NAME.search(rest)
        if found:
            names[name] = found.group(1)
        code = _OPCODE.search(" " + rest)
        code = code.group(1) if code else ""
        if root:
            roots[comp] = [name] + (
                re.findall(r"%([\w.\-]+)", rest.split(", metadata=")[0])
                if code == "tuple" else [])
        if code == "fusion":
            called = _CALLS.search(rest)
            if called:
                fusions[name] = called.group(1)
    for name, called in fusions.items():
        for inner in roots.get(called, ()):
            if inner in names:
                names[name] = names[inner]
                break
    return names


def kind(op_name, scopes):
    """``optimizer``, ``backward``, ``forward`` or None (no scope of the
    program in it). Where passes merged several ops their names are joined
    by ``;`` and the first speaks."""
    tokens = set(re.split(r"[/()]", op_name.split(";")[0]))
    if "updater" in tokens:
        return "optimizer"
    if "loss" in tokens or tokens & scopes:
        return "backward" if "transpose" in tokens else "forward"
    return None


def _module_intervals(dev):
    """{program name: its executions' intervals on ``dev``}, the
    fingerprint in brackets dropped."""
    by = collections.defaultdict(list)
    for ev in dev.modules:
        by[re.sub(r"\(\d+\)$", "", ev.name)].append((ev.start, ev.end))
    return by


def _self_seconds(dev, inside):
    """``({op name: self seconds} over the ops of dev that ran inside the
    merged intervals inside, self seconds of all the others)``. An op nested
    in one that ran inside ran inside too; the ops of the line do not
    overlap otherwise, so all self times add up to the busy time."""
    within = xplane.self_seconds(xplane.Device(dev.ordinal, [
        ev for ev in dev.ops
        if xplane.overlap(inside, ev.start, ev.start + 1) > 0], [], []))
    busy = xplane.measure(xplane.busy_intervals(dev)) * 1e-9
    return within, busy - sum(within.values())


def scoped_ms_per_step(run, texts=None):
    """``{"forward", "backward", "optimizer", "unscoped": ms per step}`` of
    device self time, averaged over the chips: an op inside an execution of
    the step program counts under the scope its ``op_name`` carries in that
    program's text, everything else (an op with no scope or no metadata,
    the small programs beside the step) as unscoped. The four add up to the
    device's busy time per step. None where no op carries a scope.
    ``texts``: the candidate programs' HLO texts, the live executables' if
    not given; of those named like the step program, the one that knows
    most of its ops' time."""
    trace = run.trace
    if trace is None or not trace.devices or not run.trace_window.steps:
        return None
    if "scoped_ms_per_step" not in run.extras:   # four readers, one pass
        # the step: the program device 0 spent most time in
        ran = _module_intervals(trace.devices[0])
        if not ran:
            return None
        module = max(ran, key=lambda k: xplane.measure(ran[k]))
        if texts is None:
            texts = device.live_program_texts(run.devices)
        scopes = layer_scopes(run.cell.config)
        maps = [op_names(t) for t in texts
                if re.match(rf"HloModule {re.escape(module)}\b", t)]
        per_dev = [
            _self_seconds(d, xplane.union(_module_intervals(d)[module]))
            for d in trace.devices]
        names = max(maps, default={}, key=lambda m: sum(
            sec for ops, _ in per_dev for name, sec in ops.items()
            if name in m))
        out = collections.Counter()
        for ops, rest in per_dev:
            out["unscoped"] += rest
            for name, sec in ops.items():
                out[kind(names.get(name, ""), scopes) or "unscoped"] += sec
        per = len(trace.devices) * run.trace_window.steps * 1e-3
        run.extras["scoped_ms_per_step"] = {
            k: out[k] / per
            for k in ("forward", "backward", "optimizer", "unscoped")}
    found = run.extras["scoped_ms_per_step"]
    return found if sum(found.values()) > found["unscoped"] else None

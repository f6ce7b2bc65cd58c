"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What a TPU trace of this program looks like (read by hand, PR 22): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per executed program), ``XLA Ops`` (one event per HLO op, named by its whole
HLO text ``%name = shape opcode(...)``; a ``while`` spans its body, so ops
nest) and ``Async XLA Ops`` (one event from each ``*-start`` to its
``*-done``: DMA copies and asynchronous collectives). Host threads are lines
of the plane ``/host:CPU``, on the same clock, and hold JAX's own events
(``PjitFunction(step)``, ``DevicePut``) and every ``TraceAnnotation``.

Everything here works on plain intervals in nanoseconds, so that a test can
hand it a trace it wrote down by hand as well as a recorded one.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import re

#: HLO opcodes of collective operations, as they appear in op names
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
#: ops whose interval is the interval of a body of other ops
CONTAINERS = ("while", "conditional", "call")
#: how a Pallas kernel shows in an op's HLO text
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
#: the runtime's host event of one host-to-device transfer; its ``size``
#: stat is the bytes moved
H2D_EVENT = "tpu::System::TransferToDevice"

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str        # short: the HLO value name, or the host event's name
    text: str        # whole HLO text (device ops) or the name again
    start: float     # ns
    end: float       # ns
    #: ``fusion``, ``custom-call``, ``all-reduce-start`` … of a device op;
    #: empty for anything that is not HLO text
    opcode: str = ""
    size: float = 0.0    # bytes, of a transfer event

    @property
    def seconds(self):
        return (self.end - self.start) * 1e-9


@functools.lru_cache(maxsize=None)
def _parse(text):
    """``(short name, opcode)`` of an event's name as the trace has it. An
    op runs many times under one text, so this is worth remembering."""
    if " = " not in text:
        return text, ""
    name, rest = text.split(" = ", 1)
    m = _OPCODE.search(rest)
    return name.lstrip("%"), m.group(1) if m else ""


def event(text, start, end):
    """An :class:`Event` from an event's name as the trace has it."""
    name, opcode = _parse(text)
    return Event(name, text, start, end, opcode)


@dataclasses.dataclass
class Device:
    ordinal: int
    ops: list          # XLA Ops, sorted by start
    async_ops: list    # Async XLA Ops
    modules: list      # XLA Modules


@dataclasses.dataclass
class Trace:
    devices: list      # of Device, by ordinal
    host: list         # of (thread name, [Event]) for every host thread


def _events(line):
    out = []
    for e in line.events:
        ev = event(e.name, float(e.start_ns),
                   float(e.start_ns + e.duration_ns))
        if ev.name == H2D_EVENT:
            ev = dataclasses.replace(
                ev, size=float(dict(e.stats).get("size", 0)))
        out.append(ev)
    out.sort(key=lambda ev: (ev.start, -ev.end))
    return out


def load(path):
    """Read ``path`` with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(Device(
                int(m.group(1)),
                _events(lines["XLA Ops"]) if "XLA Ops" in lines else [],
                _events(lines["Async XLA Ops"])
                if "Async XLA Ops" in lines else [],
                _events(lines["XLA Modules"])
                if "XLA Modules" in lines else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = _events(ln)
                if evs:
                    host.append((ln.name, evs))
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices, host)


# ------------------------------------------------------------- intervals
def union(intervals):
    """Merged, sorted, disjoint ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals):
    return sum(e - s for s, e in intervals)


def overlap(merged, s, e):
    """Length of ``(s, e)`` covered by the merged intervals ``merged``."""
    i = bisect.bisect_right(merged, (s, float("inf"))) - 1
    if i < 0:
        i = 0
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        total += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


def gaps(merged, t0, t1):
    """The parts of ``(t0, t1)`` that ``merged`` leaves uncovered."""
    out, at = [], t0
    for s, e in merged:
        if e <= t0 or s >= t1:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return out


def subtract(merged, minus):
    """The parts of the merged intervals ``merged`` outside ``minus``."""
    out = []
    for s, e in merged:
        out += gaps(minus, s, e)
    return out


# ---------------------------------------------------------------- device
def is_collective(ev):
    return ev.opcode.startswith(COLLECTIVES)


def is_pallas(ev):
    return PALLAS_TARGET in ev.text


def window(trace):
    """``(t0, t1)``: from the first device op of the trace to the last."""
    starts = [d.ops[0].start for d in trace.devices if d.ops]
    ends = [max(ev.end for ev in d.ops) for d in trace.devices if d.ops]
    if not starts:
        return None
    return min(starts), max(ends)


def busy_intervals(device):
    return union((ev.start, ev.end) for ev in device.ops)


def busy_seconds(trace):
    """Seconds in which an op ran, averaged over the devices of the trace."""
    per = [measure(busy_intervals(d)) * 1e-9 for d in trace.devices]
    return sum(per) / len(per) if per else 0.0


def self_seconds(device):
    """{op name: seconds} with every op's time less that of the ops nested
    in it, so that a ``while`` does not count its body twice."""
    total = collections.Counter()
    stack = []                       # [event, self ns]
    for ev in device.ops:
        while stack and stack[-1][0].end <= ev.start:
            done, ns = stack.pop()
            total[done.name] += ns
        if stack:
            stack[-1][1] -= ev.end - ev.start
        stack.append([ev, ev.end - ev.start])
    for done, ns in stack:
        total[done.name] += ns
    return {k: v * 1e-9 for k, v in total.items()}


def top_ops(trace, n=10):
    """``[[label, seconds], ...]``: the ``n`` ops with most self time, summed
    over their executions and averaged over the devices. The label is the
    HLO value name with its opcode (``fusion.30 fusion``): the compiler's
    names, which is all the program gives until its kernels are named."""
    total = collections.Counter()
    for d in trace.devices:
        first = {}
        for ev in d.ops:
            first.setdefault(ev.name, ev)
        for name, sec in self_seconds(d).items():
            code = first[name].opcode
            label = name if not code or code in name else f"{name} {code}"
            if is_pallas(first[name]):
                label += " (pallas)"
            total[label] += sec / len(trace.devices)
    return [[k, v] for k, v in total.most_common(n)]


def pallas_seconds(trace):
    """Device seconds inside Mosaic custom calls, averaged over devices, and
    how many such calls ran on the first device."""
    per = [sum(ev.seconds for ev in d.ops if is_pallas(ev))
           for d in trace.devices]
    calls = sum(1 for ev in trace.devices[0].ops if is_pallas(ev)) \
        if trace.devices else 0
    return (sum(per) / len(per) if per else 0.0), calls


def collective_intervals(device):
    """Merged intervals in which a collective was in flight on ``device``:
    synchronous ones from the op line, asynchronous ones from start to
    done."""
    sync = [(ev.start, ev.end) for ev in device.ops
            if is_collective(ev) and not ev.opcode.endswith(("-start",
                                                             "-done"))]
    flying = [(ev.start, ev.end) for ev in device.async_ops
              if is_collective(ev)]
    return union(sync + flying)


def compute_intervals(device):
    """Merged intervals in which an op that is neither a collective nor a
    container of other ops ran."""
    return union((ev.start, ev.end) for ev in device.ops
                 if not is_collective(ev) and ev.opcode not in CONTAINERS)


def collective_seconds(trace):
    """``(in flight, exposed)`` seconds, averaged over the devices: exposed
    is the part of a collective during which no compute ran on that device."""
    flight, exposed = [], []
    for d in trace.devices:
        coll = collective_intervals(d)
        comp = compute_intervals(d)
        flight.append(measure(coll) * 1e-9)
        exposed.append(sum((e - s) - overlap(comp, s, e)
                           for s, e in coll) * 1e-9)
    n = len(trace.devices)
    return (sum(flight) / n, sum(exposed) / n) if n else (0.0, 0.0)


def module_seconds(trace):
    """{program name: (executions on device 0, mean seconds)} from the
    ``XLA Modules`` line, the fingerprint in brackets dropped."""
    if not trace.devices:
        return {}
    by = collections.defaultdict(list)
    for ev in trace.devices[0].modules:
        by[re.sub(r"\(\d+\)$", "", ev.name)].append(ev.seconds)
    return {k: (len(v), sum(v) / len(v)) for k, v in by.items()}


# ------------------------------------------------------------------ host
def host_spans(trace, prefix):
    """Every host event whose name starts with ``prefix``."""
    return [ev for _, evs in trace.host for ev in evs
            if ev.name.startswith(prefix)]


def h2d_bytes(trace):
    """Bytes the runtime moved from host to device inside the trace, or None
    where the trace holds no such event."""
    sizes = [ev.size for _, evs in trace.host for ev in evs
             if ev.name == H2D_EVENT]
    return sum(sizes) if sizes else None


def _innermost_at(events, starts, t, depth=256):
    """The event of ``events`` (sorted by start) that began last among those
    running at ``t``, or None. Looks back over ``depth`` events at most: an
    event that long ago is a container of the whole loop and says nothing."""
    i = bisect.bisect_right(starts, t)
    for ev in reversed(events[max(0, i - depth):i]):
        if ev.end > t:
            return ev
    return None


def idle_gaps(trace, inner=("bench/run_ahead_barrier", "bench/input_next"),
              outer="bench/fit", n=10):
    """``[[label, seconds], ...]``: the idle time of the first device inside
    the traced window, by what the host was doing. A gap's time goes first
    to the benchmark's own inner spans that overlap it (the iterator's
    ``next`` and its run-ahead barrier, on whichever thread called them).
    What is left goes to the innermost host event running at the middle of
    the gap on a thread that runs ``outer``: JAX's own (``PjitFunction(step)``,
    ``DevicePut`` …) or a span of the program (``epoch``), and to "outside"
    where ``outer`` was not running."""
    if not trace.devices or not trace.devices[0].ops:
        return []
    dev = trace.devices[0]
    t0, t1 = dev.ops[0].start, max(ev.end for ev in dev.ops)
    own, claimed = {}, []
    for name in inner:               # a later span less the earlier ones
        spans = union((ev.start, ev.end) for ev in host_spans(trace, name))
        own[name] = subtract(spans, claimed)
        claimed = union(claimed + spans)
    fit = union((ev.start, ev.end) for ev in host_spans(trace, outer))
    events = sorted((ev for _, evs in trace.host
                     if any(ev.name == outer for ev in evs)
                     for ev in evs if ev.name not in inner),
                    key=lambda ev: ev.start)
    starts = [ev.start for ev in events]
    total = collections.Counter()
    for s, e in gaps(busy_intervals(dev), t0, t1):
        left = e - s
        for name in inner:
            got = min(left, overlap(own[name], s, e))
            if got > 0:
                total[name] += got
                left -= got
        if left <= 0:
            continue
        mid = (s + e) / 2
        if overlap(fit, mid, mid + 1) <= 0:
            total[f"outside {outer}"] += left
            continue
        ev = _innermost_at(events, starts, mid)
        name = ev.name if ev is not None else outer
        total[name if name == outer else f"{outer} > {name}"] += left
    return [[k, v * 1e-9] for k, v in total.most_common(n)]

"""What the program says about its own set-up, for the readers under
``layer_metrics/`` whose names start with ``setup_`` and that read spans.

The profiler does not run during set-up and the benchmark runs with the
monitor off, so nothing of the steady step's tracing sees it. Since PR 37
the program's tracer keeps the spans whose ``cat`` is ``"setup"`` or
``"compile"`` whatever the switch says (``deeplearning4j_tpu/monitor/
tracer.py``, ``Tracer.kept()``; the table in docs/OBSERVABILITY.md "Span
Tracer"): ``init`` > ``init/params`` + ``init/updater_state``,
``compile/<fn>``, the phases of every jax compile (``jax/trace``,
``jax/lower``, ``jax/backend_compile``, ``jax/cache_retrieval``),
``jitwatch/cost_capture`` on jitwatch's worker thread, ``pw/place_model``.
A record's ``start`` and ``end`` are ``time.perf_counter()`` seconds, the
clock ``run.window.t0`` is on.

The records are cut to the run's set-up: after the window the reference
check builds a second network and a four-chip cell's traced run trains a
third on one device, and both would land in any sum over the process.

On a program whose tracer keeps nothing (the commit before PR 37)
``records`` finds nothing and every reader returns ``None``.
"""
from __future__ import annotations

import collections

from benchmark import xplane

#: the compile's phases in front of the backend, and the backend's: the
#: read from the persistent cache where the request hits (that read is
#: inside the backend's span), the compile where it misses
FRONT = ("jax/trace", "jax/lower")
BACK = ("jax/backend_compile", "jax/cache_retrieval")
#: slack before ``t0 - setup_s``: ``run_cell`` takes ``setup_s`` a few
#: milliseconds before ``measure`` sets ``t0``
SLACK_S = 0.5


def records(run):
    """The kept spans of the run's set-up, those that start at or after
    ``window.t0 - setup_s - SLACK_S`` and end at or before ``window.t0``;
    None where the tracer keeps nothing, the run measured no window or no
    ``init`` is among them."""
    if "setup_records" not in run.extras:       # seven readers, one cut
        from deeplearning4j_tpu.monitor import get_tracer
        kept = getattr(get_tracer(), "kept", None)
        window = getattr(run, "window", None)
        found = []
        if kept is not None and window is not None:
            t0 = window.t0 - run.setup_s - SLACK_S
            found = [r for r in kept()
                     if r["start"] >= t0 and r["end"] <= window.t0]
        run.extras["setup_records"] = (
            found if any(r["name"] == "init" for r in found) else None)
    return run.extras["setup_records"]


def seconds(run, names):
    """Summed duration of the set-up's spans called one of ``names``
    (0.0 where there is none), or None."""
    found = records(run)
    if found is None:
        return None
    return sum(r["end"] - r["start"] for r in found if r["name"] in names)


def fit_threads(found):
    """The threads that ran an ``init`` or the first call of a monitored
    step: not jitwatch's cost worker, whose compiles are its own."""
    return {r["tid"] for r in found
            if r["name"] == "init" or r["name"].startswith("compile/")}


def covered(found, wanted, threads, less=()):
    """Seconds during which a thread of ``threads`` was inside a span that
    ``wanted(name)`` accepts and inside none called one of ``less``: per
    thread the union of the intervals, so a span nested in another of them
    counts once."""
    by_thread = collections.defaultdict(lambda: ([], []))
    for r in found:
        if r["tid"] in threads:
            if r["name"] in less:
                by_thread[r["tid"]][1].append((r["start"], r["end"]))
            elif wanted(r["name"]):
                by_thread[r["tid"]][0].append((r["start"], r["end"]))
    return sum(xplane.measure(xplane.subtract(xplane.union(spans),
                                              xplane.union(minus)))
               for spans, minus in by_thread.values())


def phase_seconds(run, names, less=()):
    """``covered`` for the compile phases ``names`` on the fit threads,
    outside the spans ``less`` and outside ``init`` (whose own eager
    programs are inside ``setup_init_s``), so that the ``init`` spans, the
    front phases and the backend's are three disjoint parts of set-up; or
    None."""
    found = records(run)
    if found is None:
        return None
    return covered(found, lambda name: name in names, fit_threads(found),
                   less=tuple(less) + ("init",))

"""Monitor-registry hygiene: metric names must carry their units.

The alert engine (``monitor/alerts.py``) and every dashboard built on the
registry interpret series semantically from the NAME alone — windowed
``rate()`` is only meaningful on a monotonic counter, ``quantile_over``
only on a histogram whose unit it can report, a ``_bytes`` threshold only
when the value really is bytes. One misnamed series (a gauge spelled like
a counter, a seconds histogram on ms bucket geometry, a unit buried
mid-name) silently corrupts every downstream consumer. MON001 pins the
convention the package settled on:

- **counters end ``_total``** (Prometheus convention; the registry even
  refuses ``dec`` on them — the name should promise the same).
- **gauges do NOT end ``_total``** — that spelling promises monotonicity
  a gauge cannot keep.
- **histograms end in a unit**: ``_ms``, ``_seconds``, ``_bytes``, or
  ``_examples`` (the dimensionless-count spelling
  ``training_examples_total`` established).
- **``_seconds`` histograms pass ``unit="s"``** — the name claims
  seconds, so the bucket geometry must be the seconds geometry
  (``registry.py``); on the default ms geometry every sub-100 ms sample
  collapses into bucket 0 and the quantiles lie.
- **unit tokens sit at the END of the name** (or directly before
  ``_total``, the Prometheus counter spelling ``*_bytes_total``):
  ``device_memory_in_use_bytes``, never ``device_memory_bytes_in_use``.
- **hit/miss series are monotonic event counts** (names whose stem ends
  ``_hits``/``_misses`` — ISSUE 11's ``serving_cache_*`` response-cache
  series, any future cache): they must be counters ending ``_total`` — a
  gauge or histogram spelling would break the hit-rate math every
  consumer (the /profile ``cache`` column) derives from windowed
  counter deltas.

The rule fires on direct registry-handle creations — ``X.counter("name",
...)`` / ``X.gauge`` / ``X.histogram`` with a literal (or
literal-suffixed f-string) name — anywhere in the package.
"""
from __future__ import annotations

import ast
from typing import Iterator, Optional

from . import Rule, register, terminal_name

#: tokens that denote a unit; they may only appear terminally (or right
#: before a counter's _total)
_UNIT_TOKENS = {"ms", "seconds", "bytes", "examples"}

#: suffixes a histogram name may end with
_HIST_SUFFIXES = ("_ms", "_seconds", "_bytes", "_examples")

_KINDS = {"counter", "gauge", "histogram"}


def _literal_name(call: ast.Call) -> Optional[str]:
    """The metric-name literal of a registry call: a plain string, or an
    f-string (the ``paramserver_{k}_total`` idiom) flattened with ``*``
    placeholders for the dynamic parts so suffix checks still work.
    None when the name is fully dynamic (nothing to check)."""
    if not call.args:
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.JoinedStr):
        parts = []
        for v in arg.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append("*")
        name = "".join(parts)
        return name if name.strip("*") else None
    return None


def _unit_kwarg(call: ast.Call) -> Optional[str]:
    for kw in call.keywords:
        if kw.arg == "unit" and isinstance(kw.value, ast.Constant):
            return kw.value.value
    return None


def _hits_misses_stem(name: str) -> bool:
    """Whether the name is a hit/miss EVENT COUNT: its token sequence
    ends with ``hits``/``misses``, optionally followed by unit tokens
    and/or a final ``total``. Such series must be ``_total`` counters —
    hit-rate math everywhere derives from monotonic counter deltas.
    (``cache_hit_latency_ms`` — singular, mid-name — is not one.)"""
    tokens = name.split("_")
    if tokens and tokens[-1].endswith("*"):
        # trailing "*" = dynamic f-string suffix, unknowable statically
        # (the counter branch's same escape — the suffix may well be
        # "total" at runtime, the paramserver_{k}_total idiom)
        return False
    while tokens and (tokens[-1] in _UNIT_TOKENS or tokens[-1] == "total"):
        tokens.pop()
    return bool(tokens) and tokens[-1] in ("hits", "misses")


def _misplaced_unit(name: str) -> Optional[str]:
    """The first unit token that is neither terminal nor directly before a
    final ``_total`` (None when the name is clean). ``*`` placeholder
    tokens from f-strings are ignored."""
    tokens = name.split("_")
    for i, tok in enumerate(tokens):
        if tok not in _UNIT_TOKENS:
            continue
        terminal = i == len(tokens) - 1
        pre_total = i == len(tokens) - 2 and tokens[-1] == "total"
        if not (terminal or pre_total):
            return tok
    return None


@register
class MetricNameUnitSuffix(Rule):
    id = "MON001"
    title = "metric name breaks the unit-suffix convention"
    rationale = (
        "Alert rules and dashboards interpret registry series from the "
        "name alone: rate() needs a counter (`_total`), quantile math "
        "needs the unit the name claims, and a `_seconds` histogram on "
        "the default ms bucket geometry reports quantiles that are flat "
        "lies below 100 ms. Counters end `_total`; gauges must not; "
        "histograms end `_ms`/`_seconds`/`_bytes`/`_examples` (with "
        "`unit=\"s\"` for `_seconds`); unit tokens go at the END of the "
        "name (`..._bytes`), or directly before a counter's `_total` "
        "(`..._bytes_total`).")

    def check(self, tree, lines, path) -> Iterator:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            kind = terminal_name(node.func)
            if kind not in _KINDS or not isinstance(node.func,
                                                    ast.Attribute):
                continue
            name = _literal_name(node)
            if name is None:
                continue
            bad = self._verdict(kind, name, node)
            if bad:
                yield self.finding(node, lines, path, bad)

    def _verdict(self, kind: str, name: str,
                 call: ast.Call) -> Optional[str]:
        tok = _misplaced_unit(name)
        if tok:
            return (f"{kind} {name!r} buries the unit token {tok!r} "
                    f"mid-name — units go at the end "
                    f"(…_{tok}, or …_{tok}_total for a counter)")
        if _hits_misses_stem(name):
            # hit/miss series (response cache, any future cache) are
            # monotonic events by definition — any non-counter spelling
            # silently breaks every hit-rate consumer downstream
            if kind != "counter" or not name.endswith("_total"):
                return (f"{kind} {name!r}: hit/miss series must be "
                        f"counters ending '_total' (e.g. "
                        f"serving_cache_hits_total / "
                        f"serving_cache_misses_total) — hit-rate math "
                        f"needs monotonic counter deltas")
        if kind == "counter":
            if not name.endswith("_total") and not name.endswith("*"):
                return (f"counter {name!r} must end '_total' (the name "
                        f"should promise the monotonicity the registry "
                        f"enforces)")
        elif kind == "gauge":
            if name.endswith("_total"):
                return (f"gauge {name!r} must not end '_total' — that "
                        f"suffix promises a monotonic counter")
        else:  # histogram
            if not name.endswith(_HIST_SUFFIXES) \
                    and not name.endswith("*"):
                # trailing "*" = dynamic f-string suffix, unknowable
                # statically (same escape as the counter branch)
                return (f"histogram {name!r} must end one of "
                        f"{'/'.join(_HIST_SUFFIXES)} so readers know the "
                        f"sample unit")
            if name.endswith("_seconds") and _unit_kwarg(call) != "s":
                return (f"histogram {name!r} claims seconds but does not "
                        f"pass unit=\"s\" — on the default ms bucket "
                        f"geometry its quantiles saturate below 100 ms "
                        f"(monitor/registry.py)")
        return None

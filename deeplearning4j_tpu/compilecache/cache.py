"""Persistent XLA compile-cache wiring (the compile-once fleet, half 1).

Every process in the fleet pays full XLA compile cost at start — visible
in ``jit_compile_seconds``, in ``ModelRegistry.warm()``'s cold-start
compiles, and in worker rejoin after ``scale_to()``. jax ships a
persistent on-disk compilation cache that turns a recompile into a disk
read, but it is off by default; this module is the package's one switch:

- :func:`enable` turns jax's compilation cache on for every program (not
  just slow-to-compile ones) and registers a ``jax.monitoring`` listener
  so cache hits/misses are observable. Where the cache lives, in order:
  ``JAX_COMPILATION_CACHE_DIR`` (jax's own variable — when it is set the
  cache is placed from outside and no code here sets another directory),
  the directory the caller passes (``chip_smoke.py`` and ``bench.py
  --one`` pass the fixed ``<checkout>/.jax_cache``), then the
  ``DL4J_TPU_COMPILE_CACHE_DIR`` fleet dial.
- :func:`maybe_enable` is the fleet seam: a no-op unless one of the two
  variables is set (tier-1 runs with both unset, so the cache is off by
  default), called from ``ModelRegistry.register`` (serving replicas) and
  the paramserver join/rejoin path (workers) — every process that is
  about to compile checks the dial once, so a fleet shares one cache dir
  by exporting one env var.
- :func:`take_persistent_hit` is jitwatch's claim protocol: a compile
  that was actually served from the disk cache is a *persistent* hit —
  fast, but still a jit-cache miss in-process — and the
  ``jit_persistent_cache_hits_total{fn=}`` counter keeps the bimodal
  ``jit_compile_seconds`` distribution honest (a fleet of disk-hit
  "compiles" must not read as a retrace problem).
- :func:`cache_stats` / :func:`gc_cache` back the ``cache`` CLI
  subcommand (``--stats`` / ``--gc``): stats walk the directory; GC
  evicts AOT warmup artifacts (``artifacts.py``) whose fingerprint no
  longer matches the running jax/backend — dry-run by default.

Cache-key honesty: jax's cache key already includes the jax version and
backend, so a stale entry is never *served* wrong — it is just dead
weight GC can drop. The AOT artifacts carry an explicit fingerprint for
the same reason (docs/../PERF.md "Compile-once fleet").
"""
from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Any, Dict, List, Optional

log = logging.getLogger(__name__)

__all__ = ["ENV_DIR", "JAX_ENV_DIR", "enable", "maybe_enable", "enabled",
           "cache_dir", "hits_count", "claim_persistent_hit", "suppress_events",
           "persistent_cache_counts", "cache_stats", "gc_cache"]

#: the fleet dial: one shared directory, exported to every worker and
#: serving replica. Unset (the tier-1 default) = cache off.
ENV_DIR = "DL4J_TPU_COMPILE_CACHE_DIR"

#: jax's own variable for the same thing. It outranks everything here:
#: jax reads it into ``jax_compilation_cache_dir`` itself, and
#: :func:`enable` then only adds thresholds, the latch reset and the
#: hit/miss listener.
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

#: jax.monitoring event names the listener counts
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# plain lock, deliberately NOT through the lockwatch factory: the
# listener fires inside jax's compile path (often under MonitoredJit
# bookkeeping) and instrumenting the cache's own counters would add
# lock-graph edges for a leaf mutex that can never nest
_LOCK = threading.Lock()
_STATE: Dict[str, Any] = {"dir": None, "listener": False,
                          "hits": 0, "misses": 0, "claimed": 0}
#: lock-free fast flag for the jitwatch hot path: False = the per-call
#: hit-window read is skipped entirely (the tier-1 default). One-element
#: list so the flip is a single atomic store.
_ENABLED_FAST = [False]


#: thread-local suppression for BACKGROUND compiles (the jitwatch cost
#: worker's abstract re-lowers): their disk hits are real but must not
#: enter the attribution pool, or a foreground compile racing one would
#: claim a hit it never had (jax fires monitoring events synchronously
#: on the compiling thread, so a thread-local flag is exact)
_SUPPRESS = threading.local()


@contextlib.contextmanager
def suppress_events():
    """Events raised by compiles inside this block (on this thread) are
    not counted — see ``_SUPPRESS``."""
    prev = getattr(_SUPPRESS, "on", False)
    _SUPPRESS.on = True
    try:
        yield
    finally:
        _SUPPRESS.on = prev


def _on_event(name: str, **kwargs) -> None:
    """jax.monitoring listener — must never raise into the compiler."""
    if getattr(_SUPPRESS, "on", False):
        return
    if name == _HIT_EVENT:
        with _LOCK:
            _STATE["hits"] += 1
    elif name == _MISS_EVENT:
        with _LOCK:
            _STATE["misses"] += 1


def _install_listener() -> None:
    with _LOCK:
        if _STATE["listener"]:
            return
        _STATE["listener"] = True
    from jax import monitoring
    monitoring.register_event_listener(_on_event)


def enable(cache_dir: Optional[str] = None) -> Optional[str]:
    """Turn jax's persistent compilation cache on and cache EVERY program
    — min-compile-time / min-entry-size thresholds zeroed, because the
    fleet's win is the *sum* of many small forward/pad programs, not one
    big step. The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is
    set (jax already points its config there: nothing here sets another),
    else ``cache_dir``, else the ``DL4J_TPU_COMPILE_CACHE_DIR`` dial.
    Idempotent; returns the active directory, or None when none of the
    three names one (or the one named cannot be created)."""
    from_jax_env = os.environ.get(JAX_ENV_DIR)
    d = from_jax_env or cache_dir or os.environ.get(ENV_DIR)
    if not d:
        return None
    d = os.path.abspath(d)
    with _LOCK:
        already = _STATE["dir"]
    if already == d:
        return d
    try:
        os.makedirs(d, exist_ok=True)
    except OSError as e:
        # the cache is an optimization: an unusable directory degrades
        # loudly to live compiles
        log.warning("compilecache: cannot use %s: %r", d, e)
        return None
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not from_jax_env:
        jax.config.update("jax_compilation_cache_dir", d)
    # the program's scopes and kernel names are op metadata, which jax
    # leaves out of the cache key by default: an entry compiled by a commit
    # that named things otherwise (or not at all) then serves ITS names to
    # this one's profiler traces and program texts. In the key, a trace
    # shows the names of the code that ran; the price is that a checkout at
    # another path, or with a line moved, compiles for itself.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # jax latches its cache decision at the FIRST compile: a process
    # that already compiled anything (backend init, an eager net build)
    # before this call would silently keep the cache OFF for its whole
    # lifetime — reset the latch so the next compile re-reads the config
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()
    _install_listener()
    with _LOCK:
        _STATE["dir"] = d
    _ENABLED_FAST[0] = True
    log.info("compilecache: persistent XLA compile cache at %s", d)
    return d


def maybe_enable() -> Optional[str]:
    """The fleet seam: :func:`enable` iff ``JAX_COMPILATION_CACHE_DIR`` or
    ``DL4J_TPU_COMPILE_CACHE_DIR`` is set. Cheap when neither is (no jax
    import, two env reads) so hot registration/join paths can call it
    unconditionally."""
    with _LOCK:
        if _STATE["dir"]:
            return _STATE["dir"]
    if not (os.environ.get(JAX_ENV_DIR) or os.environ.get(ENV_DIR)):
        return None
    return enable()


def enabled() -> bool:
    """Lock-free: read per monitored-jit call on the hot path."""
    return _ENABLED_FAST[0]


def cache_dir() -> Optional[str]:
    """The active cache directory (None = cache off)."""
    with _LOCK:
        return _STATE["dir"]


def hits_count() -> int:
    """The listener's raw hit count — jitwatch snapshots this BEFORE a
    monitored call so a detected compile can be attributed precisely
    (see :func:`claim_persistent_hit`). Deliberately LOCK-FREE: this
    runs on every monitored-jit call when the cache is on, and a shared
    mutex there would serialize all monitored callers on the steady-
    state hot path; a GIL-atomic read of the int is enough — the claim
    itself re-validates under the lock."""
    return _STATE["hits"]


def claim_persistent_hit(hits_before: int) -> bool:
    """Claim one persistent-cache hit for a compile the caller just
    detected, but only when (a) the hit counter GREW during the caller's
    own call window (``hits_before`` = :func:`hits_count` taken before
    the call — without the window, unrelated hits such as the jitwatch
    cost worker's background AOT re-compiles would be mis-attributed to
    foreground compiles that really paid XLA) and (b) an unclaimed hit
    remains (the jit-cache-size claim-the-delta protocol: N threads
    racing compiles claim at most the hits observed, so the process
    total is exact even when a concurrent hit+miss pair attributes a hit
    to the wrong fn)."""
    with _LOCK:
        if _STATE["hits"] > hits_before \
                and _STATE["claimed"] < _STATE["hits"]:
            _STATE["claimed"] += 1
            return True
        return False


def persistent_cache_counts() -> Dict[str, int]:
    """Raw listener counts {hits, misses} for this process (tests, the
    ``cache --stats`` CLI)."""
    with _LOCK:
        return {"hits": _STATE["hits"], "misses": _STATE["misses"]}


# ----------------------------------------------------------- stats & GC
def _artifact_paths(d: str) -> List[str]:
    from .artifacts import ARTIFACT_EXT
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return []
    return [os.path.join(d, n) for n in names if n.endswith(ARTIFACT_EXT)]


def _resolve_dir(cache_dir: Optional[str]) -> Optional[str]:
    return (os.path.abspath(cache_dir) if cache_dir
            else _STATE["dir"] or os.environ.get(JAX_ENV_DIR)
            or os.environ.get(ENV_DIR) or None)


def cache_stats(cache_dir: Optional[str] = None) -> Dict[str, Any]:
    """Directory census for ``cache --stats``: jax cache entries (the
    opaque ``*-cache`` files jax writes), AOT warmup artifacts
    (``*.dl4jaot``), total bytes, plus this process's live hit/miss
    counts when the cache is enabled here."""
    d = _resolve_dir(cache_dir)
    out: Dict[str, Any] = {"dir": d, "enabled": enabled(),
                           "entries": 0, "artifacts": 0, "bytes": 0,
                           "process": persistent_cache_counts()}
    if not d or not os.path.isdir(d):
        return out
    from .artifacts import ARTIFACT_EXT
    for name in os.listdir(d):
        path = os.path.join(d, name)
        try:
            size = os.path.getsize(path)
        except OSError:
            continue
        out["bytes"] += size
        if name.endswith(ARTIFACT_EXT):
            out["artifacts"] += 1
        elif not name.endswith("-atime") \
                and not name.endswith(ARTIFACT_EXT + ".tmp"):
            # exclude jax's access-time sidecars AND orphaned artifact
            # temp files (a killed export; gc_cache removes them) from
            # the jax-entry census
            out["entries"] += 1
    return out


def gc_cache(cache_dir: Optional[str] = None,
             dry_run: bool = True) -> Dict[str, Any]:
    """Evict AOT warmup artifacts whose manifest fingerprint no longer
    matches the RUNNING jax/backend (plus unreadable/corrupt artifacts —
    they can never install). Dry-run by default: the report lists what
    WOULD go; ``dry_run=False`` deletes. jax's own ``*-cache`` entries
    are left alone — their key already encodes the jax/backend version,
    so stale ones are merely unreferenced bytes, and deleting by key
    heuristics risks evicting a live fleet's warm entries."""
    from .artifacts import (ARTIFACT_EXT, read_manifest,
                            runtime_fingerprint)
    d = _resolve_dir(cache_dir)
    report: Dict[str, Any] = {"dir": d, "dry_run": bool(dry_run),
                              "scanned": 0, "kept": 0, "evicted": []}
    if not d or not os.path.isdir(d):
        return report
    fp = runtime_fingerprint()
    try:
        orphans = [os.path.join(d, n) for n in sorted(os.listdir(d))
                   if n.endswith(ARTIFACT_EXT + ".tmp")]
    except OSError:
        orphans = []
    for path in _artifact_paths(d) + orphans:
        report["scanned"] += 1
        reason = None
        if path.endswith(".tmp"):
            # a killed export's half-written temp file: never loadable,
            # invisible to _artifact_paths — GC is the only thing that
            # will ever clean it up
            reason = "orphaned export temp file"
        else:
            try:
                manifest = read_manifest(path)
            except Exception as e:
                reason = f"unreadable: {e!r}"
            else:
                if manifest.get("fingerprint") != fp:
                    reason = (f"fingerprint mismatch: artifact "
                              f"{manifest.get('fingerprint')} vs "
                              f"running {fp}")
        if reason is None:
            report["kept"] += 1
            continue
        entry = {"path": path, "reason": reason}
        if not dry_run:
            try:
                os.unlink(path)
                entry["removed"] = True
            except OSError as e:
                entry["removed"] = False
                entry["error"] = repr(e)
        report["evicted"].append(entry)
    return report

"""The counters the per-layer metrics read, snapshotted around the window.

From the program: the jitwatch table (compiles and compile seconds per
monitored jit function) and the registry's ``input_wait_seconds``. Beside
them the benchmark's own listeners on ``jax.monitoring``, which see
every backend compile of the process whoever asked for it (copied from
``chip_smoke.CompileLog``).
"""
from __future__ import annotations


class Counters:
    def __init__(self):
        self.backend_compiles = 0
        self.cache = {"requests": 0, "hits": 0, "misses": 0}

    def install(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _event(self, event, **kw):
        key = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}.get(event)
        if key:
            self.cache[key] += 1

    def snapshot(self):
        """Flat {name: number} of everything, for differences."""
        from deeplearning4j_tpu.monitor import get_registry
        from deeplearning4j_tpu.monitor.jitwatch import get_jit_registry

        out = {"backend_compiles": self.backend_compiles,
               "cache_requests": self.cache["requests"],
               "cache_hits": self.cache["hits"],
               "cache_misses": self.cache["misses"]}
        table = get_jit_registry().table()
        out["jit_compiles"] = sum(r["compiles"] for r in table.values())
        out["jit_compile_seconds"] = sum(r["compile_seconds"]
                                         for r in table.values())
        wait = [r["summary"] for r in
                get_registry().snapshot().get("input_wait_seconds", [])
                if r.get("summary")]
        out["input_wait_seconds"] = sum(s["mean_s"] * s["n"] for s in wait)
        out["input_waits"] = sum(s["n"] for s in wait)
        return out


def delta(after, before):
    return {k: after[k] - before.get(k, 0) for k in after}


def window_compiles(diff):
    """Compiles inside a window, from the difference of two snapshots: the
    largest of what jitwatch, the persistent cache's request counter and the
    backend's compile timer saw."""
    return max(diff["jit_compiles"], diff["cache_requests"],
               diff["backend_compiles"])

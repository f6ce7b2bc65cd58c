"""Compile-once fleet: persistent XLA compile cache + AOT warmup
artifacts (PERF.md "Compile-once fleet").

Two composing layers turn "every process recompiles the world at start"
into disk reads:

- ``cache.py`` — wiring of jax's persistent compilation cache behind
  ``JAX_COMPILATION_CACHE_DIR`` or the ``DL4J_TPU_COMPILE_CACHE_DIR``
  fleet dial (off by default), shared across paramserver workers and
  serving replicas, with a jax.monitoring listener so ``monitor/jitwatch.py`` can split
  true compiles from disk-cache-hit compiles
  (``jit_persistent_cache_hits_total{fn=}``).
- ``artifacts.py`` — an exporter that serializes a served model's
  closed bucket×precision compile set into one content-addressed
  artifact, and the loader ``ServedModel.warm(artifact=)`` uses to make
  cold start a deserialization instead of a recompile, falling back
  loudly (``compile_cache_miss`` flight event) on any fingerprint or
  topology mismatch.

Operate it with the ``cache`` CLI subcommand:
``python -m deeplearning4j_tpu cache --stats | --gc | --export``.
"""
from .cache import (ENV_DIR, JAX_ENV_DIR, cache_dir, cache_stats,
                    claim_persistent_hit, enable, enabled, gc_cache,
                    hits_count, maybe_enable, persistent_cache_counts)
from .artifacts import (ARTIFACT_EXT, ArtifactError,
                        export_warmup_artifact, load_warmup_artifact,
                        read_manifest, runtime_fingerprint, topology_hash,
                        try_install)

__all__ = [
    "ENV_DIR", "JAX_ENV_DIR", "enable", "maybe_enable", "enabled",
    "cache_dir", "hits_count", "claim_persistent_hit", "persistent_cache_counts",
    "cache_stats", "gc_cache",
    "ARTIFACT_EXT", "ArtifactError", "export_warmup_artifact",
    "load_warmup_artifact", "read_manifest", "runtime_fingerprint",
    "topology_hash", "try_install",
]

"""AOT warmup artifacts (the compile-once fleet, half 2).

``ModelRegistry.warm()`` already enumerates a served model's CLOSED
compile set — one forward variant per batch-bucket (× time-bucket ×
precision) signature (``ContinuousBatcher.compile_signatures``). That
enumerability is what makes ahead-of-time compilation possible: this
module walks the same set, lowers and compiles each signature, and
serializes the compiled executables (``jax.experimental.
serialize_executable``) into ONE content-addressed artifact file, keyed
by

- the model **topology hash** (sha256 of the configuration JSON — two
  nets with the same architecture share it; a changed layer does not),
- the **bucket signature set** + **precision** (the closed compile set
  the batcher will actually request),
- the **jax + backend version fingerprint** (an executable is only
  valid on the toolchain that produced it).

``ServedModel.warm(artifact=...)`` then turns a serving-replica cold
start (or a post-``scale_to`` rejoin) into deserialization instead of
compilation: every check above must match, and ANY mismatch or
corruption falls back LOUDLY to a live ``warm()`` — a
``compile_cache_miss`` flight event naming the reason, never a crash
and never a silently-wrong executable. Artifact-served forwards are the
same XLA program a live compile would produce, so predictions are
bit-identical (pinned in tests/test_compilecache.py).

Scope: framework nets (``MultiLayerNetwork`` / ``ComputationGraph``) —
duck-typed models have no jit seam to compile ahead of. Sequence models
with ``time_buckets`` export per (batch, time) bucket signatures
(masked forward); graphs with time buckets are refused at export (the
serving tier's masked path is MLN-shaped).
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import zipfile
from typing import Any, Dict, List, Tuple

import numpy as np

log = logging.getLogger(__name__)

__all__ = ["ARTIFACT_EXT", "ArtifactError", "runtime_fingerprint",
           "topology_hash", "export_warmup_artifact", "read_manifest",
           "load_warmup_artifact", "try_install"]

ARTIFACT_EXT = ".dl4jaot"
FORMAT = 1


class ArtifactError(RuntimeError):
    """The artifact cannot be used (corrupt, or fingerprint/topology/
    config mismatch). The loader converts this into the loud live-compile
    fallback — it never escapes ``warm(artifact=)``."""


def runtime_fingerprint() -> Dict[str, str]:
    """The toolchain identity an executable is only valid under: jax
    version + backend platform + backend version. Compared EXACTLY —
    a serialized XLA executable from another toolchain may load and then
    miscompute, so close does not count."""
    import jax
    from jax.extend.backend import get_backend
    be = get_backend()
    return {"jax": str(jax.__version__), "backend": str(be.platform),
            "backend_version": str(be.platform_version)}


def topology_hash(model) -> str:
    """sha256 of the model's configuration JSON (``conf.to_json()`` —
    architecture, not weights: an artifact serves any parameter values of
    the same topology, exactly like a live-compiled executable would).
    Duck models without a serde surface hash their class identity."""
    conf = getattr(model, "conf", None)
    to_json = getattr(conf, "to_json", None)
    if callable(to_json):
        material = to_json()
    else:
        material = f"{type(model).__module__}.{type(model).__qualname__}"
    return hashlib.sha256(material.encode("utf-8")).hexdigest()


def _is_graph(model) -> bool:
    return hasattr(model, "conf") and hasattr(model.conf, "vertices")


def _abstract(tree):
    """Array leaves → ShapeDtypeStruct (a data-free lowering signature);
    everything else passes through."""
    import jax

    def leaf(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return jax.ShapeDtypeStruct(tuple(shape), dtype)
        return x
    return jax.tree_util.tree_map(leaf, tree)


def _manifest_digest(manifest: Dict[str, Any]) -> str:
    """Content address: the manifest's identity material, canonically
    serialized. Weights are deliberately not part of the address — see
    :func:`topology_hash`."""
    material = json.dumps(
        {k: manifest[k] for k in ("topology", "precision", "signatures",
                                  "batch_buckets", "time_buckets",
                                  "fingerprint", "kind")},
        sort_keys=True)
    return hashlib.sha256(material.encode("utf-8")).hexdigest()


def export_warmup_artifact(served, out: str) -> str:
    """Serialize ``served``'s closed compile set into one artifact file.

    ``served``: a :class:`~deeplearning4j_tpu.serving.registry.ServedModel`
    hosting a framework net, with ``input_shape`` configured (the same
    requirement live ``warm()`` has). ``out``: a directory (the artifact
    gets its content-addressed name ``<model>-<digest16>.dl4jaot``) or an
    explicit file path. Returns the written path.

    The export warms the model first (idempotent — in-memory jit cache
    hits when already warm), then LOWERS each signature abstractly and
    compiles it ahead of time; with the persistent compile cache enabled
    those AOT compiles are themselves disk hits, so exporting from an
    already-warm cache dir is cheap."""
    import jax
    from jax.experimental import serialize_executable as se

    model = served.model
    if not hasattr(model, "_jit_output"):
        raise ValueError(
            f"model {served.name!r} ({type(model).__name__}) has no jit "
            f"forward seam — AOT artifacts cover framework nets only")
    if served.input_shape is None:
        raise ValueError(f"model {served.name!r}: export needs "
                         f"input_shape= at registration (same as warm())")
    graph = _is_graph(model)
    b = served.batcher
    sigs = b.compile_signatures(served.input_shape)
    if graph and any(masked for _, _, masked in sigs):
        raise ValueError(
            f"model {served.name!r}: time-bucketed (masked) "
            f"ComputationGraph export is not supported — the serving "
            f"masked forward is MultiLayerNetwork-shaped")
    # force the LIVE warm path: a model itself warmed from an artifact
    # serves warm()'s forwards out of its AOT table, which would leave
    # model._jit_output empty and nothing to lower — re-exporting (e.g.
    # refreshing an artifact after a toolchain upgrade evicted the old
    # one) must compile for real, so the AOT table steps aside here
    saved_aot = getattr(served, "_aot", {})
    served._aot = {}
    try:
        served.warm()        # wrappers exist + this process is warm
    finally:
        served._aot = saved_aot
    params_abs = _abstract(model.params)
    states_abs = _abstract(model.states)
    manifest: Dict[str, Any] = {
        "format": FORMAT,
        "name": served.name,
        "model_class": type(model).__name__,
        "kind": "graph" if graph else "mln",
        "topology": topology_hash(model),
        "precision": served.precision,
        "input_shape": list(served.input_shape),
        "batch_buckets": list(b._bb) if b._bb else None,
        "time_buckets": list(b._tb) if b._tb else None,
        "fingerprint": runtime_fingerprint(),
        "signatures": [{"shape": list(shape), "dtype": dt, "masked": m}
                       for shape, dt, m in sigs],
    }
    entries: List[Tuple[bytes, bytes]] = []
    for shape, dt, masked in sigs:
        wrapper = model._jit_output.get((False, masked))
        if wrapper is None:
            raise ArtifactError(
                f"model {served.name!r}: warm() left no forward wrapper "
                f"for masked={masked} — cannot lower that signature")
        xs = jax.ShapeDtypeStruct(tuple(shape), np.dtype(dt))
        mask = (jax.ShapeDtypeStruct((shape[0], shape[1]), np.float32)
                if masked else None)
        if graph:
            lowered = wrapper.lower(params_abs, states_abs, (xs,), None)
        else:
            lowered = wrapper.lower(params_abs, states_abs, xs, mask)
        payload, in_tree, out_tree = se.serialize(lowered.compile())
        entries.append((payload, pickle.dumps((in_tree, out_tree))))

    if os.path.isdir(out) or out.endswith(os.sep):
        os.makedirs(out, exist_ok=True)
        fname = f"{served.name}-{_manifest_digest(manifest)[:16]}" \
                f"{ARTIFACT_EXT}"
        path = os.path.join(out, fname)
    else:
        parent = os.path.dirname(os.path.abspath(out))
        os.makedirs(parent, exist_ok=True)
        path = out
    tmp = path + ".tmp"
    # write the zip straight to the temp file (serialized executables of
    # a real model run to many MB — no reason to stage the whole archive
    # in RAM first); os.replace keeps the atomicity: a killed export
    # leaves only a *.tmp orphan (gc_cache cleans those), never a
    # half-written artifact a later load would half-trust
    with zipfile.ZipFile(tmp, "w", compression=zipfile.ZIP_DEFLATED) as z:
        z.writestr("manifest.json", json.dumps(manifest, indent=2))
        for i, (payload, trees) in enumerate(entries):
            z.writestr(f"sig_{i}.bin", payload)
            z.writestr(f"sig_{i}.trees", trees)
    os.replace(tmp, path)
    log.info("compilecache: exported %d-signature warmup artifact for "
             "%r to %s", len(entries), served.name, path)
    return path


def read_manifest(path: str) -> Dict[str, Any]:
    """The artifact's manifest alone (GC and --stats read this without
    paying executable deserialization)."""
    with zipfile.ZipFile(path) as z:
        manifest = json.loads(z.read("manifest.json").decode("utf-8"))
    if manifest.get("format") != FORMAT:
        raise ArtifactError(f"unsupported artifact format "
                            f"{manifest.get('format')!r} (expected {FORMAT})")
    return manifest


def _read_entries(path: str, manifest: Dict[str, Any]
                  ) -> List[Tuple[bytes, Any]]:
    """Raw (payload, (in_tree, out_tree)) entries, one per signature, in
    manifest order. Unpickles the tree blobs — call ONLY after
    :func:`_verify` has accepted the manifest (deserialization is code
    execution; the gates must run first). Trust boundary: the cache dir
    itself is trusted infrastructure — anyone who can write it can
    already poison jax's own serialized cache entries — the verify-first
    ordering exists so a merely STALE or corrupt artifact is rejected
    without ever deserializing its payload."""
    entries = []
    with zipfile.ZipFile(path) as z:
        for i in range(len(manifest.get("signatures", []))):
            payload = z.read(f"sig_{i}.bin")
            trees = pickle.loads(z.read(f"sig_{i}.trees"))
            entries.append((payload, trees))
    return entries


def load_warmup_artifact(path: str
                         ) -> Tuple[Dict[str, Any], List[Tuple[bytes, Any]]]:
    """manifest + raw entries (see :func:`_read_entries` — the caller is
    responsible for verifying the manifest first when the artifact is
    untrusted; :func:`try_install` always does). Raises
    :class:`ArtifactError` (or the underlying OSError/BadZipFile) on any
    corruption."""
    manifest = read_manifest(path)
    return manifest, _read_entries(path, manifest)


def _verify(served, manifest: Dict[str, Any]) -> None:
    """Every gate an executable must pass before it may serve. Raises
    :class:`ArtifactError` naming the FIRST mismatch (the flight event's
    forensic payload)."""
    fp = runtime_fingerprint()
    if manifest.get("fingerprint") != fp:
        raise ArtifactError(f"fingerprint mismatch: artifact "
                            f"{manifest.get('fingerprint')} vs running {fp}")
    topo = topology_hash(served.model)
    if manifest.get("topology") != topo:
        raise ArtifactError(f"topology mismatch: artifact "
                            f"{manifest.get('topology', '')[:16]}… vs model "
                            f"{topo[:16]}…")
    if manifest.get("precision") != served.precision:
        raise ArtifactError(f"precision mismatch: artifact "
                            f"{manifest.get('precision')!r} vs served "
                            f"{served.precision!r}")
    b = served.batcher
    bb = list(b._bb) if b._bb else None
    tb = list(b._tb) if b._tb else None
    if manifest.get("batch_buckets") != bb or \
            manifest.get("time_buckets") != tb:
        raise ArtifactError(
            f"bucket mismatch: artifact ({manifest.get('batch_buckets')}, "
            f"{manifest.get('time_buckets')}) vs batcher ({bb}, {tb}) — "
            f"live traffic would pad to signatures the artifact lacks")


def _make_caller(loaded, kind: str):
    if kind == "graph":
        def call(params, states, x, mask):
            out = loaded(params, states, (x,), None)
            return out[0] if len(out) == 1 else list(out)
        return call

    def call(params, states, x, mask):
        return loaded(params, states, x, mask)
    return call


def try_install(served, path: str) -> bool:
    """Load ``path`` and install its executables as ``served``'s AOT
    forward table. True on success (a ``compile_cache_artifact_loaded``
    flight event records it); False on ANY failure, after recording a
    ``compile_cache_miss`` flight event with the reason — the caller
    (``ServedModel.warm``) then falls back to a live compile. Never
    raises: a bad artifact must cost a recompile, not an outage."""
    import jax
    from jax.experimental import serialize_executable as se
    from ..monitor.flightrec import get_flight_recorder
    try:
        # gate order matters: manifest checks BEFORE any entry
        # deserialization — a stale/tampered artifact is rejected without
        # unpickling a byte of its payload (_read_entries docstring)
        manifest = read_manifest(path)
        _verify(served, manifest)
        entries = _read_entries(path, manifest)
        aot = {}
        kind = manifest.get("kind", "mln")
        for sig, (payload, (in_tree, out_tree)) in zip(
                manifest["signatures"], entries):
            # export lowers every signature unsharded, so each entry is
            # a one-device program for the default device; left to its
            # default, the loader spreads it over every device of the
            # backend and a multi-device host rejects the arguments
            loaded = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=jax.devices()[:1])
            key = (tuple(int(d) for d in sig["shape"]),
                   str(sig["dtype"]), bool(sig["masked"]))
            aot[key] = _make_caller(loaded, kind)
    except Exception as e:
        log.warning("compilecache: artifact %s rejected for model %r "
                    "(%r) — falling back to live compile", path,
                    served.name, e)
        get_flight_recorder().record(
            "compile_cache_miss", model=served.name, artifact=path,
            reason=repr(e))
        return False
    served._aot = aot
    if served.input_shape is None and manifest.get("input_shape"):
        # the artifact knows the trailing shape warm() was exported with;
        # adopting it lets a loader-only replica still warm its pad jits
        served.input_shape = tuple(int(d)
                                   for d in manifest["input_shape"])
    get_flight_recorder().record(
        "compile_cache_artifact_loaded", model=served.name, artifact=path,
        signatures=len(aot))
    log.info("compilecache: model %r warm from artifact %s "
             "(%d signatures, zero compiles)", served.name, path, len(aot))
    return True

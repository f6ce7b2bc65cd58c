"""What the benchmark knows about the device: the table of published peaks,
how a result line names the device, and the peak of its memory."""
from __future__ import annotations

import math

#: published per-chip peaks, keyed by ``jax.devices()[0].device_kind``
#: (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).
#: Copied from ``bench.DEVICE_PEAKS``: the yardstick may not import from a
#: file that later PRs edit. A kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(kind):
    if kind not in PEAKS:
        raise SystemExit(f"no published peaks for device_kind {kind!r} "
                         f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]


def describe(devices):
    """The device as JAX reports it."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak bytes on the fullest device, as its allocator counts them: the
    peak of live buffers plus the peak of what executables reserved for
    their temporaries. On the v5e the two are counted apart
    (``peak_bytes_in_use`` read 0.87 GB after ResNet50 b256 training while
    ``peak_bytes_reserved`` held the step's 8.8 GB; my chip run, PR 22).
    0 where the backend reports nothing (the CPU)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def bytes_in_use(devices):
    """Bytes of live buffers on the fullest device, now: the allocator's
    ``bytes_in_use``, or the sum over ``jax.live_arrays()`` of the shards
    each device holds where the backend reports none (the CPU). A sample at
    one moment, between computations: the allocator's *peak* is the
    window's and cannot tell what a later check held."""
    import jax

    stats = [d.memory_stats() or {} for d in devices]
    if all("bytes_in_use" in s for s in stats):
        return max(int(s["bytes_in_use"]) for s in stats)
    held = {d: 0 for d in devices}
    for array in jax.live_arrays():      # by shape: a shard's .data is an
        shard = array.sharding.shard_shape(array.shape)   # array of its own
        for d in array.sharding.device_set & held.keys():
            held[d] += math.prod(shard) * array.dtype.itemsize
    return max(held.values())


def delete(tree):
    """Frees the device buffers of every array in ``tree`` now, whoever
    still refers to them."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def largest_program_bytes(devices):
    """Bytes the largest live executable needs while it runs (arguments +
    outputs - aliased + temporaries), from the compiler's own account; None
    where the backend gives none."""
    best = None
    for exe in devices[0].client.live_executables():
        try:
            stats = exe.get_compiled_memory_stats()
        except Exception:  # noqa: BLE001 - a backend without the account
            return None
        need = (stats.argument_size_in_bytes + stats.output_size_in_bytes
                - stats.alias_size_in_bytes + stats.temp_size_in_bytes)
        best = need if best is None else max(best, need)
    return best


def live_program_texts(devices):
    """HLO text of every live executable, largest programs first."""
    texts = []
    for exe in devices[0].client.live_executables():
        for mod in exe.hlo_modules():
            texts.append(mod.to_string())
    return sorted(texts, key=len, reverse=True)

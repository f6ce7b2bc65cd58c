"""What the benchmark knows about the device: the table of published peaks,
how a result line names the device, and the peak of its memory."""
from __future__ import annotations

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

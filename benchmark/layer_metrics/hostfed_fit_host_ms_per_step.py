"""``fit_host_ms_per_step`` in the host-fed cells: the name differs because
a per-layer metric hangs on one end-to-end metric."""
from benchmark.layer_metrics.fit_host_ms_per_step import read  # noqa: F401

"""``device_idle_share`` in the host-fed cells: the name differs because a
per-layer metric hangs on one end-to-end metric."""
from benchmark.layer_metrics.device_idle_share import read  # noqa: F401

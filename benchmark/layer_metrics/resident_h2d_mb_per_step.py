"""``h2d_mb_per_step`` in the device-fed cells, where it should be about 0:
the name differs because a per-layer metric hangs on one end-to-end
metric."""
from benchmark.layer_metrics.h2d_mb_per_step import read  # noqa: F401

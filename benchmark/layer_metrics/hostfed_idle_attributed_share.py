"""``idle_attributed_share`` in the host-fed cells: the name differs because
a per-layer metric hangs on one end-to-end metric."""
from benchmark.layer_metrics.idle_attributed_share import read  # noqa: F401

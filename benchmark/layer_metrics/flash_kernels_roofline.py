"""The Pallas flash-attention kernels' share of their roofline in per cent:
the least time the chip could take for the products attention needs per step
(operations over peak FLOP/s; ``opcount/<config>.py: kernel_work`` counts no
bytes, the kernels' operands being values inside the program) over the
device time per step of all Mosaic custom calls (``pallas_ms_per_step``; the
flash kernels are the only ones such a program has). The time holds the
forward calls of recomputed blocks and whatever the causal kernels do not
skip, which the needed operations leave out: the share reads low by both.
The arithmetic is ``lstm_kernels_roofline``'s: a configuration's
``kernel_work`` over the Mosaic calls' time."""
from benchmark.layer_metrics.lstm_kernels_roofline import read  # noqa: F401

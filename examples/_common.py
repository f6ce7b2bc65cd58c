"""Shared example bootstrap.

``maybe_force_cpu()`` honors two knobs BEFORE the first framework import
(the virtual device count is read at backend initialization):

- ``DL4J_TPU_EXAMPLE_CPU=1``  — run the example on the CPU backend.
- ``DL4J_TPU_EXAMPLE_CPU=N``  (N > 1) — virtual N-device CPU mesh, so the
  parallel examples exercise their sharding without TPU hardware.

Combine with ``DL4J_TPU_EXAMPLE_SMALL=1`` for a quick smoke footprint.
"""
import os


def maybe_force_cpu():
    v = os.environ.get("DL4J_TPU_EXAMPLE_CPU", "").strip().lower()
    if v in ("", "0", "false", "no", "off"):
        return
    try:
        n = int(v)
    except ValueError:
        n = 1
    from deeplearning4j_tpu.compat import set_cpu_devices

    set_cpu_devices(max(n, 1))

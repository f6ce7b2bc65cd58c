"""Virtual CPU devices for tests, examples and the CPU-mesh workers."""
from __future__ import annotations

import os

import jax


def set_cpu_devices(n: int):
    """Configure an ``n``-device virtual CPU backend. Call before any jax
    computation (the config is read at backend initialization).

    Any inherited ``--xla_force_host_platform_device_count`` is STRIPPED
    from ``XLA_FLAGS`` first: test runners export it for their own device
    count, subprocesses inherit the environment, and a stale flag would
    fight the ``jax_num_cpu_devices`` config."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count=")]
    os.environ["XLA_FLAGS"] = " ".join(flags)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n))

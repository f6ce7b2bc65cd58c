"""Test configuration: force an 8-device CPU JAX backend.

The suite runs on the CPU (``JAX_PLATFORMS=cpu``); sharding is tested on a
virtual 8-device mesh. ``compat.set_cpu_devices`` configures it before any
backend is initialized and strips any inherited force-flag so spawned
worker subprocesses configure their own device count cleanly.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.compat import set_cpu_devices

set_cpu_devices(8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process / long-running tests excluded from tier-1")
    # Hang insurance: the tier-1 driver kills the run at 870s with NOTHING
    # on stderr — a deadlocked test (the exact bug class THR003/lockwatch
    # exists for) would eat the whole budget silently. Arm faulthandler to
    # dump EVERY thread's stack shortly before that deadline so a wedged
    # run leaves the lock-holder stacks behind. repeat=True keeps dumping
    # for genuinely longer local runs; exit stays False (the dump is
    # diagnostic, never the killer — the driver owns the timeout).
    import faulthandler
    try:
        timeout = float(os.environ.get("DL4J_TPU_TEST_HANG_DUMP_S", "840"))
    except ValueError:
        timeout = 840.0
    if timeout > 0:
        faulthandler.dump_traceback_later(timeout, repeat=True)


def pytest_unconfigure(config):
    import faulthandler
    faulthandler.cancel_dump_traceback_later()

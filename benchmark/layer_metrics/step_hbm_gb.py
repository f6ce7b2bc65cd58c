"""Gigabytes the largest compiled program of the process needs while it
runs (arguments + outputs - aliased + temporaries), from the compiler's
account of the executable: the step. The allocator's own peak is
``device.memory_peak_bytes`` of every result line."""
from benchmark import device


def read(run):
    need = device.largest_program_bytes(run.devices)
    return None if not need else need / 1e9

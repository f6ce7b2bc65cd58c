"""Seconds of set-up in ``ParallelWrapper``'s ``pw/place_model`` spans:
parameters, layer state and updater state placed over the mesh again at
the start of every ``ParallelWrapper.fit``, one span per warm-up ``fit``."""
from benchmark import setup_trace


def read(run):
    return setup_trace.seconds(run, ("pw/place_model",))

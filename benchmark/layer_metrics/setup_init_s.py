"""Seconds of set-up inside the program's ``init`` spans
(``ComputationGraph.init`` / ``MultiLayerNetwork.init``: the layers'
implementations, every leaf drawn on the host and handed to the device, the
updater's state), from the tracer's kept records cut to the run's set-up
(``setup_trace.records``): the reference check's network and a one-device
run's are built after the window and are not in it."""
from benchmark import setup_trace


def read(run):
    return setup_trace.seconds(run, ("init",))

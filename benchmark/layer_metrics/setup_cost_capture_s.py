"""Seconds of jitwatch's cost captures that ended inside set-up
(``jitwatch/cost_capture`` on its worker thread): the second lowering and
compile of every monitored program, which only ``/profile``'s cost rows
read. It runs beside the warm-up steps; what of it the benchmark waited for
is on standard error ("… for jitwatch's cost capture")."""
from benchmark import setup_trace


def read(run):
    return setup_trace.seconds(run, ("jitwatch/cost_capture",))

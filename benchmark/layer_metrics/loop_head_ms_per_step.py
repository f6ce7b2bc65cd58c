"""Device milliseconds per step, self time, of the step program's ops under
the looped output layer's ``head`` and ``exit_gate`` scopes
(``LoopLMOutputLayer``): the head's product, its cross-entropy and its two
gradient products once per pass, all where the loss runs forward since PR 31
(``output.weighted_xent``), and the gate's distribution forward and
backward. Nothing where the program has no such scope
(``op_scopes.ms_per_step``)."""
from benchmark import op_scopes


def read(run):
    return op_scopes.ms_per_step(run, "head", "exit_gate")

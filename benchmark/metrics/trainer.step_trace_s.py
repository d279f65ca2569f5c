"""Seconds JAX took to trace the trainer's step function into a jaxpr
(the Python of the model, the loss, the optimizer and every kernel's
wrapper): the step's ``jaxpr_trace_duration`` events in the program's
set-up record, summed until the record closed."""
from benchmark.trace import setup

UNIT, LAYER, MOVES, SOURCE = "s", "trainer", "setup_s", "program_span"


def read(run):
    return setup.step_s("trace_s")

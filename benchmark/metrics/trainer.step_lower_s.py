"""Seconds JAX took to lower the step's jaxpr to an MLIR module (every
``pallas_call``'s lowering to Mosaic among them): the step's
``jaxpr_to_mlir_module_duration`` events in the program's set-up record,
summed until the record closed."""
from benchmark.trace import setup

UNIT, LAYER, MOVES, SOURCE = "s", "trainer", "setup_s", "program_span"


def read(run):
    return setup.step_s("lower_s")

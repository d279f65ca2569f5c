"""Seconds of the step's backend compile: XLA's own on a checkout's first
run, the read of the executable from the persistent cache on a later one
(the cache's key is computed inside it either way). The step's
``backend_compile_duration`` events in the program's set-up record,
summed until the record closed."""
from benchmark.trace import setup

UNIT, LAYER, MOVES, SOURCE = "s", "trainer", "setup_s", "program_span"


def read(run):
    return setup.step_s("compile_s")

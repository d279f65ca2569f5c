"""Device time a step, on the first chip, of the operations whose scope
path puts them in phase ``optimizer``:
the optimizer (``bps.optimizer``: the inner transformation's update and
``apply_updates``).
``benchmark/trace/program.py::phase`` has the rule."""
from benchmark.trace import program

UNIT, LAYER, MOVES, SOURCE = "ms", "trainer", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return None if trace is None else trace.phase_ms("optimizer")

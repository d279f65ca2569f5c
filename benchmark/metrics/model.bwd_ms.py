"""Device time a step, on the first chip, of the operations whose scope
path puts them in phase ``backward``:
the model's backward pass (transposed operations under ``bps.model``),
without the recompute.
``benchmark/trace/program.py::phase`` has the rule."""
from benchmark.trace import program

UNIT, LAYER, MOVES, SOURCE = "ms", "model", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return None if trace is None else trace.phase_ms("backward")

"""Device time a step, on the first chip, of the operations whose scope
path puts them in phase ``forward``:
the model's forward pass: what lies under ``bps.model`` and is neither
transposed nor recomputed.
``benchmark/trace/program.py::phase`` has the rule."""
from benchmark.trace import program

UNIT, LAYER, MOVES, SOURCE = "ms", "model", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return None if trace is None else trace.phase_ms("forward")

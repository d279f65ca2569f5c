"""Device time a step, on the first chip, of the routed layers' routing:
the operations under ``bps.moe.route`` (router scores, top-k, the sort of
the chosen pairs, the gather of rows to the experts and of results back,
and their backward). Nothing where the program opens no such scope."""
from benchmark.trace import named, program

UNIT, LAYER, MOVES, SOURCE = "ms", "model", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return None if trace is None else named.scope_ms(trace, "bps.moe.route")

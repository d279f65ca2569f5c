"""Device time a step, on the first chip, of the Gated DeltaNet halves:
every operation whose scope path holds ``bps.gdn`` (the half's norm, the
projections, the convolution, the delta rule, the gated head norm;
forward, recompute and backward). Nothing where the program opens no such
scope."""
from benchmark.trace import named, program

UNIT, LAYER, MOVES, SOURCE = "ms", "model", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return None if trace is None else named.scope_ms(trace, "bps.gdn")

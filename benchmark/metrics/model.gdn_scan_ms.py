"""Device time a step, on the first chip, of the gated delta rules:
every operation whose scope path holds ``bps.gdn.scan`` (the decays, the
l2 norms, everything of the chunked form, kernels included if the program
has any; forward, recompute and backward). Nothing where the program
opens no such scope."""
from benchmark.trace import named, program

UNIT, LAYER, MOVES, SOURCE = "ms", "model", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return None if trace is None else named.scope_ms(trace, "bps.gdn.scan")

"""Device time a step, on the first chip, of the routed layers' plan: the
operations under ``bps.moe.route.plan``, everything that decides where rows
go and moves none (the router's product and its gradients, the scores,
top-k, the sort of the chosen pairs, the slice a row tile; forward,
recompute and backward). A part of ``model.moe_route_ms``. Nothing where
the program opens no such scope."""
from benchmark.trace import named, program

UNIT, LAYER, MOVES, SOURCE = "ms", "model", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return (None if trace is None
            else named.scope_ms(trace, "bps.moe.route.plan"))

"""Device time a step, on the first chip, of what the exchange does
besides its collectives: the operations under ``bps.exchange`` less the
collective ones among them. Where the exchange reduces the gradient
leaves as they are (an ICI-only mesh, every cell since PR 37) nothing is
packed and this reads 0.0; where flat buckets run (a ``dcn`` axis, a
custom reducer) it is their pack, scaling and unpack."""
from benchmark.trace import program, reduce

UNIT, LAYER, MOVES, SOURCE = "ms", "exchange", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    if trace is None or trace.phase_ms("exchange") is None:
        return None
    return trace.ms_per_step(sum(
        end - start for name, path, start, end in trace.ops
        if program.phase(path) == "exchange"
        and reduce.category(name) != "collective"))

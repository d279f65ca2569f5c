"""Device time a step, on the first chip, of what the exchange does
besides its collectives: the operations under ``bps.exchange`` (the
buckets' pack, scaling and unpack) less the collective ones among them."""
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

"""Median host time until ``DistributedTrainer.step`` returns (the
enqueue, not the step): the benchmark's span around the call."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "trainer", "tokens_per_s_chip", "host_clock"


def read(run):
    calls = run.spans.get("step")
    return 1e3 * statistics.median(calls) if calls else None

"""Median host time of one batch's placement onto the mesh: the
program's ``bps.feed.h2d`` span around ``prefetch_to_mesh``'s
``device_put``, on the producer thread, in the profiler's trace."""
import statistics

from benchmark.trace import program

UNIT, LAYER, MOVES, SOURCE = "ms", "input", "tokens_per_s_chip", "program_span"


def read(run):
    trace = program.of_run(run)
    values = program.durations_ms(trace.host, "bps.feed.h2d") if trace else []
    return statistics.median(values) if values else None

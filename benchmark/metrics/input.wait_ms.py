"""Median host time a step waits for its batch: the benchmark's span
around ``next(feed)`` on ``data.py::prefetch_to_mesh``."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "input", "tokens_per_s_chip", "host_clock"


def read(run):
    waits = run.spans.get("next")
    return 1e3 * statistics.median(waits) if waits else None

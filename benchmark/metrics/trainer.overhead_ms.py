"""Median host time of ``trainer.step`` outside the jitted call: the
program's ``bps.step`` span less the ``bps.dispatch`` span inside it
(batch placement, statistics, the step tag), in the profiler's trace."""
import statistics

from benchmark.trace import program

UNIT, LAYER, MOVES, SOURCE = "ms", "trainer", "tokens_per_s_chip", "program_span"


def read(run):
    trace = program.of_run(run)
    steps = trace.step_spans() if trace else []
    if not steps:
        return None
    return statistics.median(
        program.self_ns(s, trace.host, ("bps.dispatch",)) for s in steps) / 1e6

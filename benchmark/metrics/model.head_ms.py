"""Device time a step, on the first chip, of the language-model head:
every operation whose scope path holds ``bps.head`` (the final norm, the
head's products, the softmax, the pick and the sum, and where the program
forms the head's gradient beside them, ``bps.head.grad``; forward,
recompute and backward). Nothing where the program opens no such scope."""
from benchmark.trace import named, program

UNIT, LAYER, MOVES, SOURCE = "ms", "model", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return None if trace is None else named.scope_ms(trace, "bps.head")

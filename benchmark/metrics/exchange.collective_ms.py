"""Device time of the collective operations per step, on the first chip,
from the profiler's trace."""

UNIT, LAYER, MOVES, SOURCE = ("ms", "exchange",
                              "tokens_per_s_chip", "device_trace")


def read(run):
    if not run.chips or not run.chips[0].steps:
        return None
    chip = run.chips[0]
    return 1e3 * chip.seconds("collective") / chip.steps

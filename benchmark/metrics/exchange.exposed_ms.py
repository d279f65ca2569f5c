"""The part of the collective operations' time per step in which no other
operation runs on the first chip: what the exchange adds to the step."""

UNIT, LAYER, MOVES, SOURCE = ("ms", "exchange",
                              "tokens_per_s_chip", "device_trace")


def read(run):
    if not run.chips or not run.chips[0].steps:
        return None
    chip = run.chips[0]
    return 1e3 * chip.exposed_seconds("collective") / chip.steps

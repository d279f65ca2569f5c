"""Share of the traced window of whole steps in which no operation runs
on the first chip."""

UNIT, LAYER, MOVES, SOURCE = "%", "device", "tokens_per_s_chip", "device_trace"


def read(run):
    if not run.chips:
        return None
    chip = run.chips[0]
    return 100.0 * (1.0 - chip.busy_s / chip.window_s)

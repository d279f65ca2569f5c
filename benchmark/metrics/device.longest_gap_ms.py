"""The longest idle gap on the first chip in the traced window; the host
span that covers it is named in the result line's ``breakdown``."""

UNIT, LAYER, MOVES, SOURCE = "ms", "device", "step_ms_p95", "device_trace"


def read(run):
    if not run.chips:
        return None
    gaps = run.chips[0].idle_gaps()
    return max((b - a) / 1e6 for a, b in gaps) if gaps else 0.0

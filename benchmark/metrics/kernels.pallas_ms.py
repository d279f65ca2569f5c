"""Device time of the Pallas (Mosaic custom-call) kernels per step, on the
first chip: the flash-attention kernels are the only custom calls in these
steps."""

UNIT, LAYER, MOVES, SOURCE = "ms", "kernels", "tokens_per_s_chip", "device_trace"


def read(run):
    if not run.chips or not run.chips[0].steps:
        return None
    chip = run.chips[0]
    return 1e3 * chip.seconds("pallas") / chip.steps

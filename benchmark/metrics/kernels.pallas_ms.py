"""Device time of EVERY Pallas (Mosaic custom-call) kernel per step, on
the first chip. Six families run in the cells' steps: the flash-attention
kernels (``bps_flash_*``), the grouped expert products (``bps_gmm*``), the
routed rows' movement and the experts' function (``bps_moe_*``), the
state-space scan (``bps_ssd_*``) with the convolution and gated norm
beside it (``bps_ssm_*``), and the embedding's backward (``bps_embed_dw``).
A kernel that takes the place of XLA operations RAISES this while the step
falls (99.68 -> 114.20 ms in PR 41, where the step fell by 64.9): read it
beside the step's time, and a family by the metric of its own."""

UNIT, LAYER, MOVES, SOURCE = "ms", "kernels", "tokens_per_s_chip", "device_trace"


def read(run):
    if not run.chips or not run.chips[0].steps:
        return None
    chip = run.chips[0]
    return 1e3 * chip.seconds("pallas") / chip.steps

"""Model FLOP/s utilisation: tokens per second and chip of the traced
run's timed steps, times the operations a token requires
(``benchmark/flops.py``), over the chip's published bf16 peak."""

UNIT, LAYER, MOVES, SOURCE = "%", "model", "tokens_per_s_chip", "host_clock"


def read(run):
    if run.peaks is None:
        return None
    tokens_per_s_chip = (run.cell.tokens_per_step / run.trainer_step_s
                         / run.cell.chips)
    return (100.0 * tokens_per_s_chip * run.flops_per_token
            / run.peaks["bf16_flops_per_s"])

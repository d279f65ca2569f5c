"""Call sites whose trace took XLA's form of a kernel on the TPU without
being asked to (``bps_attn_xla``, ``bps_ssd_xla``, ``lax.ragged_dot``,
XLA's gathers for the routed rows): the program's ``note_choice`` counter
in its set-up record, by site, form and shapes. 0 off the TPU."""
from benchmark.trace import setup

UNIT, LAYER, MOVES, SOURCE = "count", "kernels", "tokens_per_s_chip", "program_counter"


def read(run):
    return setup.fallback_sites()

"""What the trainer's compiled step needs on a device, by the compiler's
own account of the executable the first step ran: arguments + outputs -
donated + temporaries + code (``step_memory[<step>]["peak"]`` of the
program's set-up record, 1e9 bytes). The line's
``device.memory_peak_bytes``, which the harness takes from outside, is the
same number."""
from benchmark.trace import account

UNIT, LAYER, MOVES, SOURCE = "GB", "trainer", "tokens_per_s_chip", "program_counter"


def read(run):
    return account.step_gb("peak")

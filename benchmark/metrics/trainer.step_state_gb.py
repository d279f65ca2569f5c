"""The compiled step's arguments on a device: parameters, optimizer state
and the batch as the step takes them, 12 bytes a parameter with AdamW
(``step_memory[<step>]["args"]`` of the program's set-up record, 1e9
bytes). Donated, so the outputs add nothing to the peak beside them."""
from benchmark.trace import account

UNIT, LAYER, MOVES, SOURCE = "GB", "trainer", "tokens_per_s_chip", "program_counter"


def read(run):
    return account.step_gb("args")

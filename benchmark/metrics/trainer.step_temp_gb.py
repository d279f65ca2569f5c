"""Everything the compiled step allocates itself on a device: the values
its forward keeps for its backward (``model.kept_gb``) and the passes'
working set (``step_memory[<step>]["temp"]`` of the program's set-up
record, 1e9 bytes)."""
from benchmark.trace import account

UNIT, LAYER, MOVES, SOURCE = "GB", "trainer", "tokens_per_s_chip", "program_counter"


def read(run):
    return account.step_gb("temp")

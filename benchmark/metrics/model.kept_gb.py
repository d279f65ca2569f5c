"""What the step's forward hands its backward on a device: the values the
layers' checkpoints keep by name (the flash kernels' outputs, the routed
plan, the experts' bf16 weights, ...), each checkpointed layer's input and
the custom derivatives' residuals (``kept["bytes"]`` of the program's
set-up record, read from the step's own jaxpr on this first asking; 1e9
bytes). A byte kept is an operation not recomputed: read it beside
``model.remat_ms``; ``trainer.step_temp_gb`` less this is the transient
working set."""
from benchmark.trace import account

UNIT, LAYER, MOVES, SOURCE = "GB", "model", "tokens_per_s_chip", "program_counter"


def read(run):
    return account.kept_gb()

"""Device time a step of the forward flash kernel, on the first chip:
the ``bps_flash_fwd`` events of the trace (the program's ``name=`` on the
``pallas_call``). Since PR 36 every block's checkpoint keeps the kernel's
output and row statistics, so it runs once a layer, in the forward pass;
under a checkpoint that keeps neither it runs again as the recompute."""
from benchmark.trace import program

UNIT, LAYER, MOVES, SOURCE = "ms", "kernels", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return (None if trace is None
            else trace.kernels_ms(program.FORWARD_KERNELS))

"""Device time a step of the backward flash kernels, on the first chip:
the ``bps_flash_bwd_fused`` events (one call for dq, dk and dv) and the
``bps_flash_bwd_dq`` and ``bps_flash_bwd_dkv`` events (the split form)."""
from benchmark.trace import program

UNIT, LAYER, MOVES, SOURCE = "ms", "kernels", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    return (None if trace is None
            else trace.kernels_ms(program.BACKWARD_KERNELS))

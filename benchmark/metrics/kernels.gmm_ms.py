"""Device time a step, on the first chip, of the grouped expert products:
the events of the kernels ``bps_gmm``, ``bps_gmm_dx`` and ``bps_gmm_dw``
(the program's ``name=`` on the ``pallas_call``). Nothing where the trace
holds no such kernel."""
from benchmark.trace import named, program

UNIT, LAYER, MOVES, SOURCE = "ms", "kernels", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    if trace is None or not trace.steps:
        return None
    by_kernel = named.ns_by_kernel(trace, named.GMM_PREFIX)
    if not by_kernel:
        return None
    return trace.ms_per_step(sum(ns for ns, _ in by_kernel.values()))

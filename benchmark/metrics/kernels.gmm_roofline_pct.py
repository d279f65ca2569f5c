"""The grouped expert products' share of their roofline over a step, on
the first chip: the least time the chip could take for the step's
``bps_gmm*`` calls (each the larger of its required operations over the
bf16 peak and its required bytes over the HBM peak, at the MEAN routed
rows, which the configuration's balanced choice holds a step to, by the
count the configuration names under ``kernel_counts`` and
``peaks.json``) over the device time they took. Nothing where the trace
holds no such kernel or the count does not fit the step's calls."""
from benchmark import harness
from benchmark.trace import named, program

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    if trace is None or run.peaks is None:
        return None
    by_kernel = named.ns_by_kernel(trace, named.GMM_PREFIX)
    if not by_kernel:
        return None
    counts = harness.named_count(run.cell, "kernel_counts")(
        run.cell.config["sizes"], run.cell.mix)
    share = program.roofline(by_kernel, counts, run.peaks,
                             trace.steps).get("all")
    return None if share is None else share["pct"]

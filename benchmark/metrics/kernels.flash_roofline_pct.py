"""The flash kernels' share of their roofline over a step, on the first
chip: the least time the chip could take for the step's flash calls
(each call the larger of its required operations over the bf16 peak and
its required bytes over the HBM peak, by the count the configuration
names under ``kernel_counts``, ``benchmark/kernel_counts.py`` where it
names none, and ``peaks.json``) over the device time the calls took.
Nothing where the count does not fit the step's calls."""
from benchmark.trace import program

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    if trace is None or run.peaks is None:
        return None
    share = program.flash_roofline(trace, run.cell, run.peaks).get("all")
    return None if share is None else share["pct"]

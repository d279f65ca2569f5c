"""The gated delta rules' share of their roofline over a step, on the
first chip: the least time the chip could take for them (the larger of
the recurrence's required operations over the bf16 peak and its operands
and results crossing HBM once a pass over the HBM peak, by the count the
configuration names under ``delta_count``, and ``peaks.json``) over the
device time under ``bps.gdn.scan``. Nothing where the program opens no
such scope or the configuration names no such count."""
from benchmark import harness, kernel_counts
from benchmark.trace import named, program

UNIT, LAYER, MOVES, SOURCE = "%", "model", "tokens_per_s_chip", "device_trace"


def read(run):
    trace = program.of_run(run)
    if trace is None or run.peaks is None:
        return None
    took_ms = named.scope_ms(trace, "bps.gdn.scan")
    if not took_ms or "delta_count" not in run.cell.config:
        return None
    count = harness.named_count(run.cell, "delta_count")(
        run.cell.config["sizes"], run.cell.mix)
    least, _ = kernel_counts.least_seconds(count, run.peaks)
    return 100.0 * least / (took_ms / 1e3)

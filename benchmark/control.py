"""Readings that the limits of ``correct`` are set from, and the control.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2

For every seed of ``--seeds`` it drives set-up as a run does (weights,
reference, the one trainer through its first steps) and prints the three
numbers ``correct`` compares. For every seed of ``--control-seeds`` it puts
the reference, computed in the nearest precision below the one the
configuration states (``float8`` for bfloat16), in the program's place
and prints the same three numbers: a limit has to lie between the two
sets. One process, so the seeds share every compiled program. Not part of
a benchmark run.
"""

import argparse
import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-precision", default="float8")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import byteps_tpu as bps
    from benchmark import correct, harness
    harness.use_compile_cache(ROOT)

    cell = harness.load_cell(ROOT, args.workload)
    mesh = harness.open_mesh(cell, require_chip=True)
    bps.init(mesh=mesh)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds:
        up = harness.set_up(cell, seed, mesh, harness.Spans(), True,
                            harness.Laps(time.time()))
        got = correct.readings(up.program, up.reference)
        harness.emit("program", seed=seed, reference_s=up.reference_s,
                     loss=up.program["loss"],
                     **{k: v[0] for k, v in got.items()},
                     where={k: v[1] for k, v in got.items()})
        up.close()
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        ref, params0, _, first = harness.seeded_inputs(cell, seed, mesh)
        reference = harness.reference_steps(cell, ref, params0, first, mesh)
        control = harness.reference_steps(cell, ref, params0, first, mesh,
                                          args.control_precision)
        got = correct.readings(control, reference)
        harness.emit("control", seed=seed, precision=args.control_precision,
                     loss=control["loss"],
                     **{k: v[0] for k, v in got.items()},
                     where={k: v[1] for k, v in got.items()})
        del params0
        gc.collect()
    bps.shutdown()


if __name__ == "__main__":
    main()

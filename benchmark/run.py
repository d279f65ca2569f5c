"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once on the TPU chips the cell asks
for and prints, as the last line of its standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, traced ``breakdown``, and last ``checks``: each number that
decided ``correct`` beside its limit, which are also the last lines of its
standard error. Earlier lines, one JSON object each, say what was compared
with what limit and where, and how many samples each number stands on. Without the chips it exits non-zero and prints no
result: it never falls back to the CPU.
"""

import time

T_START = time.time()     # before any heavy import: set-up starts here

import argparse           # noqa: E402
import json               # noqa: E402
import os                 # noqa: E402
import sys                # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import harness
    harness.use_compile_cache(ROOT)
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for name, check in result["checks"].items():    # stderr's last lines
        print(f"check {name}: value {check['value']} limit {check['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

"""Print what a profiler trace holds, for a look by hand:

    python3 benchmark/trace/dump.py <file.xplane.pb | directory>

every plane, every line with its number of events and the names that took
most time, then the reduction the per-layer metrics read."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(path: str) -> None:
    sys.path.insert(0, ROOT)
    from benchmark import harness
    from benchmark.trace import reduce
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = harness.newest_xplane(path)
    print("file", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            total, count, first = {}, 0, None
            for e in line.events:
                count += 1
                first = first if first is not None else e.start_ns
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
            print(f"  LINE {line.name!r}: {count} events, first at "
                  f"{first} ns")
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:12]:
                print(f"      {ns / 1e6:12.3f} ms  {name[:110]}")
    trace = reduce.read_xplane(path)
    for plane in reduce.device_planes(trace):
        chip = reduce.summarize(trace, plane)
        by_cat = {}
        for e in chip.ops:
            c = reduce.category(e.name)
            by_cat[c] = by_cat.get(c, 0.0) + e.dur_ns / 1e9
        print("SUMMARY", plane, "module", chip.step_module, "steps",
              chip.steps, "window_s", chip.window_s, "busy_s", chip.busy_s,
              "by_category_s", by_cat)
        print("   top gaps", chip.top_gaps(5))


if __name__ == "__main__":
    main(sys.argv[1])

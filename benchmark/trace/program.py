"""Read the PROGRAM's own names out of a JAX profiler trace: the step's
phases, the model's parts, the flash kernels and the ``bps.*`` host spans.

    python3 benchmark/trace/program.py <file.xplane.pb | directory> [workload]

``reduce.py`` keeps what the first nine metrics need: the benchmark's own
``bench.*`` host spans, and name, start and duration of device events.
This file reads the same trace a second time for what the program itself
wrote into it (PERF.md section 3 has the table of names):

- ``jax.named_scope`` names. The metadata of an ``XLA Ops`` event of a TPU
  plane carries JAX's name stack of the instruction (its ``op_name``) as
  the stat ``tf_op``, which ``xspace.py`` reads:
  ``jit(step)/.../bps.model/.../bps.attn/...`` with JAX's own ``jvp(...)``,
  ``transpose(...)``, ``checkpoint`` and ``rematted_computation`` in
  between. A fusion is one instruction and has ONE path, that of its root:
  work that XLA fused across a scope's border is counted where the
  fusion's root lies.
- the kernels' ``name=``: the TPU compiler names a custom call's
  instruction after the kernel, ``%bps_flash_fwd.3 = ... custom-call(...)``.
- ``jax.profiler.TraceAnnotation`` spans of the trainer and the feed, on
  the host plane's thread lines, with their arguments (``step_num``,
  ``bytes``) as stats.

Only the first chip's plane and the host plane are decoded, once a file
(``read`` is cached). The arithmetic is functions over plain ``(name, path, start_ns, end_ns)``
tuples so that tests drive it by hand.
"""

from __future__ import annotations

import dataclasses
import functools
import gzip
import json
import os
import statistics
import sys
import time
from typing import Iterable, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if __name__ == "__main__":          # run as a script: find the package
    sys.path.insert(0, ROOT)

from benchmark import kernel_counts                     # noqa: E402
from benchmark.trace import reduce, xspace              # noqa: E402

# the stat of an ``XLA Ops`` event's metadata that holds the instruction's
# op_name (read by hand on the chip in PR 25, PERF.md section 3)
PATH_STAT = "tf_op"
HOST_SPAN_PREFIX = "bps."
KERNEL_PREFIX = "bps_flash_"
FORWARD_KERNELS = ("bps_flash_fwd",)
BACKWARD_KERNELS = ("bps_flash_bwd_fused", "bps_flash_bwd_dq",
                    "bps_flash_bwd_dkv")
PHASES = ("forward", "remat", "backward", "optimizer", "exchange", "other")
PARTS = ("bps.embed", "bps.attn", "bps.mlp", "bps.head")

Op = Tuple[str, str, float, float]      # name, path, start_ns, end_ns


# ------------------------------------------------------------ arithmetic

def phase(path: str) -> str:
    """The phase of a device operation from its scope path, first match
    wins: ``exchange`` (under ``bps.exchange``), ``optimizer``
    (``bps.optimizer``), ``remat`` (``rematted_computation``: the forward
    run again inside the backward pass), ``backward`` (``transpose(``),
    ``forward`` (what else lies under ``bps.model``), ``other``. The
    recompute runs inside the transposed computation, so its path holds
    both ``transpose(`` and ``rematted_computation``: it is asked first."""
    if "bps.exchange" in path:
        return "exchange"
    if "bps.optimizer" in path:
        return "optimizer"
    if "bps.model" in path:
        if "rematted_computation" in path:
            return "remat"
        if "transpose(" in path:
            return "backward"
        return "forward"
    return "other"


def part(path: str) -> str:
    """The model's part an operation belongs to: the innermost of
    ``PARTS`` in its path, or ``"-"``."""
    best, at = "-", -1
    for name in PARTS:
        i = path.rfind(name)
        if i > at:
            best, at = name, i
    return best


def kernel(name: str) -> Optional[str]:
    """The flash kernel an ``XLA Ops`` event is a call of, from the
    instruction's name (``%bps_flash_fwd.3 = ...``), or None."""
    lhs = name.partition(" = ")[0].lstrip("%")
    if not lhs.startswith(KERNEL_PREFIX):
        return None
    head, _, tail = lhs.rpartition(".")
    return head if head and tail.isdigit() else lhs


def target(name: str) -> str:
    """The ``custom_call_target`` of a custom call's event (the kernels'
    is ``tpu_custom_call``; XLA's own ``AllocateBuffer`` is one too), or
    the instruction's short name where the event's name is cut short."""
    _, found, rest = name.partition('custom_call_target="')
    return rest.partition('"')[0] if found else reduce.short_name(name)


def named(ops: Iterable[Op]) -> bool:
    """Whether the program that was traced names its scopes at all (the
    parent of PR 25 does not: its readers then report nothing)."""
    return any("bps." in path for _, path, _, _ in ops)


def ns_by(ops: Iterable[Op], key) -> dict:
    """Summed durations of ``ops`` by ``key(op)``, in ns."""
    total: dict = {}
    for op in ops:
        k = key(op)
        total[k] = total.get(k, 0.0) + op[3] - op[2]
    return total


def ns_by_phase(ops: Iterable[Op]) -> dict:
    return {**dict.fromkeys(PHASES, 0.0),
            **ns_by(ops, lambda op: phase(op[1]))}


def ns_by_part_and_phase(ops: Iterable[Op]) -> dict:
    return ns_by(ops, lambda op: (part(op[1]), phase(op[1])))


def ns_by_kernel(ops: Iterable[Op]) -> dict:
    """``{kernel: (ns, calls)}`` of the flash kernels' events."""
    out: dict = {}
    for name, _, start, end in ops:
        k = kernel(name)
        if k is not None:
            ns, calls = out.get(k, (0.0, 0))
            out[k] = (ns + end - start, calls + 1)
    return out


def least_of_calls(count, calls: int, steps: int, peaks: dict):
    """(the least seconds the chip could take for a kernel's ``calls``
    calls in ``steps`` steps, which bound sets it). ``count`` is one
    ``{"flops", "bytes"}`` where every call is alike, or a list of
    ``{"flops", "bytes", "calls"}``, one entry a kind of call: then the
    list's least seconds, scaled by how many times the calls of a step
    hold the list. None where that is no whole number: the count does not
    describe this step, and a share made from it would be a guess."""
    if isinstance(count, dict):
        least, bound = kernel_counts.least_seconds(count, peaks)
        return least * calls, bound
    listed = sum(kind["calls"] for kind in count)
    if not listed or calls % steps or (calls // steps) % listed:
        return None
    of_list, bounds = 0.0, set()
    for kind in count:
        least, bound = kernel_counts.least_seconds(kind, peaks)
        of_list += least * kind["calls"]
        bounds.add(bound)
    return (of_list * (calls // listed),
            bounds.pop() if len(bounds) == 1 else "mixed")


def roofline(by_kernel: dict, counts: dict, peaks: dict,
             steps: int = 1) -> dict:
    """For each kernel of ``by_kernel`` (``(ns, calls)`` over ``steps``
    steps) with a count: its share of the roofline (the least seconds the
    chip could take for its calls over the seconds they took) and which
    bound sets it; under ``"all"`` the same over all of them together.
    A kernel whose count does not fit its calls (``least_of_calls``) has
    no share, and then neither has ``"all"``."""
    out, least_all, took_all, fits = {}, 0.0, 0.0, True
    for k, (ns, calls) in by_kernel.items():
        if k not in counts or not ns:
            continue
        least = least_of_calls(counts[k], calls, steps, peaks)
        if least is None:
            fits = False
            continue
        out[k] = {"pct": 100.0 * least[0] / (ns / 1e9), "bound": least[1]}
        least_all += least[0]
        took_all += ns / 1e9
    if took_all and fits:
        out["all"] = {"pct": 100.0 * least_all / took_all}
    return out


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    thread: int             # the line's place on the host plane
    start_ns: float
    end_ns: float
    args: dict              # the annotation's arguments: step_num, bytes


def children(span: HostSpan, spans: Iterable[HostSpan]) -> List[HostSpan]:
    """The spans on ``span``'s thread that lie inside it."""
    return [s for s in spans if s is not span and s.thread == span.thread
            and s.start_ns >= span.start_ns and s.end_ns <= span.end_ns]


def self_ns(span: HostSpan, spans: Iterable[HostSpan],
            names: Optional[tuple] = None) -> float:
    """``span``'s duration less the part of it that its children cover
    (all of them, or those called one of ``names``)."""
    inside = [(s.start_ns, s.end_ns) for s in children(span, spans)
              if names is None or s.name in names]
    return span.end_ns - span.start_ns - reduce.length(inside)


def durations_ms(spans: Iterable[HostSpan], name: str) -> list:
    return [(s.end_ns - s.start_ns) / 1e6 for s in spans if s.name == name]


# --------------------------------------------------------------- a trace

@dataclasses.dataclass
class Program:
    """What the program wrote into one trace, cut to the window of whole
    steps on the first chip."""
    plane: str
    window: tuple           # as reduce.summarize defines it
    steps: int
    ops: List[Op]           # XLA Ops events in the window, no containers
    containers: List[Op]    # the while / conditional / call events there
    host: List[HostSpan]    # bps.* spans that touch the window
    path_stat: Optional[str]    # the stat the paths were read from
    read_s: float = 0.0     # seconds this read took

    @property
    def busy_ns(self) -> float:
        return reduce.length((s, e) for _, _, s, e in self.ops)

    @functools.cached_property
    def by_phase(self) -> Optional[dict]:
        """ns by phase; None where the program names no scope."""
        return ns_by_phase(self.ops) if named(self.ops) else None

    @functools.cached_property
    def by_kernel(self) -> dict:
        return ns_by_kernel(self.ops)

    def ms_per_step(self, ns: float) -> float:
        return ns / 1e6 / self.steps

    def phase_ms(self, name: str) -> Optional[float]:
        """Device ms a step in one phase; None where the program names
        no scope."""
        if not self.steps or self.by_phase is None:
            return None
        return self.ms_per_step(self.by_phase[name])

    def kernels_ms(self, names: tuple) -> Optional[float]:
        """Device ms a step of the kernels called ``names``; None where
        the trace holds no flash kernel by name."""
        if not self.steps or not self.by_kernel:
            return None
        return self.ms_per_step(sum(self.by_kernel.get(k, (0.0, 0))[0]
                                    for k in names))

    def step_spans(self) -> List[HostSpan]:
        return [s for s in self.host if s.name == "bps.step"]

    def top_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of the chip, each named by the ``bps.*``
        span that covers most of it."""
        chip = reduce.DeviceSummary(
            self.plane, self.window, self.steps, "",
            [reduce.Event(name, s, e - s) for name, _, s, e in self.ops], [],
            [reduce.Event(h.name, h.start_ns, h.end_ns - h.start_ns)
             for h in self.host])
        return [[name.replace("no_benchmark_span", "no_bps_span"), s]
                for name, s in chip.top_gaps(n)]


def _trace_dict(path: str) -> dict:
    """``{"plane", "modules", "ops", "host", "path_stat"}`` as plain lists
    from an ``.xplane.pb``: chip 0's module and op lines (an op as
    ``[name, path, start_ns, dur_ns]``) and the host plane's ``bps.*``
    spans (``[name, thread, start_ns, dur_ns, args]``)."""
    raw = xspace.planes(path)
    devices = reduce.device_planes({"devices": [
        p for p in raw if p.startswith(reduce.DEVICE_PLANE)]})
    if not devices:
        raise ValueError(f"{path}: no {reduce.DEVICE_PLANE}<n> plane")
    chip = xspace.plane(raw[devices[0]], lambda line: line in (
        reduce.MODULE_LINE, reduce.OP_LINE))
    host = (xspace.plane(raw[reduce.HOST_PLANE])
            if reduce.HOST_PLANE in raw else None)
    doc = {"plane": chip.name, "modules": [], "ops": [], "host": [],
           "path_stat": None}
    paths: dict = {}
    for meta, stats in chip.metadata_stats.items():
        if PATH_STAT in stats:
            doc["path_stat"] = PATH_STAT
            # "name stack:op type", the type empty on these traces
            paths[meta] = str(stats[PATH_STAT]).rstrip(":")
    for line in chip.lines:
        if line.name == reduce.MODULE_LINE:
            doc["modules"] = [[e.name, e.start_ns, e.dur_ns]
                              for e in line.events]
        elif line.name == reduce.OP_LINE:
            doc["ops"] = [[e.name, paths.get(e.metadata_id, ""), e.start_ns,
                           e.dur_ns] for e in line.events]
    for thread, line in enumerate(host.lines if host else ()):
        for e in line.events:
            if e.name.startswith(HOST_SPAN_PREFIX):
                args = {k: v for k, v in e.stats.items()
                        if not k.startswith("_")}
                doc["host"].append([e.name, thread, e.start_ns, e.dur_ns,
                                    args])
    return doc


def of_dict(doc: dict) -> Program:
    """Cut a trace to the window of whole steps, which is
    ``reduce.summarize``'s: it is given chip 0's module and op lines and
    asked."""
    events = {reduce.MODULE_LINE: [reduce.Event(*m) for m in doc["modules"]],
              reduce.OP_LINE: [reduce.Event(n, s, d)
                               for n, _, s, d in doc["ops"]]}
    chip = reduce.summarize({"devices": {doc["plane"]: events}, "host": []},
                            doc["plane"])
    lo, hi = chip.window
    ops, containers = [], []
    for name, path, start, dur in doc["ops"]:
        if start >= lo and start + dur <= hi:
            (containers if reduce.category(name) == "container"
             else ops).append((name, path, start, start + dur))
    host = [HostSpan(n, t, s, s + d, dict(a)) for n, t, s, d, a in doc["host"]
            if s + d > lo and s < hi]
    return Program(doc["plane"], chip.window, chip.steps, ops, containers,
                   host, doc.get("path_stat"))


@functools.lru_cache(maxsize=2)
def read(path: str) -> Program:
    """The program's part of one ``.xplane.pb``; read once a path."""
    t0 = time.perf_counter()
    program = of_dict(_trace_dict(path))
    program.read_s = time.perf_counter() - t0
    return program


def dump_fixture(xplane: str, out: str) -> None:
    """Write what ``read`` keeps of a trace as gzipped JSON (the recorded
    fixture of the tests). As in ``reduce.dump_fixture`` an operation
    keeps its name and opcode and loses its shapes and operands."""
    doc = _trace_dict(xplane)
    for op in doc["ops"]:
        if " = " in op[0]:
            lhs = op[0].partition(" = ")[0].lstrip("%")
            op[0] = f"%{lhs} = _ {reduce.opcode(op[0])}()"
    with gzip.open(out, "wt") as f:
        json.dump(doc, f)


def load_fixture(path: str) -> Program:
    with gzip.open(path, "rt") as f:
        return of_dict(json.load(f))


# ------------------------------------------------------------- a run's

def root_of(dirs: Iterable[str]) -> str:
    """The checkout a cell's directories lie in: the nearest directory
    above the first of them that holds a ``BENCHMARK.json``."""
    d = os.path.abspath(next(iter(dirs)))
    while not os.path.exists(os.path.join(d, "BENCHMARK.json")):
        parent = os.path.dirname(d)
        if parent == d:
            raise FileNotFoundError(f"no BENCHMARK.json above {list(dirs)}")
        d = parent
    return d


def of_run(run) -> Optional[Program]:
    """The program's part of a traced run's trace, found as the harness
    finds it; None where the run has no device trace (the CPU tests)."""
    if not run.chips:
        return None
    from benchmark import harness
    directory = os.path.join(root_of(run.cell.dirs), "benchmark_out",
                             "trace", run.cell.name)
    try:
        return read(harness.newest_xplane(directory))
    except FileNotFoundError:
        return None


def flash_roofline(program: Program, cell, peaks: dict) -> dict:
    """``roofline`` of a trace's flash calls, by the count the cell's
    configuration names (``harness.named_count``)."""
    from benchmark import harness
    counts = harness.named_count(cell, "kernel_counts")(
        cell.config["sizes"], cell.mix)
    return roofline(program.by_kernel, counts, peaks, program.steps)


# ---------------------------------------------------------------- a dump

def _cell_of(root: str, workload: str):
    from benchmark import harness
    cell = harness.load_cell(root, workload)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    return cell, peaks


def report(program: Program, cell=None, peaks=None) -> str:
    """The breakdown-style dump, as text."""
    ms = program.ms_per_step
    out = [f"plane {program.plane}: {program.steps} steps, window "
           f"{(program.window[1] - program.window[0]) / 1e6:.3f} ms, busy "
           f"{ms(program.busy_ns):.3f} ms a step; paths from stat "
           f"{program.path_stat!r}; read in {program.read_s:.2f} s"]
    by_phase = ns_by_phase(program.ops)
    out.append("ms a step by phase:")
    for name in PHASES:
        out.append(f"  {name:10s} {ms(by_phase[name]):10.3f}")
    out.append(f"  {'sum':10s} {ms(sum(by_phase.values())):10.3f}   "
               f"(busy {ms(program.busy_ns):.3f})")
    out.append("ms a step by part x phase:")
    table = ns_by_part_and_phase(program.ops)
    out.append("  " + " ".join(f"{p:>10s}" for p in ("part",) + PHASES))
    for prt in PARTS + ("-",):
        out.append("  " + f"{prt:>10s} " + " ".join(
            f"{ms(table.get((prt, ph), 0.0)):10.3f}" for ph in PHASES))
    out.append("containers, ms a step:")
    for name, ns in sorted(ns_by(program.containers, lambda op:
                                 reduce.short_name(op[0])).items(),
                           key=lambda kv: -kv[1])[:6]:
        out.append(f"  {ms(ns):10.3f}  {name}")
    by_kernel = ns_by_kernel(program.ops)
    shares = flash_roofline(program, cell, peaks) if cell is not None else {}
    out.append("kernels, ms a step (calls a step; share of roofline, bound):")
    for k, (ns, calls) in sorted(by_kernel.items()):
        share = shares.get(k)
        out.append(f"  {ms(ns):10.3f}  {k} ({calls / program.steps:g}"
                   + (f"; {share['pct']:.1f} %, {share['bound']})"
                      if share else ")"))
    pallas = sum(e - s for n, _, s, e in program.ops
                 if reduce.category(n) == "pallas")
    out.append(f"  {ms(sum(ns for ns, _ in by_kernel.values())):10.3f}  "
               f"sum (custom calls in all {ms(pallas):.3f}"
               + (f"; all flash calls {shares['all']['pct']:.1f} % of their "
                  f"roofline)" if "all" in shares else ")"))
    others = ns_by((op for op in program.ops
                    if reduce.category(op[0]) == "pallas"
                    and kernel(op[0]) is None), lambda op: target(op[0]))
    out.append("custom calls under another name, ms a step by target: "
               + (", ".join(f"{t} {ms(ns):.4f}" for t, ns in others.items())
                  or "none"))
    out.append("longest idle gaps, us, and the bps.* span over most of each:")
    for name, seconds in program.top_gaps(10):
        out.append(f"  {seconds * 1e6:10.1f}  {name}")
    steps = program.step_spans()
    out.append(f"host spans ({len(steps)} bps.step), median ms:")
    for name in sorted({s.name for s in program.host}):
        values = durations_ms(program.host, name)
        out.append(f"  {statistics.median(values):10.4f}  {name} "
                   f"(n={len(values)})")
    if steps:
        out.append(f"  {statistics.median(self_ns(s, program.host) for s in steps) / 1e6:10.4f}"
                   f"  bps.step self time (less all its children)")
        out.append(f"  {statistics.median(self_ns(s, program.host, ('bps.dispatch',)) for s in steps) / 1e6:10.4f}"
                   f"  bps.step less bps.dispatch")
        out.append(f"  step numbers {[s.args.get('step_num') for s in steps][:8]}...")
    return "\n".join(out)


def main(argv) -> None:
    from benchmark import harness
    path = argv[0]
    workload = argv[1] if len(argv) > 1 else os.path.basename(
        os.path.normpath(path))
    if os.path.isdir(path):
        path = harness.newest_xplane(path)
    try:
        cell, peaks = _cell_of(ROOT, workload)
    except SystemExit:
        cell = peaks = None         # not a cell's trace: no roofline
    print(report(read(path), cell, peaks))


if __name__ == "__main__":
    main(sys.argv[1:])

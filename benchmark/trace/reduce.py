"""Reduce a JAX profiler trace to device busy/idle time, operation times
by category, idle gaps and the host spans that cover them.

What a trace of this program on a TPU v5e looks like (read by hand in
PR 24, see PERF.md): one plane per chip, ``/device:TPU:<n>``; on it the
line ``XLA Ops`` carries one event per executed HLO operation (name as
in the compiled module, start and duration in ns) and ``XLA Modules``
one event per executed program. ``/host:CPU`` carries one line per host
thread, with the benchmark's ``TraceAnnotation`` spans among the
events. All planes share one clock.

The arithmetic works on plain ``(start_ns, end_ns)`` intervals so that
tests can drive it with hand-made ones.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
from typing import Iterable, List, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


# ------------------------------------------------------------ intervals

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The parts of the union of ``a`` that no interval of ``b`` covers."""
    out: List[Interval] = []
    cover = merge(b)
    for lo, hi in merge(a):
        for c, d in cover:
            if d <= lo:
                continue
            if c >= hi:
                break
            if c > lo:
                out.append((lo, c))
            lo = max(lo, d)
            if lo >= hi:
                break
        if lo < hi:
            out.append((lo, hi))
    return out


def spans_of(events: Iterable[Event]) -> List[Interval]:
    return [(e.start_ns, e.end_ns) for e in events]


# ----------------------------------------------------------- categories

def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event. The trace names an event by
    the whole instruction, ``%name = shape opcode(operands), attrs``; a
    name without `` = `` is taken as it is."""
    _, sep, rhs = name.partition(" = ")
    if not sep:
        return name.lstrip("%").split("(")[0].split(".")[0]
    if rhs.startswith("("):             # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rhs = rhs[i + 1:].lstrip()
                break
    else:
        rhs = rhs.split(" ", 1)[1] if " " in rhs else rhs
    return rhs.split("(", 1)[0].strip()


def short_name(name: str) -> str:
    """``name opcode`` of an event, without shapes and operands."""
    lhs = name.partition(" = ")[0].lstrip("%")
    op = opcode(name)
    return lhs if op == lhs else f"{lhs} {op}"


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
CONTAINERS = ("while", "conditional", "call")   # they span their bodies' ops
DATA_MOVEMENT = ("copy", "bitcast", "transpose", "slice", "dynamic-slice",
                 "dynamic-update-slice", "concatenate", "broadcast", "pad",
                 "reshape", "copy-start", "copy-done", "slice-start",
                 "slice-done")


def category(name: str) -> str:
    """The kind of device operation an event is: ``collective``,
    ``pallas`` (a custom call: the Mosaic kernels are the only ones in
    these steps), ``fusion`` (XLA's fused matmuls and elementwise work),
    ``matmul`` (an unfused one), ``data_movement``, ``container`` or
    ``other``."""
    op = opcode(name)
    if op.startswith(COLLECTIVES):
        return "collective"
    if op == "custom-call":
        return "pallas"
    if op == "fusion":
        return "fusion"
    if op in ("convolution", "dot"):
        return "matmul"
    if op in DATA_MOVEMENT:
        return "data_movement"
    if op in CONTAINERS:
        return "container"
    return "other"


# ------------------------------------------------------------- reading

def read_xplane(path: str) -> dict:
    """``{"devices": {plane: {line: [Event]}}, "host": [Event]}`` from an
    ``.xplane.pb``: every line of every TPU plane, and the benchmark's
    own spans from the host plane."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            out["devices"][plane.name] = {
                line.name: [Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
                for line in plane.lines}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [Event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith(HOST_SPAN_PREFIX)]
    return out


def dump_fixture(trace: dict, path: str,
                 keep_lines=(OP_LINE, ASYNC_LINE, MODULE_LINE)) -> None:
    """Write a trace read by ``read_xplane`` as gzipped JSON (the recorded
    fixture of the tests). An operation keeps its name and opcode and
    loses its shapes and operands: ``%name = _ opcode()``."""
    def brief(e: Event):
        name = e.name
        if " = " in name:
            name = f"%{name.partition(' = ')[0].lstrip('%')} = _ {opcode(name)}()"
        return (name, e.start_ns, e.dur_ns)

    doc = {"devices": {p: {ln: [brief(e) for e in evs]
                           for ln, evs in lines.items() if ln in keep_lines}
                       for p, lines in trace["devices"].items()},
           "host": [dataclasses.astuple(e) for e in trace["host"]]}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def load_fixture(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return {"devices": {p: {ln: [Event(*e) for e in evs]
                            for ln, evs in lines.items()}
                        for p, lines in doc["devices"].items()},
            "host": [Event(*e) for e in doc["host"]]}


# ------------------------------------------------------------ reduction

@dataclasses.dataclass
class DeviceSummary:
    """One chip's part of a traced window of whole steps."""
    plane: str
    window: Interval            # first step's start to last step's end
    steps: int                  # executions of the step program in it
    step_module: str
    ops: List[Event]            # XLA Ops events in the window, no containers
    async_ops: List[Event]      # Async XLA Ops events: start-to-done spans
    host: List[Event]           # the benchmark's host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return length(spans_of(self.ops)) / 1e9

    def intervals(self, cat: str) -> List[Interval]:
        """Where operations of one category run: their events on the op
        line and, for what runs asynchronously, the start-to-done spans."""
        return spans_of(e for e in self.ops + self.async_ops
                        if category(e.name) == cat)

    def seconds(self, cat: str) -> float:
        return length(self.intervals(cat)) / 1e9

    def exposed_seconds(self, cat: str) -> float:
        """The part of ``cat``'s intervals in which no operation of
        another category runs on this chip."""
        rest = spans_of(e for e in self.ops if category(e.name) != cat)
        return length(subtract(self.intervals(cat), rest)) / 1e9

    def by_category_ms_per_step(self) -> dict:
        total: dict = {}
        for e in self.ops:
            cat = category(e.name)
            total[cat] = total.get(cat, 0.0) + e.dur_ns / 1e6 / self.steps
        return total

    def idle_gaps(self) -> List[Interval]:
        return subtract([self.window], spans_of(self.ops))

    def top_ops(self, n: int = 10) -> list:
        total: dict = {}
        for e in self.ops:
            key = short_name(e.name)
            total[key] = total.get(key, 0.0) + e.dur_ns / 1e9
        return sorted(([k, v] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        """The longest idle gaps, each named by the host span of the
        benchmark that covers most of it."""
        out = []
        for lo, hi in sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:n]:
            out.append([self.covering_span((lo, hi)), (hi - lo) / 1e9])
        return out

    def covering_span(self, gap: Interval) -> str:
        best, most = "no_benchmark_span", 0.0
        for e in self.host:
            over = min(e.end_ns, gap[1]) - max(e.start_ns, gap[0])
            if over > most:
                best, most = e.name, over
        return best


def summarize(trace: dict, plane: str) -> DeviceSummary:
    """The window of whole steps on one chip: from the start of the first
    execution of the step program (the module that took most time) to the
    end of its last."""
    lines = trace["devices"][plane]
    modules = lines.get(MODULE_LINE, [])
    if not modules:
        raise ValueError(f"{plane}: no {MODULE_LINE!r} line in the trace")
    total: dict = {}
    for e in modules:
        total[e.name] = total.get(e.name, 0.0) + e.dur_ns
    step_module = max(total, key=total.get)
    runs = [e for e in modules if e.name == step_module]
    window = (min(e.start_ns for e in runs), max(e.end_ns for e in runs))
    def inside(line):
        return [e for e in lines.get(line, [])
                if e.start_ns >= window[0] and e.end_ns <= window[1]
                and category(e.name) != "container"]

    host = [e for e in trace["host"]
            if e.end_ns > window[0] and e.start_ns < window[1]]
    return DeviceSummary(plane, window, len(runs), step_module,
                         inside(OP_LINE), inside(ASYNC_LINE), host)


def device_planes(trace: dict) -> list:
    return sorted(trace["devices"],
                  key=lambda p: int(p[len(DEVICE_PLANE):].split()[0]))

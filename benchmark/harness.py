"""One run of one cell: set-up, the checks that decide ``correct``, the
measured window, and the result line.

Driven by data. A cell is an entry of ``BENCHMARK.json``'s ``workloads``;
its configuration is the file the manifest names, its traffic mix is
``traffic/<traffic>.json`` and each per-layer metric is
``metrics/<metric>.py``, all found by name under the manifest's ``paths``,
as are the counts a configuration names (``named_count``). This file knows
no cell, configuration or metric by name.

The order of a run:

1. the weights, made on the device from ``--seed`` (the reference's
   ``make_params``), and the seeded stream of host batches;
2. the plain float32 reference follows the first ``CHECK_STEPS`` training
   steps (its time is reported, and is not part of ``setup_s``);
3. ONE ``DistributedTrainer`` is built on those weights and fed through
   ``prefetch_to_mesh``; its first ``CHECK_STEPS`` steps, by the very call
   and feed the window uses, are compared with the reference's
   (``correct.py``); it warms up;
4. the window drives that same trainer for ``--seconds``.

``setup_s`` is the seconds of set-up that lines of this repository own:
every lap of the ``setup`` line but ``OUTSIDE_LAPS`` (the TPU runtime's
start, the reference's steps, the dump and search of the step's text).
Those are printed a run and judged by nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib
import importlib.util
import json
import math
import os
import queue
import re
import threading
import time
from collections import defaultdict

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import correct, flops, generator, kernel_counts
from .trace import reduce as trace_reduce
from .trace.program import root_of

CHECK_STEPS = 3          # steps the reference follows
WARM_STEPS = 2           # further steps before the window opens
MAX_IN_FLIGHT = 4        # steps dispatched and not yet completed, at most
TRACE_SECONDS = 6.0      # length of the traced window of a --trace 1 run
ARM_STEPS = 8            # steps timed together in each arm of a traced run
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
# laps of set-up that are no part of ``setup_s``: no line of the repository
# decides the runtime's start, which wanders by seconds on one machine; the
# other two are the benchmark's own checks (PERF.md section 2)
OUTSIDE_LAPS = ("runtime_s", "reference_s", "step_text_s")


def use_compile_cache(root: str) -> None:
    """JAX's persistent compilation cache: where the machine says, else at
    a fixed path inside the checkout (the path is part of the cache's key),
    and for every program however quick its compile, so that a cell's
    second run compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def resolve(dotted: str):
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


def load_file(name: str, path: str):
    """The module in the file at ``path``, executed under ``name``: how the
    files a manifest's ``paths`` add (readers, counts) are loaded, whether
    or not their directory can be imported."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the counts the benchmark brings itself, by the name a configuration gives
COUNTS = {"transformer_lm": flops.transformer_lm,
          "benchmark.kernel_counts:of_cell": kernel_counts.of_cell}
# the name a configuration that gives none is read as, by its key
DEFAULT_COUNT = {"kernel_counts": "benchmark.kernel_counts:of_cell"}


def named_count(cell, key: str):
    """The function a cell's configuration names under ``key``
    (``flops_rule``: required operations a token; ``kernel_counts``: each
    kernel's operations and bytes a call): a name of ``COUNTS``, or
    ``module:function`` where the module is a file under the manifest's
    ``paths``, found from the checkout's root and loaded from that file as
    a metric's reader is, so that a configuration brings its count with it
    and cannot point outside the benchmark."""
    name = cell.config.get(key, DEFAULT_COUNT.get(key))
    if name in COUNTS:
        return COUNTS[name]
    module, _, attr = str(name).partition(":")
    path = os.path.normpath(
        os.path.join(root_of(cell.dirs), *module.split(".")) + ".py")
    count = None
    if os.path.exists(path) and any(
            path.startswith(os.path.abspath(d) + os.sep) for d in cell.dirs):
        count = getattr(load_file(
            "benchmark_count_" + module.replace(".", "_"), path), attr, None)
    if not callable(count):
        raise ValueError(
            f"{key} {name!r} is neither one of {sorted(COUNTS)} nor "
            f"module:function of a file under the manifest's paths")
    return count


# ----------------------------------------------------------------- cells

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration's file
    mix: dict               # the traffic mix's file
    dirs: list              # the manifest's ``paths``, absolute
    end_to_end: list        # names of the end-to-end metrics it reports
    per_layer: list         # names of the per-layer metrics it reports
    units: dict             # every metric's unit, by name

    @property
    def rows(self) -> int:
        return self.mix["batch_per_chip"] * self.chips

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.mix["seq"]


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it "
                         f"has {[w['name'] for w in manifest['workloads']]}")
    config = next(c for c in manifest["configs"]
                  if c["name"] == entry["config"])
    with open(os.path.join(root, config["file"])) as f:
        config_file = json.load(f)
    dirs = [os.path.join(root, p) for p in manifest["paths"]]
    mix = generator.load(generator.find(entry["traffic"], dirs))

    def reported(metrics):
        return [m["name"] for m in metrics
                if workload in m.get("workloads", [workload])]

    metrics = manifest["end_to_end"] + manifest["per_layer"]
    return Cell(workload, entry["chips"], config_file, mix, dirs,
                reported(manifest["end_to_end"]),
                reported(manifest["per_layer"]),
                {m["name"]: m["unit"] for m in metrics})


def load_metric(name: str, dirs):
    """The reader of one per-layer metric: ``metrics/<name>.py``."""
    for d in list(dirs) + [os.path.dirname(os.path.abspath(__file__))]:
        path = os.path.join(d, "metrics", name + ".py")
        if os.path.exists(path):
            return load_file("benchmark_metric_" + name.replace(".", "_"),
                             path)
    raise FileNotFoundError(f"no metrics/{name}.py under {list(dirs)}")


# --------------------------------------------------------------- program

def build_program(cell: Cell):
    """(model config, ``loss_fn(params, batch)``, optax transformation) of
    the system under test, from the configuration's ``program`` entry. A
    ``"$key"`` among the loss's arguments is that key of the traffic mix."""
    import optax
    prog = cell.config["program"]
    cfg = resolve(prog["config"])(**prog["config_kwargs"])
    loss = resolve(prog["loss"])
    kwargs = {k: cell.mix[v[1:]] if isinstance(v, str) and v[:1] == "$"
              else v for k, v in prog.get("loss_kwargs", {}).items()}

    def loss_fn(params, batch):
        return loss(params, cfg, batch, **kwargs)

    opt = dict(cell.config["optimizer"])
    return cfg, loss_fn, getattr(optax, opt.pop("name"))(**opt)


def first_gradient(opt_state, optimizer: dict):
    """The gradient the optimizer got at its first step, from its state
    after that step: Adam's first moment is then (1 - b1) times it."""
    import optax
    if not optimizer["name"].startswith("adam"):
        raise ValueError(f"no rule to read the first gradient from the "
                         f"state of {optimizer['name']!r}")
    mu = optax.tree_utils.tree_get(opt_state, "mu")
    return jax.tree_util.tree_map(
        lambda m: m / (1.0 - optimizer.get("b1", 0.9)), mu)


def plain_step(loss_fn, tx, mesh):
    """The plain-JAX arm: one jitted step; across chips per-shard
    gradients and ONE tree ``pmean`` (XLA's own all-reduce)."""
    import optax
    axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)

    def step(p, s, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        if axes:
            g, loss = jax.lax.pmean((g, loss), axes)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    if axes:
        step = jax.shard_map(step, mesh=mesh, in_specs=(P(), P(), P(axes)),
                             out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(step, donate_argnums=(0, 1))


# ----------------------------------------------------------------- spans

class Spans:
    """The benchmark's own host spans: seconds by name on the host clock,
    and a ``TraceAnnotation`` of the same name in the profiler's trace."""

    def __init__(self) -> None:
        self.seconds = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                trace_reduce.HOST_SPAN_PREFIX + name):
            yield
        self.seconds[name].append(time.perf_counter() - t0)


class Laps:
    """Seconds of set-up by phase, for the ``setup`` line: where a slow
    set-up was slow. Each lap runs from the end of the one before it, the
    first from ``t_start``, so together they cover the process's start
    to the window's."""

    def __init__(self, t_start: float) -> None:
        self.seconds = {}
        self._last = t_start

    def lap(self, name: str) -> float:
        now = time.time()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now
        return self.seconds[name]

    def total(self, outside=()) -> float:
        """The laps' sum, less the ones named ``outside``."""
        return sum(v for k, v in self.seconds.items() if k not in outside)


class CompileCounter:
    """Counts XLA compilations and persistent-cache retrievals."""

    def __init__(self) -> None:
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1


# ---------------------------------------------------------------- window

@dataclasses.dataclass
class Window:
    attempted: int
    failed: int
    seconds: float          # window start to the last step's completion
    first_s: float          # window start to the first step's completion
    intervals: list         # seconds between successive step completions
    compiles: int


def drive(trainer, feed, seconds: float, spans: Spans,
          compiles: CompileCounter) -> Window:
    """The training loop of the window: ``next(feed)`` then
    ``trainer.step``, with no sync in the loop beyond a bound of
    ``MAX_IN_FLIGHT`` steps on how far the host runs ahead (unbounded, the
    host would enqueue hundreds of steps in the window and the window
    would never end; with one step queued behind the running one, a host
    held up for a step's length idles the chip, which on a machine that
    shares its cores happened in one run of six). A helper thread blocks
    on each step's loss in order and stamps its completion."""
    stamps, losses, errors = [], [], []
    done: "queue.Queue" = queue.Queue()
    room = threading.Semaphore(MAX_IN_FLIGHT)

    def stamper() -> None:
        while True:
            loss = done.get()
            if loss is None:
                return
            with spans.span("block"):
                try:
                    jax.block_until_ready(loss)
                except Exception as e:          # a step that failed late
                    errors.append(repr(e))
            stamps.append(time.perf_counter())
            losses.append(loss)
            room.release()

    thread = threading.Thread(target=stamper, name="bench-stamper")
    before = compiles.count
    attempted = 0
    t0 = time.perf_counter()
    thread.start()
    try:
        while time.perf_counter() - t0 < seconds and not errors:
            room.acquire()
            with spans.span("next"):
                batch = next(feed)
            attempted += 1
            try:
                with spans.span("step"):
                    loss = trainer.step(batch)
            except Exception as e:              # counted, and the run ends
                errors.append(repr(e))
                room.release()
                break
            done.put(loss)
    finally:
        done.put(None)
        thread.join()
    values = [float(x) for x in jax.device_get(losses)] if losses else []
    bad = sum(not math.isfinite(x) for x in values)
    failed = min(attempted, len(errors) + bad)
    for e in errors:
        emit("window_error", error=e)
    return Window(attempted, failed,
                  (stamps[-1] - t0) if stamps else 0.0,
                  (stamps[0] - t0) if stamps else 0.0,
                  list(np.diff(stamps)), compiles.count - before)


# ------------------------------------------------------------------- run

def interval_lines(seconds_by_name: dict) -> dict:
    """Median and largest of each list of seconds, in ms, for the
    ``window`` line: a stall shows as a largest far from its median."""
    return {f"{name}_ms_{stat.__name__}": 1e3 * float(stat(values))
            for name, values in seconds_by_name.items() if len(values)
            for stat in (np.median, np.max)}


def newest_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"the profiler wrote no trace to {directory}")
    return max(files, key=os.path.getmtime)


def device_record(devices, memory_peak_bytes) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak_bytes}


def step_memory_bytes(compiled) -> int:
    """Peak device memory of the step program on one chip by the
    compiler's own account: arguments + outputs - aliased + temporaries +
    code. ``memory_stats()["peak_bytes_in_use"]`` does not see a
    program's temporaries on this runtime (PERF.md, PR 22)."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes
               + m.generated_code_size_in_bytes)


@dataclasses.dataclass
class SetUp:
    """What set-up hands to the window: the ONE trainer with its feed,
    and what its first steps and the reference's came to."""
    trainer: object
    feed: object
    program: dict           # the trainer's readings over CHECK_STEPS steps
    reference: dict         # the float32 reference's
    checks: list            # rows of correct.compare, and the step's text
    memory_peak: int
    reference_s: float
    first: list             # the host batches of those steps
    loss_fn: object
    tx: object
    ref: object             # the reference's module

    def close(self) -> None:
        self.feed.close()
        self.trainer.params = self.trainer.opt_state = None
        gc.collect()


def step_text_checks(text: str, wanted) -> list:
    """One row a name of ``wanted`` (a configuration's
    ``step_must_contain``): whether the compiled step's text holds it as a
    whole name, so that ``bps_gmm_dx`` does not stand for ``bps_gmm``.
    Before them a line of every ``bps_*`` kernel the text holds, with how
    many custom calls are its (an ``op_name`` that ends in
    ``<kernel>/pallas_call``): what a list is written from."""
    calls = defaultdict(int)
    for line in text.splitlines():
        call = "custom-call" in line and re.search(
            r'op_name="[^"]*\b(bps_\w+)/pallas_call[^"/]*"', line)
        if call:
            calls[call.group(1)] += 1
    emit("step_kernels", **dict(sorted(calls.items())))
    rows = []
    for needle in wanted:
        found = re.search(r"(?<!\w)" + re.escape(needle) + r"(?!\w)",
                          text) is not None
        rows.append({"check": "step_contains:" + needle, "value": int(found),
                     "limit": 1, "where": "compiled step", "ok": found})
    return rows


def open_mesh(cell: Cell, require_chip: bool):
    """The mesh over exactly the chips the cell asks for; no TPU, or
    another number of chips, ends the run."""
    from byteps_tpu.parallel.mesh import make_mesh
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) != cell.chips):
        raise SystemExit(
            f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"found {len(devices)} {devices[0].platform} device(s)")
    return make_mesh({"data": cell.chips}, devices=devices[:cell.chips])


def reference_steps(cell: Cell, ref, params0, first, mesh,
                    precision: str = "float32") -> dict:
    """The reference's readings over the first steps, in ``precision``
    (``float32`` is THE reference; a lower one is the control)."""
    optimizer = {k: v for k, v in cell.config["optimizer"].items()
                 if k != "name"}
    return ref.train_steps(
        params0, first, cell.config["sizes"], optimizer, cell.mix["kind"],
        cell.mix["reference_rows_per_block"] * cell.chips, precision,
        NamedSharding(mesh, P(None, "data")) if cell.chips > 1 else None)


def seeded_inputs(cell: Cell, seed: int, mesh):
    """Step 1 of a run: (the reference's module, the weights on the mesh,
    the endless stream of host batches, the first CHECK_STEPS of them
    again for the reference), all a function of the seed."""
    ref = importlib.import_module(cell.config["reference"])
    sizes = cell.config["sizes"]
    params0 = jax.device_put(ref.make_params(seed, sizes),
                             NamedSharding(mesh, P()))
    stream, twin = (generator.batches(cell.mix, sizes["vocab_size"],
                                      cell.chips, seed) for _ in range(2))
    return ref, params0, stream, [next(twin) for _ in range(CHECK_STEPS)]


def set_up(cell: Cell, seed: int, mesh, spans: Spans, require_chip: bool,
           laps: Laps) -> SetUp:
    """Steps 1 to 3 of a run (see the module's docstring)."""
    from byteps_tpu.data import prefetch_to_mesh
    from byteps_tpu.training import DistributedTrainer
    optimizer = cell.config["optimizer"]
    ref, params0, stream, first = seeded_inputs(cell, seed, mesh)
    jax.block_until_ready(params0)      # set-up's, not the reference's
    laps.lap("weights_s")

    # 2. the reference follows the first steps (not part of setup_s)
    reference = reference_steps(cell, ref, params0, first, mesh)
    gc.collect()
    reference_s = laps.lap("reference_s")
    emit("reference", seconds=reference_s, loss=reference["loss"])

    # 3. the one trainer, its first steps checked against the reference
    _, loss_fn, tx = build_program(cell)
    trainer = DistributedTrainer(loss_fn, params0, tx, mesh=mesh)
    feed = prefetch_to_mesh(stream, trainer.mesh)
    laps.lap("trainer_s")
    leaf_norms = jax.jit(ref.leaf_norms)
    change_norms = jax.jit(lambda p, p0: ref.leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, p, p0)))
    program = {"loss": []}
    checks = []
    for i in range(CHECK_STEPS):
        with spans.span("next"):
            batch = next(feed)
        if i == 0:
            compiled = trainer._step_fn.lower(
                trainer.params, trainer.opt_state, batch).compile()
            memory_peak = step_memory_bytes(compiled)
            laps.lap("lower_s")
            # the benchmark's own look at the step's text: no part of setup_s
            wanted = (cell.config["program"].get("step_must_contain", [])
                      if require_chip else [])
            if wanted:
                checks += step_text_checks(compiled.as_text(), wanted)
            del compiled
            laps.lap("step_text_s")
        with spans.span("step"):
            loss = trainer.step(batch)
        program["loss"].append(float(loss))
        if i == 0:
            program["grad_norm"] = np.asarray(leaf_norms(
                first_gradient(trainer.opt_state, optimizer)), np.float64)
    program["change_norm"] = np.asarray(
        change_norms(trainer.params, params0), np.float64)
    del params0
    gc.collect()
    laps.lap("check_steps_s")
    checks = correct.compare(program, reference,
                             cell.config["limits"]) + checks
    return SetUp(trainer, feed, program, reference, checks, memory_peak,
                 reference_s, first, loss_fn, tx, ref)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_chip: bool = True,
             trace_dir: str = None) -> dict:
    """Run one cell once and return the result line as a dict.
    ``require_chip=False`` is for the tests: it skips the look for a TPU
    and what only a TPU has (the kernel in the step, the peaks)."""
    import byteps_tpu as bps
    import byteps_tpu.parallel.mesh     # noqa: F401  open_mesh's, ahead of it
    laps = Laps(t_start)
    cell = load_cell(root, workload)
    imports_py_s = laps.lap("imports_py_s")     # jax, benchmark, byteps_tpu
    mesh = open_mesh(cell, require_chip)        # jax.devices() answers
    devices = list(mesh.devices.flat)
    compiles = CompileCounter()
    spans = Spans()
    bps.init(mesh=mesh)
    runtime_s = laps.lap("runtime_s")
    emit("cell", workload=workload, seed=seed, chips=cell.chips,
         rows=cell.rows, seq=cell.mix["seq"],
         targets_per_row=generator.targets_per_row(cell.mix),
         imports_s=imports_py_s + runtime_s, imports_py_s=imports_py_s,
         runtime_s=runtime_s)
    up = set_up(cell, seed, mesh, spans, require_chip, laps)
    trainer, feed, checks = up.trainer, up.feed, up.checks
    memory_peak = up.memory_peak
    for _ in range(WARM_STEPS):
        loss = trainer.step(next(feed))
    jax.block_until_ready(loss)
    laps.lap("warm_s")
    setup_s = laps.total(OUTSIDE_LAPS)
    # process_to_window_s: what setup_s was until PR 50, for the same run
    emit("setup", **laps.seconds, setup_s=setup_s,
         process_to_window_s=laps.total(("reference_s",)))
    spans.seconds.clear()

    # 4. the window
    result_metrics = {}
    device = device_record(devices, memory_peak)
    breakdown = None
    if not trace:
        window = drive(trainer, feed, seconds, spans, compiles)
        done = window.attempted - window.failed
        emit("window", steps=done, seconds=window.seconds,
             intervals=len(window.intervals),
             first_step_ms=1e3 * window.first_s, **interval_lines(
                 {"interval": window.intervals, **spans.seconds}))
        values = {
            "tokens_per_s_chip": done * cell.tokens_per_step
            / window.seconds / cell.chips if window.seconds else 0.0,
            "step_ms_p95": 1e3 * float(np.percentile(window.intervals, 95))
            if window.intervals else 0.0,
            "setup_s": setup_s}
        result_metrics = {k: values[k] for k in cell.end_to_end}
    else:
        trace_dir = trace_dir or os.path.join(root, "benchmark_out",
                                              "trace", workload)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            window = drive(trainer, feed, min(seconds, TRACE_SECONDS),
                           spans, compiles)
        finally:
            jax.profiler.stop_trace()
        readers = {name: load_metric(name, cell.dirs)
                   for name in cell.per_layer}
        needs = {n for r in readers.values() for n in getattr(r, "NEEDS", ())}
        run = traced_run(cell, spans, up, seed, trace_dir, require_chip,
                         plain_arm="plain_arm" in needs)
        if run.chips:
            device.update(busy_s=run.busy_s, window_s=run.window_s)
            breakdown = run.breakdown
        for name, reader in readers.items():
            value = reader.read(run)
            if value is not None:
                result_metrics[name] = float(value)
    checks.append({"check": "compiles_in_window", "value": window.compiles,
                   "limit": 0, "where": "window",
                   "ok": window.compiles == 0})
    for row in checks:
        emit("check", **row)
    up.close()
    bps.shutdown()
    result = {
        "correct": bool(all(r["ok"] for r in checks) and window.failed == 0
                        and window.attempted > 0),
        "attempted": window.attempted, "failed": window.failed,
        "metrics": {k: {"value": v, "unit": cell.units[k]}
                    for k, v in result_metrics.items()},
        "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # last in the line: each number compared, beside its limit
    result["checks"] = {r["check"]: {"value": printable(r["value"]),
                                     "limit": r["limit"]} for r in checks}
    return result


def printable(value):
    """A compared number as the result line can carry it: JSON has no
    infinity, which is what a gap over a NaN reads."""
    return value if math.isfinite(value) else repr(value)


# ------------------------------------------------------------ traced run

@dataclasses.dataclass
class TracedRun:
    """What a per-layer metric's reader is given."""
    cell: Cell
    spans: dict             # name -> seconds, the benchmark's host spans
    chips: list             # one trace_reduce.DeviceSummary per chip, or []
    trainer_step_s: float   # ARM_STEPS steps timed together, profiler off
    plain_step_s: float     # the same of the plain arm, or None
    flops_per_token: float
    peaks: dict             # this device's entry of peaks.json, or None

    @property
    def busy_s(self) -> float:
        return float(np.mean([c.busy_s for c in self.chips]))

    @property
    def window_s(self) -> float:
        return float(np.mean([c.window_s for c in self.chips]))

    @property
    def breakdown(self) -> dict:
        first = self.chips[0]
        return {"device_ops": first.top_ops(), "idle_gaps": first.top_gaps()}


def timed_steps(step, feed, n: int) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        loss = step(next(feed))
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / n


def traced_run(cell, spans, up: SetUp, seed, trace_dir, require_chip,
               plain_arm: bool) -> TracedRun:
    """After the traced window: reduce the trace, time the trainer's step
    with the profiler off and, where a metric of the cell needs it, the
    plain arm's (the trainer's state is freed first: two do not fit)."""
    chips = []
    if require_chip:
        trace = trace_reduce.read_xplane(newest_xplane(trace_dir))
        chips = [trace_reduce.summarize(trace, p)
                 for p in trace_reduce.device_planes(trace)]
        emit("trace", steps=chips[0].steps, window_s=chips[0].window_s,
             busy_s=chips[0].busy_s, step_module=chips[0].step_module,
             ms_per_step_by_category=chips[0].by_category_ms_per_step())
    trainer, feed, loss_fn, tx, ref = (up.trainer, up.feed, up.loss_fn,
                                       up.tx, up.ref)
    trainer_step_s = timed_steps(trainer.step, feed, ARM_STEPS)
    mesh = trainer.mesh
    plain_step_s = None
    if plain_arm:
        trainer.params = trainer.opt_state = None
        gc.collect()
        replicated = NamedSharding(mesh, P())
        state = {"p": jax.device_put(
            ref.make_params(seed, cell.config["sizes"]), replicated)}
        state["s"] = jax.device_put(tx.init(state["p"]), replicated)
        step = plain_step(loss_fn, tx, mesh)

        def plain(batch):
            state["p"], state["s"], loss = step(state["p"], state["s"],
                                                batch)
            return loss

        losses = [float(plain(trainer.shard_batch(b))) for b in up.first]
        gap, at = correct.loss_gap(up.program["loss"], losses)
        limit = cell.config["limits"]["trainer_vs_plain_loss_rel"]
        up.checks.append({"check": "trainer_vs_plain_loss_rel", "value": gap,
                          "limit": limit, "where": f"step {at}",
                          "ok": gap <= limit})
        plain_step_s = timed_steps(plain, feed, ARM_STEPS)
        state.clear()
    peaks = None
    if require_chip:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "peaks.json")) as f:
            table = json.load(f)
        kind = jax.devices()[0].device_kind
        if kind not in table:
            raise SystemExit(f"benchmark: device kind {kind!r} is not in "
                             f"peaks.json; add it with its source")
        peaks = table[kind]
    return TracedRun(
        cell, dict(spans.seconds), chips, trainer_step_s, plain_step_s,
        flops_per_token(cell), peaks)


def flops_per_token(cell: Cell) -> float:
    """Required operations a token of a training step, by the rule the
    cell's configuration names."""
    return named_count(cell, "flops_rule")(
        cell.config["sizes"], cell.mix["seq"],
        generator.targets_per_row(cell.mix))

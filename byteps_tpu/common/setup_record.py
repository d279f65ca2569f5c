"""A trainer's record of its own start: one plain dict (``open_record``),
open from the constructor's entry until the first ``step`` call returns
(``training._recorded``; docs/timeline.md).

- ``spans``: ``{name, start, end, parent, args, thread}`` on
  ``time.perf_counter``, each also a ``jax.profiler.TraceAnnotation``;
- ``compiles``: one entry a function JAX lowered or compiled meanwhile,
  joined from ``jax.monitoring``'s duration events by ONE process-wide
  listener: seconds by part, ``cache_hit``, the ``span`` open on its
  thread, ``step`` (is it the trainer's own step function), ``inside``
  (the trace it was nested in: its seconds are part of that trace's);
- ``recompiles``: the same for a compile inside the trainer's ``step``
  after the record closed, with the ``step_num`` it happened in;
- ``choices``: calls by ``(site, took)``; ``fallbacks``: calls by ``(site,
  took, shapes)`` that took XLA's form on a TPU unasked (``note_choice``);
- ``step_memory``: ``{fun_name: {args, out, alias, temp, code, peak}}``,
  the compiler's account of the executable the first step ran, bytes a
  device (``note_step_memory``: once, inside ``bps.setup.first_step``,
  under the span ``bps.setup.step_memory``; JAX's cached trace, lowering
  and executable, so no entry of ``compiles`` comes of it);
- ``kept``: ``{bytes, by_name: {name: [values, bytes]}, by_scope:
  {scope: bytes}}``, what the step's forward hands its backward
  (``common/kept_values.py``): None until somebody calls
  ``trainer.step_account()``, which walks the step's jaxpr once;
- ``trainer``: a weak reference, for a reader that is handed no trainer.
The listener runs when JAX compiles, ``note_choice`` when it traces:
nothing here runs in a step.
"""

import contextlib
import sys
from collections import Counter
import threading
import time

import jax

from .logging import get_logger

PARTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
         "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
         "/jax/core/compile/backend_compile_duration": "compile_s",
         "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
         "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s"}
# the arms that are XLA's form of a kernel, and an ``impl`` that asks for it
XLA_FORMS = ("xla", "ragged", "naive")
MAX_ENTRIES = 1024                  # of ``compiles`` and of ``recompiles``
# ``step_memory``'s parts, as ``CompiledMemoryStats`` names them
MEMORY_PARTS = {"args": "argument_size_in_bytes",
                "out": "output_size_in_bytes",
                "alias": "alias_size_in_bytes",
                "temp": "temp_size_in_bytes",
                "code": "generated_code_size_in_bytes"}

_current = None         # the open record that compiles and choices go to
_listening = False      # the ONE listener is registered
_warned = set()         # (site, shapes) whose fall-back has been said
_thread = threading.local()     # .traces, .entry, .cache: a compile in parts


def open_record(rec: dict = None) -> dict:
    """A new record (or ``rec`` again) as the one compiles and choices go to,
    and as the newest: ``GlobalState.setup_record`` where ``bps.init`` ran."""
    global _current, _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _current = rec or {
        "spans": [], "compiles": [], "recompiles": [], "choices": Counter(),
        "fallbacks": Counter(), "step_funs": (), "step_memory": {},
        "kept": None, "trainer": None, "closed": False}
    from .global_state import GlobalState
    if GlobalState._instance is not None:
        GlobalState._instance.setup_record = _current
    return _current


def close(rec: dict) -> None:
    global _current
    rec["closed"] = True
    if _current is rec:
        _current = None


def _open_span(rec: dict, tid: int):
    return next((s["name"] for s in reversed(rec["spans"])
                 if s["end"] is None and s["thread"] == tid), None)


@contextlib.contextmanager
def span(rec: dict, name: str, **args):
    tid = threading.get_ident()
    s = {"name": name, "start": time.perf_counter(), "end": None,
         "parent": _open_span(rec, tid), "args": args, "thread": tid}
    rec["spans"].append(s)
    try:
        with jax.profiler.TraceAnnotation(name, **args):
            yield s
    finally:
        s["end"] = time.perf_counter()


def note_step_memory(rec: dict, fun, *args):
    """The compiler's account of the executable ``fun`` ran on ``args``, the
    arguments of a dispatch that has returned: JAX hands ``trace``, ``lower``
    and ``compile`` what that dispatch made (no second lowering; given
    shapes in the arrays' place it would make one). ``peak`` is what the
    program needs on a device: arguments and outputs less the donated,
    temporaries, code. Returns the trace, whose jaxpr ``kept`` is read from
    on demand; a backend without an analysis writes nothing."""
    with span(rec, "bps.setup.step_memory"):
        traced = fun.trace(*args)
        stats = traced.lower().compile().memory_analysis()
        if stats is not None:
            sizes = {part: int(getattr(stats, field))
                     for part, field in MEMORY_PARTS.items()}
            sizes["peak"] = (sizes["args"] + sizes["out"] - sizes["alias"]
                             + sizes["temp"] + sizes["code"])
            rec["step_memory"][traced.fun_name] = sizes
    return traced


def _new_entry(name: str, tid: int, trace_s: float):
    """In the open record, else in the stepping trainer's ``recompiles``."""
    rec, entries = _current, None
    if rec is not None:
        entries, fields = rec["compiles"], {"span": _open_span(rec, tid)}
    frame = sys._getframe(2)
    while entries is None and frame is not None:
        if frame.f_code.co_name == "step":
            rec = getattr(frame.f_locals.get("self"), "_setup", None)
            if isinstance(rec, dict) and rec["closed"]:
                entries = rec["recompiles"]
                fields = {"step_num": frame.f_locals["self"].step_count}
        frame = frame.f_back
    if entries is None or len(entries) >= MAX_ENTRIES:
        return None
    entries.append(dict(
        fields, fun_name=name, step=name in rec["step_funs"], thread=tid,
        trace_s=trace_s, lower_s=0.0, compile_s=0.0, cache_hit=False))
    return entries[-1]


def _on_duration(event: str, secs: float, fun_name: str = "", **_) -> None:
    part = PARTS.get(event)
    if part is None:
        return
    now, tid, t = time.perf_counter(), threading.get_ident(), _thread.__dict__
    if part == "trace_s":
        t.setdefault("traces", {})[fun_name] = secs
        for e in reversed(_current["compiles"] if _current else ()):
            if e["wall"] < now - secs:
                break
            if e["thread"] == tid:          # compiled inside this trace
                e["inside"] = fun_name
    elif part.startswith("cache"):          # inside a backend compile
        t.setdefault("cache", {"cache_hit": True})[part] = secs
    else:
        name = fun_name[fun_name.find("(") + 1:-1]      # "jit(step)"
        entry = t.pop("entry", None)
        if part == "lower_s" or entry is None or entry["fun_name"] != name:
            # lowering traces further functions: the newest of this name
            entry = _new_entry(name, tid, t.pop("traces", {}).get(name, 0.0))
        cache = t.pop("cache", {}) if part == "compile_s" else {}
        if entry is not None:
            entry.update(cache, **{part: secs, "wall": now})
            if part == "lower_s":
                t["entry"] = entry          # its backend compile follows


def note_choice(site: str, took: str, shapes, why: str = "",
                asked: str = "auto") -> None:
    """Called where a trace chooses between a kernel family and XLA's form:
    counted in the open record; a fall-back on a TPU that nobody asked for
    also with its shapes, and said once a site and shape."""
    rec = _current
    if rec is not None:
        rec["choices"][site, took] += 1
    if (took not in XLA_FORMS or asked in XLA_FORMS
            or jax.default_backend() != "tpu"):
        return
    if rec is not None:
        rec["fallbacks"][site, took, shapes] += 1
    if (site, shapes) not in _warned:
        _warned.add((site, shapes))
        get_logger().warning("%s %s falls back to %s on the TPU (%s)",
                             site, shapes, took, why)

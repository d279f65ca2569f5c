"""Read the PROGRAM's own record of its set-up: what the trainer's start
took, what JAX compiled for it and which kernels its trace chose
(``byteps_tpu/common/setup_record.py``; PERF.md section 3 has the table).

The record is the newest trainer's, found where the program hangs it
(``GlobalState.setup_record``), since a metric's reader is handed no
trainer. A program that keeps no such record reads as nothing: every
function here then returns None, and the line leaves the metric out.
"""

from typing import Optional

STEP_PARTS = ("trace_s", "lower_s", "compile_s")


def record() -> Optional[dict]:
    """The closed record of the run's trainer, or None."""
    try:
        from byteps_tpu.common.global_state import GlobalState
    except ImportError:
        return None
    rec = getattr(GlobalState._instance, "setup_record", None)
    return rec if isinstance(rec, dict) and rec.get("closed") else None


def span_s(name: str) -> Optional[float]:
    """Seconds of the record's span ``name``."""
    rec = record()
    spans = [s for s in rec["spans"] if s["name"] == name] if rec else []
    return sum(s["end"] - s["start"] for s in spans) if spans else None


def step_s(part: str) -> Optional[float]:
    """Seconds of one part (``STEP_PARTS``) of compiling the trainer's own
    step function, summed over its entries before the record closed,
    whoever called ``lower`` on it."""
    rec = record()
    if rec is None:
        return None
    return sum(e[part] for e in rec["compiles"] if e["step"])


def step_lowerings() -> Optional[int]:
    """Times the step function went from a jaxpr to an MLIR module."""
    rec = record()
    if rec is None:
        return None
    return sum(e["step"] and e["lower_s"] > 0 for e in rec["compiles"])


def other_compile_s() -> Optional[float]:
    """Trace, lower and compile seconds of every other function compiled
    while the record was open. One compiled inside another's trace is
    left out: its seconds are part of that trace's."""
    rec = record()
    if rec is None:
        return None
    return sum(e[part] for e in rec["compiles"]
               if not e["step"] and "inside" not in e for part in STEP_PARTS)


def fallback_sites() -> Optional[int]:
    """Call sites (site, form taken, shapes) whose trace took XLA's form of
    a kernel on a TPU without being asked to."""
    rec = record()
    return None if rec is None else len(rec["fallbacks"])

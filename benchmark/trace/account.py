"""Read the PROGRAM's own account of its step's memory from the trainer's
set-up record (``byteps_tpu/common/setup_record.py``; PERF.md section 3):
``step_memory``, the compiled step's bytes by the compiler's categories,
written once inside the first step, and ``kept``, what the step's forward
hands its backward, which the trainer reads from its step's jaxpr the
first time it is asked (here, so in a traced run alone: one walk a run,
printed on an ``account`` line with its seconds and those of the span
``bps.setup.step_memory``).

The record is found as ``benchmark/trace/setup.py`` finds it, the trainer
through the weak reference the record holds. A program that keeps no such
record, or whose record lacks the keys, reads as nothing: every function
here then returns None, and the line leaves the metric out.
"""

import json
import time
from typing import Optional

from benchmark.trace import setup

GB = 1e9


def step_gb(part: str) -> Optional[float]:
    """GB a device of one part (``args``, ``out``, ``alias``, ``temp``,
    ``code``, ``peak``) of the trainer's own step program."""
    rec = setup.record() or {}
    account = rec.get("step_memory", {})
    sizes = [account[f] for f in rec.get("step_funs", ()) if f in account]
    return sizes[0][part] / GB if sizes else None


def kept_gb() -> Optional[float]:
    """GB a device of the values the step's forward hands its backward."""
    rec = setup.record() or {}
    trainer = rec["trainer"]() if rec.get("trainer") else None
    if trainer is not None and rec.get("kept") is None:
        t0 = time.perf_counter()
        account = trainer.step_account()
        if account["kept"] is not None:
            print(json.dumps({
                "phase": "account", **account,
                "walk_s": time.perf_counter() - t0,
                "step_memory_s": setup.span_s("bps.setup.step_memory")}),
                flush=True)
    kept = rec.get("kept")
    return None if kept is None else kept["bytes"] / GB

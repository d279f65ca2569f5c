"""Chrome-trace timeline of communication intervals.

Reference: BYTEPS_TRACE_ON/START_STEP/END_STEP/DIR (global.cc:113-124),
per-(key, stage) interval recording (scheduled_queue.cc:105-123,
core_loops.cc:69-129), async dump to ``<dir>/<local_rank>/comm.json`` in
Chrome Trace Format (global.cc:469-564; docs/timeline.md).

Here each push_pull bucket emits one complete event per stage, keyed by
bucket index (pid = key, like the reference's per-key rows): DISPATCH
(program launch), REDUCE (dispatch → device completion, i.e. queue +
execution), CREDIT_BLOCK (credit-gate stall), and on the PS path
REDUCE_WAIT / COPYD2H / PS_PACK / PS_PUSH / PS_PULL / PS_UNPACK per
bucket, plus the streamed step tail's PS_H2D (per-leaf device_put as a
leaf's last covering bucket unpacks; pid = leaf index) and
PS_APPLY_CHUNK (per-bucket-group optimizer apply; pid = group index) —
overlap of those two with still-running PS_PULL rows is the pipeline
the chunked tail exists for (BPS_APPLY_CHUNKED=0 disables it).
The staged step HEAD adds PS_BWD_SEG (one span per jitted backward
segment; pid = segment index) and PS_D2H (per-leaf host
materialization inside the pack workers; pid = leaf index) — push-side
rows (PS_D2H/PS_PACK/PS_PUSH) starting before the last PS_BWD_SEG ends
is the head pipeline (BPS_BWD_STAGED=0 disables it).
The cross-step pipeline adds PS_XSTEP_GATE (per-segment wait for the
previous step's param-group applies; pid = segment index) and tags its
events with the TRUE owning step via record()'s explicit ``step`` —
step k's straggler tail records while the ambient step is already k+1,
and telemetry.cross_step_overlap groups per step
(BPS_CROSS_STEP=0 disables it).
The MPMD pipeline plane (byteps_tpu.pipeline) adds PP_FWD_SEG /
PP_BWD_SEG (one span per stage segment per microbatch; pid = stage
index — PP_BWD_SEG(stage k) overlapping PP_FWD_SEG(stage k+1) is the
1F1B schedule's existence proof) and PP_ACT_SEND / PP_ACT_RECV (one
span per boundary frame crossing to/from a neighbor stage's mailbox).
With ``BPS_TRACE_PROFILER=1`` the same step window also
captures a ``jax.profiler`` device trace into
``<trace_dir>/<local_rank>/profile`` — host spans land in comm.json
(reference schema, existing viewers work), device-side op timing in the
profiler trace.

Every stage above is a row of the eager ``push_pull`` engine or of the
PS path. The collective path (``DistributedTrainer``'s one jitted step,
the path the benchmark's cells run) records NONE of them: all it does
here is advance the step tag (``set_step``), which opens and closes the
profiler capture. What that path does is named inside the profiler's
own trace instead, on the device's clock: ``bps.step`` /
``bps.shard_batch`` / ``bps.dispatch`` / ``bps.stats`` (trainer) and
``bps.feed.source`` / ``bps.feed.h2d`` / ``bps.feed.wait``
(``prefetch_to_mesh``) on the host threads, the ``bps.*`` scopes and the
``bps_flash_*`` kernels on the device lines (docs/timeline.md,
"Profiling a job on the chip"; ``benchmark/trace/program.py`` reduces
such a trace).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional

from .common.config import Config


class Timeline:
    def __init__(self, config: Config) -> None:
        self.cfg = config
        self.enabled = config.trace_on
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._t0 = time.time()
        self.step = 0
        self._profiling = False
        self._flushed = False    # first flush truncates stale files;
        #                          later flushes merge (see flush())

    def _active(self) -> bool:
        return (self.enabled and
                self.cfg.trace_start_step <= self.step <= self.cfg.trace_end_step)

    def set_step(self, step: int) -> None:
        self.step = step
        if not self.enabled:
            return
        if (self.cfg.trace_profiler and not self._profiling
                and self.cfg.trace_start_step <= step
                <= self.cfg.trace_end_step):
            # device-side bridge: one jax.profiler capture over the same
            # window the host spans cover
            import jax
            outdir = os.path.join(self.cfg.trace_dir,
                                  str(self.cfg.local_rank), "profile")
            os.makedirs(outdir, exist_ok=True)
            try:
                # no Python tracer, as in the benchmark's traced runs: a
                # few MB for a few seconds instead of a Python trace
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(outdir, profiler_options=options)
                self._profiling = True
            except Exception as e:        # profiling must never kill a run
                from .common.logging import get_logger
                get_logger().warning("jax.profiler bridge failed: %s", e)
        if step == self.cfg.trace_end_step + 1:
            if self._profiling:
                import jax
                try:
                    jax.profiler.stop_trace()
                except Exception as e:   # a stop failure (disk full, dir
                    # removed) must neither kill the run nor lose the
                    # host-span timeline below
                    from .common.logging import get_logger
                    get_logger().warning("jax.profiler stop failed: %s", e)
                finally:
                    self._profiling = False
            self.flush()

    def record(self, name: str, stage: str, start_s: float, dur_s: float,
               key: int = 0, step: Optional[int] = None,
               round: Optional[int] = None) -> None:
        """One complete ('X') event, microsecond timestamps like the
        reference (global.cc:489-538). ``step`` overrides the ambient
        step tag — cross-step pipelines record step k's straggler tail
        spans while the timeline has already advanced to k+1, and the
        per-step overlap aggregates need the true owner. ``round`` tags
        the span with its PS round number (PS_PUSH/PS_PULL) so the
        merged view and the critical-path analyzer can join it against
        the server's per-(key, round) span records exactly, instead of
        pairing positionally."""
        # gate on the event's TRUE owning step, not the ambient one: a
        # cross-step straggler tail records step k's spans after the
        # timeline advanced to k+1 — if k+1 left the trace window, an
        # ambient gate would silently drop the final window step's tail
        # (and the post-window flush-merge would have nothing to merge)
        owner = self.step if step is None else step
        if not (self.enabled and self.cfg.trace_start_step <= owner
                <= self.cfg.trace_end_step):
            return
        args = {"name": name, "step": owner}
        if round is not None:
            args["round"] = int(round)
        with self._lock:
            self._events.append({
                "name": stage, "ph": "X", "pid": key, "tid": 0,
                "ts": int((start_s - self._t0) * 1e6), "dur": int(dur_s * 1e6),
                "args": args,
            })

    def span(self, name: str, stage: str, key: int = 0,
             step: Optional[int] = None):
        """Context-manager form of ``record``. ``step`` passes through
        to ``record(step=)`` — cross-step tail code paths using spans
        would otherwise tag a straggler span with the AMBIENT (already
        advanced) step and corrupt ``cross_step_overlap``'s per-step
        grouping."""
        tl = self

        class _Span:
            def __enter__(self):
                self.t = time.time()
                return self

            def __exit__(self, *exc):
                tl.record(name, stage, self.t, time.time() - self.t, key,
                          step=step)
                return False

        return _Span()

    def snapshot(self) -> List[dict]:
        """Copy of the events recorded so far WITHOUT flushing — for
        in-process consumers (bench's exchange-tail breakdown, overlap
        tests) that want the spans before the trace file is written."""
        with self._lock:
            return list(self._events)

    def flush(self) -> None:
        with self._lock:
            events, self._events = self._events, []
        if not events:
            return
        rank = self.cfg.local_rank
        outdir = os.path.join(self.cfg.trace_dir, str(rank))
        os.makedirs(outdir, exist_ok=True)
        path = os.path.join(outdir, "comm.json")
        # MERGE with THIS process's earlier flushes instead of
        # truncating: flush() runs more than once per process (the
        # end-of-window flush, then an exit-time flush carrying the
        # cross-step pipeline's straggler tail spans recorded after
        # trace_end_step+1) — a plain rewrite would overwrite the whole
        # window with only the late events. The FIRST flush still
        # truncates: a comm.json left by a previous run has a different
        # t0 base, and merging it would double-count spans and pair
        # stages across unrelated runs.
        if self._flushed and os.path.exists(path):
            try:
                with open(path) as f:
                    prior = json.load(f).get("traceEvents", [])
            except (OSError, ValueError):
                prior = []      # unreadable/torn file: keep new events
            events = prior + events
        with open(path, "w") as f:
            # metadata.t0_unix_s anchors this rank's relative ts to the
            # wall clock — merge_trace uses it to place clock-aligned
            # SERVER span rows on the same axis (docs/observability.md)
            json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                       "metadata": {"t0_unix_s": self._t0,
                                    "rank": rank}}, f)
        self._flushed = True

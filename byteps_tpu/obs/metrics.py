"""Lock-cheap process-wide metrics registry.

The whole pipeline reports into one module-level ``MetricsRegistry``:
counters (monotonic), gauges (last value), and fixed-bucket latency
histograms with interpolated p50/p95/p99. Unlike the Chrome-trace
timeline (active only inside a configured step window), these are
ALWAYS on unless ``BPS_STATS=0`` — the design constraint is that one
observation costs a dict-free attribute hop plus one short per-metric
lock, cheap enough to sit on the exchange's per-bucket hot path
(gauged by the bench's ``BPS_STATS`` on/off A/B).

Metric objects are created on first use and live for the process; call
sites may cache them. ``BPS_STATS=0`` short-circuits inside
``inc``/``set``/``observe`` via a module flag, so cached handles honor
a later ``configure()`` (the bench A/B flips it between variants).

Every stage in docs/timeline.md's stage table is pre-registered as a
``stage/<NAME>`` histogram at import, so "which stages exist" is
answerable before (or without) any traffic.
"""

from __future__ import annotations

import bisect
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

# The Chrome-trace stage vocabulary (docs/timeline.md): one latency
# histogram per stage. PS-path stages are observed always (their call
# sites already take wall-clock timestamps); jit-path stages
# (DISPATCH/REDUCE/...) are only *measured* inside a trace window —
# the extra block_until_ready that gives them meaning is a cost only
# tracing opts into — but their histograms exist regardless.
STAGES: Tuple[str, ...] = (
    "DISPATCH", "REDUCE", "CREDIT_BLOCK", "PUSH_PULL", "PS_PUSH_PULL",
    "REDUCE_WAIT", "COPYD2H",
    "PS_BWD_SEG", "PS_D2H", "PS_PACK", "PS_COMPRESS", "PS_COMPRESS_DEV",
    "PS_PUSH", "PS_PULL", "PS_DECOMPRESS", "PS_UNPACK", "PS_H2D",
    "PS_APPLY_CHUNK", "PS_XSTEP_GATE",
    "PS_PARAM_PUT", "PS_PARAM_GET",
    "PP_FWD_SEG", "PP_BWD_SEG", "PP_ACT_SEND", "PP_ACT_RECV",
)

# Server-plane control-loop signals (byteps_tpu.server.plane,
# docs/server-plane.md), pre-registered like the stages so "which plane
# signals exist" is answerable before any traffic. Per-shard loads ride
# alongside as dynamic plane/shard_bytes/s<i> / plane/keys_per_shard/s<i>
# gauges (shard count is a runtime property).
PLANE_GAUGES: Tuple[str, ...] = ("plane/epoch", "plane/replication_lag")
PLANE_COUNTERS: Tuple[str, ...] = ("plane/migrations", "plane/failovers",
                                   "plane/wrong_epoch")

# Fused compression plane (byteps_tpu.compress, docs/gradient-
# compression.md): decision/byte counters pre-registered so "is the
# controller doing anything" is answerable before any traffic; the
# per-layer ``compress/level/<layer>`` gauges and
# ``ps/push_bytes/<layer>`` / ``ps/pull_bytes/<layer>`` counters ride
# alongside dynamically (layer set is a runtime property of the bucket
# plan — the pull side registers at exchange plan time, the push side
# at compress-plane registration).
COMPRESS_COUNTERS: Tuple[str, ...] = (
    "compress/decisions", "compress/raw_bytes", "compress/wire_bytes",
    # device-side encode + homogeneous server summation (PR 11):
    # ps/d2h_bytes = bytes buckets moved across D2H (dense segments on
    # the host path, encoded payloads on the device path; per-layer
    # ps/d2h_bytes/<decl>.<bucket> ride alongside dynamically);
    # server/fused_* = the merge path's decode accounting — a
    # homogeneous run keeps fused_dense_decodes at ZERO
    "ps/d2h_bytes",
    "server/fused_rounds_homog", "server/fused_rounds_fallback",
    "server/fused_dense_decodes", "server/fused_merge_cpu_s",
    "server/fused_pull_hits", "server/fused_pull_encodes",
    # activation codecs (pipeline/exchange.py): raw vs wire bytes
    "pp/act_raw_bytes")

# Sharded weight update (byteps_tpu.sharded_update,
# docs/sharded-update.md): param-frame byte counters pre-registered so
# "is the sharded update doing anything" is answerable before any
# traffic; grad-pull reduction shows in ps/pull_bytes (global and
# per-layer).
SHARD_COUNTERS: Tuple[str, ...] = ("ps/param_put_bytes",
                                   "ps/param_fetch_bytes")

# Pipeline-parallel plane (byteps_tpu.pipeline, docs/pipeline-
# parallelism.md) + the two-class wire scheduler (server/admission.py):
# pre-registered so "is the pipeline / scheduler doing anything" is
# answerable before any traffic.
PP_COUNTERS: Tuple[str, ...] = (
    "pp/microbatches", "pp/act_send_bytes", "pp/act_recv_bytes",
    "pp/builds", "pp/build_fallback",
    "sched/admitted_act", "sched/admitted_grad", "sched/overtakes")
PP_GAUGES: Tuple[str, ...] = ("pp/stage", "pp/stages",
                              "sched/inflight_bytes")

# Critical-path attribution (byteps_tpu.obs.critpath): the last traced
# step's wall, split along its BLOCKING CHAIN into these categories —
# pre-registered so "what can critpath blame" is answerable before any
# traffic. Gauges hold the latest step's seconds per category
# (crit/<cat>_s) and its fraction of the step wall (crit/<cat>_frac);
# crit/steps counts attributed steps.
CRIT_CATEGORIES: Tuple[str, ...] = (
    "compute", "d2h", "host", "wire", "server_queue", "straggler",
    "absorbed", "admission", "credit", "h2d", "apply", "gap", "other")

# Bounded-staleness admission (server/admission.py StaleStore):
# stale-serve / barrier decisions and the lag budget actually used —
# pre-registered so the Prometheus export names the lag plane's
# families before the first sealed round (all-zero at K=1)
LAG_COUNTERS: Tuple[str, ...] = (
    "lag/stale_serves", "lag/barrier_falls", "lag/late_folds",
    "lag/evicted_serves")
LAG_GAUGES: Tuple[str, ...] = ("lag/max_streak",)

# Sharded embedding store (server/embed.py, docs/embedding.md):
# hit/miss split of the worker-side hot-row cache (hits = rows served
# with ZERO row bytes on the wire — locally inside the K window or
# version-validated "unchanged"), full-row fetch bytes, rows pushed
# after the client-side dedup fold, and the live cache size —
# pre-registered so the Prometheus export names the embedding plane's
# families before the first table is declared. The durability trio
# (ISSUE 20): rows forward-logged to chain successors, failover
# promotions replayed from the replica log, and table-epoch bumps
# (server promotions/restores + client cache invalidations).
EMBED_COUNTERS: Tuple[str, ...] = (
    "embed/cache_hits", "embed/cache_misses", "embed/epoch_bumps",
    "embed/failover_replays", "embed/replicated_rows",
    "embed/row_fetch_bytes", "embed/rows_pushed")
EMBED_GAUGES: Tuple[str, ...] = ("embed/hot_set_size",)

# Fleet watchtower (byteps_tpu.obs.watchtower): detector ticks, opened
# incidents (regime flips split out), and the currently-open count —
# pre-registered so the Prometheus export names the watchtower's
# families before the first detection (all-zero on a quiet run).
WATCH_COUNTERS: Tuple[str, ...] = (
    "watch/ticks", "watch/incidents", "watch/regime_flips")
WATCH_GAUGES: Tuple[str, ...] = ("watch/open_incidents",)

# ONE truthiness rule shared with Config (BPS_STATS must resolve
# identically whether read here or through Config.stats_on)
from ..common.config import _TRUE  # noqa: E402


def _env_stats_on() -> bool:
    return os.environ.get("BPS_STATS", "1").strip().lower() in _TRUE


# module flag, not per-metric state: cached metric handles must honor a
# later configure() (the bench's BPS_STATS on/off A/B re-reads the env
# between variants)
_enabled = _env_stats_on()


def configure(enabled: Optional[bool] = None) -> bool:
    """Re-resolve the master switch (``BPS_STATS``), or force it.
    Called by ``bps.init()`` so env changes between runs take effect."""
    global _enabled
    if enabled is None:
        enabled = _env_stats_on()
    _enabled = bool(enabled)
    return _enabled


def metrics_enabled() -> bool:
    return _enabled


class Counter:
    """Monotonic counter."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        if not _enabled:
            return
        with self._lock:
            self._v += n

    @property
    def value(self) -> int:
        return self._v

    def reset(self) -> None:
        with self._lock:
            self._v = 0


class Gauge:
    """Last-value gauge (with inc/dec for level-style gauges like
    rounds-in-flight)."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        if not _enabled:
            return
        with self._lock:
            self._v = float(v)

    def inc(self, n: float = 1.0) -> None:
        if not _enabled:
            return
        with self._lock:
            self._v += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._v

    def reset(self) -> None:
        with self._lock:
            self._v = 0.0


def _default_bounds() -> Tuple[float, ...]:
    """Geometric latency buckets, 10 µs → ~84 s (doubling): 24 bounds
    cover everything from a native pack to a wedged pull about to trip
    the watchdog. Fixed at creation so merging/percentiles stay O(1)."""
    bounds, b = [], 1e-5
    for _ in range(24):
        bounds.append(b)
        b *= 2.0
    return tuple(bounds)


_DEFAULT_BOUNDS = _default_bounds()


class Histogram:
    """Fixed-bucket histogram with interpolated percentiles.

    ``bounds[i]`` is the inclusive upper edge of bucket ``i``; one
    overflow bucket catches the rest. ``observe`` is a binary search +
    two adds under a per-histogram lock — no allocation, no global
    coordination, safe from any pipeline thread.
    """

    __slots__ = ("name", "bounds", "_counts", "_count", "_sum", "_max",
                 "_lock")

    def __init__(self, name: str,
                 bounds: Optional[Sequence[float]] = None) -> None:
        self.name = name
        self.bounds = tuple(bounds) if bounds is not None \
            else _DEFAULT_BOUNDS
        self._counts = [0] * (len(self.bounds) + 1)
        self._count = 0
        self._sum = 0.0
        self._max = 0.0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        if not _enabled:
            return
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def percentile(self, p: float) -> float:
        """Interpolated percentile (p in [0, 100]) from the buckets; the
        overflow bucket reports the observed max."""
        with self._lock:
            total = self._count
            if total == 0:
                return 0.0
            target = total * p / 100.0
            cum = 0
            for i, c in enumerate(self._counts):
                if c == 0:
                    continue
                if cum + c >= target:
                    if i >= len(self.bounds):
                        return self._max
                    lo = self.bounds[i - 1] if i > 0 else 0.0
                    hi = self.bounds[i]
                    frac = (target - cum) / c
                    # interpolation can overshoot the bucket's observed
                    # values — never report a percentile above the max
                    return min(lo + (hi - lo) * frac, self._max)
                cum += c
            return self._max

    def summary(self) -> dict:
        with self._lock:
            count, tot, mx = self._count, self._sum, self._max
        if count == 0:
            return {"count": 0, "sum_ms": 0.0}
        return {
            "count": count,
            "sum_ms": round(tot * 1e3, 3),
            "mean_ms": round(tot / count * 1e3, 3),
            "max_ms": round(mx * 1e3, 3),
            "p50_ms": round(self.percentile(50) * 1e3, 3),
            "p95_ms": round(self.percentile(95) * 1e3, 3),
            "p99_ms": round(self.percentile(99) * 1e3, 3),
        }

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._count = 0
            self._sum = 0.0
            self._max = 0.0


class MetricsRegistry:
    """Name → metric map. Creation is locked (rare); observation touches
    only the metric's own lock (hot). Types are pinned per name —
    re-requesting ``counter("x")`` after ``gauge("x")`` is a bug and
    raises rather than silently aliasing."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}
        for s in STAGES:
            self.histogram(f"stage/{s}")
        for g in PLANE_GAUGES:
            self.gauge(g)
        for c in PLANE_COUNTERS:
            self.counter(c)
        for c in COMPRESS_COUNTERS:
            self.counter(c)
        for c in SHARD_COUNTERS:
            self.counter(c)
        for c in PP_COUNTERS:
            self.counter(c)
        for g in PP_GAUGES:
            self.gauge(g)
        for c in CRIT_CATEGORIES:
            self.gauge(f"crit/{c}_s")
            self.gauge(f"crit/{c}_frac")
        self.counter("crit/steps")
        for c in LAG_COUNTERS:
            self.counter(c)
        for g in LAG_GAUGES:
            self.gauge(g)
        for c in EMBED_COUNTERS:
            self.counter(c)
        for g in EMBED_GAUGES:
            self.gauge(g)
        for c in WATCH_COUNTERS:
            self.counter(c)
        for g in WATCH_GAUGES:
            self.gauge(g)

    def _get(self, name: str, cls, *args):
        m = self._metrics.get(name)
        if m is not None:
            if not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} is a {type(m).__name__}, "
                    f"requested as {cls.__name__}")
            return m
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, *args)
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} is a {type(m).__name__}, "
                    f"requested as {cls.__name__}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str,
                  bounds: Optional[Sequence[float]] = None) -> Histogram:
        if bounds is None:
            return self._get(name, Histogram)
        return self._get(name, Histogram, bounds)

    def stage(self, stage: str) -> Histogram:
        """The latency histogram for a Chrome-trace stage name."""
        return self.histogram(f"stage/{stage}")

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._metrics)

    def snapshot(self) -> dict:
        """Raw values: {name: int|float|{histogram summary}}."""
        with self._lock:
            items = list(self._metrics.items())
        out: dict = {}
        for name, m in items:
            if isinstance(m, Histogram):
                out[name] = m.summary()
            else:
                out[name] = m.value
        return out

    def summary(self, nonzero: bool = True) -> dict:
        """snapshot() with zero-valued metrics dropped (default) — the
        form the bench's ``--stats`` flag prints."""
        out = self.snapshot()
        if not nonzero:
            return out
        return {k: v for k, v in out.items()
                if (v.get("count", 0) if isinstance(v, dict) else v)}

    def stage_totals(self) -> Dict[str, Tuple[int, float]]:
        """{stage: (count, total_seconds)} for every ``stage/*``
        histogram — the cheap per-step delta base StepStats uses."""
        with self._lock:
            items = [(n, m) for n, m in self._metrics.items()
                     if n.startswith("stage/") and isinstance(m, Histogram)]
        return {n[len("stage/"):]: (m.count, m.sum) for n, m in items}

    def counters_with_prefix(
            self, prefixes: Tuple[str, ...]) -> Dict[str, int]:
        """{name: value} for every counter under ``prefixes`` — the
        delta base for the DYNAMICALLY-registered per-layer byte
        counters (``ps/pull_bytes/<decl>.<bucket>`` etc. appear at
        exchange plan time, so a fixed pre-registered list can never
        cover them; StepStats re-sweeps this each step)."""
        with self._lock:
            items = [(n, m) for n, m in self._metrics.items()
                     if isinstance(m, Counter) and n.startswith(prefixes)]
        return {n: m.value for n, m in items}

    def reset(self) -> None:
        """Zero every metric (bench A/B between variants; tests)."""
        with self._lock:
            items = list(self._metrics.values())
        for m in items:
            m.reset()


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry every pipeline layer reports into."""
    return _REGISTRY


def observe_stage(stage: str, dur_s: float) -> None:
    """Record one span of a Chrome-trace stage into its latency
    histogram. The always-on sibling of ``Timeline.record`` — call
    sites that already hold (t0, dur) report here unconditionally and
    to the timeline only inside a trace window."""
    if not _enabled:
        return
    _REGISTRY.stage(stage).observe(dur_s)

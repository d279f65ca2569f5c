"""Environment-variable configuration system.

The reference framework (BytePS) is configured purely through environment
variables (reference: docs/env.md; global.cc:105-281 reads them at init).
We keep that contract — every knob here is an env var with the same or an
analogous name — but resolve them once into a frozen, typed ``Config``
object instead of scattering ``getenv`` calls through the runtime.

Env vars recognised (reference name → here):
  DMLC_ROLE                → BPS_ROLE            (worker|server|scheduler)
  DMLC_WORKER_ID           → BPS_WORKER_ID
  DMLC_NUM_WORKER          → BPS_NUM_WORKER
  BYTEPS_LOCAL_RANK/SIZE   → BPS_LOCAL_RANK/SIZE
  BYTEPS_PARTITION_BYTES   → BPS_PARTITION_BYTES
  BYTEPS_SCHEDULING_CREDIT → BPS_SCHEDULING_CREDIT
  BYTEPS_MIN_COMPRESS_BYTES→ BPS_MIN_COMPRESS_BYTES
  BYTEPS_FORCE_DISTRIBUTED → BPS_FORCE_DISTRIBUTED
  BYTEPS_ENABLE_ASYNC      → BPS_ENABLE_ASYNC
  BYTEPS_KEY_HASH_FN       → BPS_KEY_HASH_FN
  BYTEPS_TRACE_ON/...      → BPS_TRACE_ON / BPS_TRACE_START_STEP /
                             BPS_TRACE_END_STEP / BPS_TRACE_DIR
  BYTEPS_TELEMETRY_ON      → BPS_TELEMETRY_ON
  BYTEPS_LOG_LEVEL         → BPS_LOG_LEVEL
  BYTEPS_SERVER_ENGINE_THREAD  → BPS_SERVER_ENGINE_THREAD
  BYTEPS_SERVER_ENABLE_SCHEDULE→ BPS_SERVER_ENABLE_SCHEDULE

The original ``BYTEPS_``/``DMLC_`` spellings are accepted as fallbacks so
that launch scripts written for the reference keep working.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

_TRUE = {"1", "true", "yes", "on"}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry script
    (chip_smoke.py, bench.py, the examples) and return its directory.
    Never called by ``bps.init()`` or at package import.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no
    directory is set in code. Unset: ``<checkout>/.jax_cache``, derived
    from this file's location — the path is part of the cache key, so it
    must not depend on the working directory, a pid or the time."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        import jax
        d = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    return d


def _env(name: str, legacy: Optional[str] = None, default: Optional[str] = None) -> Optional[str]:
    """Read BPS_* env var, falling back to the legacy BYTEPS_/DMLC_ name."""
    v = os.environ.get(name)
    if v is None and legacy is not None:
        v = os.environ.get(legacy)
    return v if v is not None else default


def _env_int(name: str, legacy: Optional[str], default: int) -> int:
    v = _env(name, legacy)
    return int(v) if v not in (None, "") else default


def _env_bool(name: str, legacy: Optional[str], default: bool = False) -> bool:
    v = _env(name, legacy)
    if v is None:
        return default
    return v.strip().lower() in _TRUE


@dataclasses.dataclass(frozen=True)
class Config:
    """Frozen snapshot of all runtime knobs, resolved at ``bps.init()``."""

    # --- topology / bootstrap (reference: docs/env.md:7-45) ---
    role: str = "worker"                 # worker | server | scheduler
    worker_id: int = 0
    num_worker: int = 1
    local_rank: int = 0
    local_size: int = 1
    force_distributed: bool = False
    # JAX distributed coordinator (replaces DMLC_PS_ROOT_URI/PORT rendezvous)
    coordinator_address: Optional[str] = None
    process_id: Optional[int] = None
    num_processes: Optional[int] = None

    # --- pipeline tuning (reference: global.cc:134-143, scheduled_queue.cc:35-40) ---
    partition_bytes: int = 4096000       # BYTEPS_PARTITION_BYTES default, global.cc:134
    scheduling_credit: int = 0           # 0 = disabled, scheduled_queue.cc:35-45
    reverse_layer_priority: bool = True  # issue grad buckets in reverse layer order

    # --- PS / server mode (reference: server.cc:407-439) ---
    enable_async: bool = False           # BYTEPS_ENABLE_ASYNC
    enable_ps: bool = False              # route push_pull through host PS service
    host_only: bool = False              # BPS_HOST_ONLY: no device mesh / no
                                         # JAX backend discovery — the runtime
                                         # is the host PS plane only (the torch
                                         # plugin's numpy-over-TCP path, which
                                         # never needs a device)
    server_addrs: str = ""               # BPS_SERVER_ADDRS: host:port,... of
                                         # standalone servers (empty → in-process)
    server_engine_threads: int = 4       # BYTEPS_SERVER_ENGINE_THREAD
    server_enable_schedule: bool = False # BYTEPS_SERVER_ENABLE_SCHEDULE

    # --- key placement (reference: global.cc:158-180) ---
    key_hash_fn: str = "djb2"            # naive|built_in|djb2|sdbm|mixed|ring

    # --- server plane (ours: placement/replication/rebalancing,
    # docs/server-plane.md) ---
    plane_replicas: int = 0              # BPS_PLANE_REPLICAS: >0 with
                                         # multiple BPS_SERVER_ADDRS wraps
                                         # the shards in the managed plane
                                         # (primary-backup forward logs,
                                         # failover = reroute + replay)
    plane_rebalance_sec: float = 0.0     # BPS_PLANE_REBALANCE_SEC: load-
                                         # aware rebalancer cadence (0 off)
    plane_vnodes: int = 0                # BPS_PLANE_VNODES: virtual nodes
                                         # per shard on the hash ring
                                         # (0 = default 64)
    plane_liveness: bool = True          # BPS_PLANE_LIVENESS: act on the
                                         # fleet scraper's staleness
                                         # verdicts — a black-holed shard
                                         # (scrape age past 3 cadences)
                                         # is failed over server-side,
                                         # not just observed; needs the
                                         # scraper (BPS_FLEET_SCRAPE_SEC)
                                         # and plane_replicas>0 to act

    # --- pipeline parallelism (ours: byteps_tpu/pipeline,
    # docs/pipeline-parallelism.md) ---
    pp_stages: int = 1                   # BPS_PP_STAGES: pipeline depth
                                         # (1 = no pipeline parallelism)
    pp_rank: int = 0                     # BPS_PP_RANK: this worker's
                                         # stage index in [0, pp_stages)
    pp_microbatch: int = 1               # BPS_PP_MICROBATCH: microbatches
                                         # per step driving the 1F1B
                                         # schedule
    pp_virtual: int = 1                  # BPS_PP_VIRTUAL: virtual model
                                         # chunks per physical stage —
                                         # >1 selects the interleaved
                                         # 1F1B schedule over a
                                         # P*V-stage program (sub-
                                         # linear bubbles at depth;
                                         # needs microbatch % stages
                                         # == 0)

    # --- sharded weight update (ours: byteps_tpu/sharded_update,
    # docs/sharded-update.md) ---
    sharded_update: bool = False         # BPS_SHARDED_UPDATE: partition
                                         # the bucket groups across the
                                         # dp replicas — pull/apply only
                                         # your shard, publish params,
                                         # fetch the rest (ZeRO-style);
                                         # probe-or-fallback to the full
                                         # apply (dp=1, async, legacy-
                                         # compressed keys, coupled tx)
    shard_rank: int = -1                 # BPS_SHARD_RANK: this
                                         # replica's ownership rank
                                         # (-1 = worker_id)
    shard_world: int = 0                 # BPS_SHARD_WORLD: ownership
                                         # degree (0 = num_worker)
    # BPS_PARAM_TIMEOUT_MS (owner-death diagnostic threshold for param
    # fetches, default 30000) is read by sharded_update itself — it
    # tunes the mode, not selects it

    # --- emulated-NIC throttle for this worker endpoint (perf lab:
    # charges all RemotePSBackend traffic to a throttle.Nic so
    # multi-process training A/Bs run under a bandwidth constraint;
    # 0 = off) ---
    emu_nic_rate: float = 0.0            # BPS_EMU_NIC_RATE bytes/sec
    emu_nic_latency: float = 0.0         # BPS_EMU_NIC_LATENCY seconds/frame

    # --- compression (reference: global.cc:137-139) ---
    min_compress_bytes: int = 65536      # BYTEPS_MIN_COMPRESS_BYTES default 64KiB

    # --- fused adaptive compression plane (ours: byteps_tpu/compress,
    # docs/gradient-compression.md) ---
    compress: str = "none"               # BPS_COMPRESS: none | auto |
                                         # fp16 | int8 | topk — per-
                                         # bucket codecs fused into the
                                         # streamed PS pipeline; "auto"
                                         # = runtime controller driven
                                         # by the live congestion
                                         # signals; a codec name pins
                                         # the decision trace (determi-
                                         # nistic compressed training)
    # BPS_COMPRESS_EF (error-feedback residuals, default on),
    # BPS_COMPRESS_MAX (auto ladder cap, default int8),
    # BPS_COMPRESS_INTERVAL (decision cadence in rounds) and
    # BPS_COMPRESS_TOPK_DIV (k = elems/div) are read by the plane
    # itself (compress/plane.py) — they tune a mode, not select one

    # --- tracing / telemetry (reference: global.cc:113-124, 697-752) ---
    trace_on: bool = False
    trace_start_step: int = 10
    trace_end_step: int = 20
    trace_dir: str = "."
    trace_profiler: bool = False         # BPS_TRACE_PROFILER: also capture
                                         # a jax.profiler device trace over
                                         # the same step window
    telemetry_on: bool = False
    debug_sample_tensor: str = ""        # BYTEPS_DEBUG_SAMPLE_TENSOR

    # --- observability (ours — byteps_tpu/obs/; docs/observability.md) ---
    stats_on: bool = True                # BPS_STATS: metrics registry +
                                         # per-step StepStats (cheap, on
                                         # by default; 0 = A/B off)
    stats_file: str = ""                 # BPS_STATS_FILE: rolling JSON
                                         # dump of recent StepStats
    stats_every: int = 50                # BPS_STATS_EVERY: dump cadence
    watchdog_sec: float = 0.0            # BPS_WATCHDOG_SEC: stall
                                         # watchdog threshold (0 = off)
    fleet_scrape_sec: float = 0.0        # BPS_FLEET_SCRAPE_SEC: fleet
                                         # telemetry scrape cadence —
                                         # >0 stands up a FleetScraper
                                         # over the PS backend's
                                         # stats() surface (OP_STATS),
                                         # publishing the shard-labeled
                                         # fleet/<shard>/<metric> view
                                         # + scrape-age staleness
    metrics_port: int = 0                # BPS_METRICS_PORT: HTTP
                                         # exporter port (/metrics
                                         # Prometheus text,
                                         # /metrics.json, /fleet.json);
                                         # 0 = off
    # BPS_FLIGHT_RECORDER (default on) + BPS_FLIGHT_RECORDER_SIZE are
    # read by obs/flight.py itself — they tune the ring, not a mode

    # --- logging ---
    log_level: str = "INFO"

    @staticmethod
    def from_env(**overrides) -> "Config":
        cfg = dict(
            role=_env("BPS_ROLE", "DMLC_ROLE", "worker"),
            worker_id=_env_int("BPS_WORKER_ID", "DMLC_WORKER_ID", 0),
            num_worker=_env_int("BPS_NUM_WORKER", "DMLC_NUM_WORKER", 1),
            local_rank=_env_int("BPS_LOCAL_RANK", "BYTEPS_LOCAL_RANK", 0),
            local_size=_env_int("BPS_LOCAL_SIZE", "BYTEPS_LOCAL_SIZE", 1),
            force_distributed=_env_bool("BPS_FORCE_DISTRIBUTED", "BYTEPS_FORCE_DISTRIBUTED"),
            coordinator_address=_env("BPS_COORDINATOR_ADDRESS", "DMLC_PS_ROOT_URI"),
            # Multi-host bootstrap: one JAX process per host. Falls back to the
            # reference's worker-count/worker-id env contract (docs/env.md:7-45).
            num_processes=(int(v) if (v := _env("BPS_NUM_PROCESSES", "DMLC_NUM_WORKER")) else None),
            process_id=(int(v) if (v := _env("BPS_PROCESS_ID", "DMLC_WORKER_ID")) else None),
            partition_bytes=_env_int("BPS_PARTITION_BYTES", "BYTEPS_PARTITION_BYTES", 4096000),
            scheduling_credit=_env_int("BPS_SCHEDULING_CREDIT", "BYTEPS_SCHEDULING_CREDIT", 0),
            enable_async=_env_bool("BPS_ENABLE_ASYNC", "BYTEPS_ENABLE_ASYNC"),
            enable_ps=_env_bool("BPS_ENABLE_PS", "BYTEPS_ENABLE_PS"),
            host_only=_env_bool("BPS_HOST_ONLY", None),
            server_addrs=_env("BPS_SERVER_ADDRS", None, ""),
            server_engine_threads=_env_int("BPS_SERVER_ENGINE_THREAD", "BYTEPS_SERVER_ENGINE_THREAD", 4),
            server_enable_schedule=_env_bool("BPS_SERVER_ENABLE_SCHEDULE", "BYTEPS_SERVER_ENABLE_SCHEDULE"),
            key_hash_fn=_env("BPS_KEY_HASH_FN", "BYTEPS_KEY_HASH_FN", "djb2"),
            plane_replicas=int(_env("BPS_PLANE_REPLICAS", None, "0") or 0),
            plane_rebalance_sec=float(
                _env("BPS_PLANE_REBALANCE_SEC", None, "0") or 0),
            plane_vnodes=int(_env("BPS_PLANE_VNODES", None, "0") or 0),
            plane_liveness=_env_bool("BPS_PLANE_LIVENESS", None, True),
            pp_stages=_env_int("BPS_PP_STAGES", None, 1),
            pp_rank=_env_int("BPS_PP_RANK", None, 0),
            pp_microbatch=_env_int("BPS_PP_MICROBATCH", None, 1),
            pp_virtual=_env_int("BPS_PP_VIRTUAL", None, 1),
            sharded_update=_env_bool("BPS_SHARDED_UPDATE", None),
            shard_rank=_env_int("BPS_SHARD_RANK", None, -1),
            shard_world=_env_int("BPS_SHARD_WORLD", None, 0),
            emu_nic_rate=float(_env("BPS_EMU_NIC_RATE", None, "0") or 0),
            emu_nic_latency=float(_env("BPS_EMU_NIC_LATENCY", None, "0") or 0),
            min_compress_bytes=_env_int("BPS_MIN_COMPRESS_BYTES", "BYTEPS_MIN_COMPRESS_BYTES", 65536),
            compress=(_env("BPS_COMPRESS", None, "none") or "none").lower(),
            trace_on=_env_bool("BPS_TRACE_ON", "BYTEPS_TRACE_ON"),
            trace_start_step=_env_int("BPS_TRACE_START_STEP", "BYTEPS_TRACE_START_STEP", 10),
            trace_end_step=_env_int("BPS_TRACE_END_STEP", "BYTEPS_TRACE_END_STEP", 20),
            trace_dir=_env("BPS_TRACE_DIR", "BYTEPS_TRACE_DIR", "."),
            trace_profiler=_env_bool("BPS_TRACE_PROFILER", None),
            telemetry_on=_env_bool("BPS_TELEMETRY_ON", "BYTEPS_TELEMETRY_ON"),
            debug_sample_tensor=_env("BPS_DEBUG_SAMPLE_TENSOR", "BYTEPS_DEBUG_SAMPLE_TENSOR", ""),
            stats_on=_env_bool("BPS_STATS", None, True),
            stats_file=_env("BPS_STATS_FILE", None, ""),
            stats_every=_env_int("BPS_STATS_EVERY", None, 50),
            watchdog_sec=float(_env("BPS_WATCHDOG_SEC", None, "0") or 0),
            fleet_scrape_sec=float(
                _env("BPS_FLEET_SCRAPE_SEC", None, "0") or 0),
            metrics_port=_env_int("BPS_METRICS_PORT", None, 0),
            log_level=_env("BPS_LOG_LEVEL", "BYTEPS_LOG_LEVEL", "INFO"),
        )
        cfg.update(overrides)
        return Config(**cfg)

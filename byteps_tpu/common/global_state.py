"""Process-wide runtime state (reference: BytePSGlobal, global.h:52-225).

Holds the resolved Config, the device mesh, the tensor name registry, the
push_pull engine, telemetry, and the timeline tracer. Created by
``bps.init()`` and torn down by ``bps.shutdown()``; ``suspend``/``resume``
re-initialise with new membership while replaying tensor declarations so
name→key mappings stay stable (reference: operations.cc:96-119,
global.cc:431-436).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

from .config import Config
from .logging import get_logger
from .naming import NameRegistry

log = get_logger()


class _HostOnlyEngine:
    """Engine stand-in for host-only mode (``BPS_HOST_ONLY`` / the torch
    plugin): carries the PS host-exchange plane with NO device mesh and
    NO JAX backend discovery. The torch path is numpy-over-TCP end to
    end (torch/ops.py), so forcing accelerator discovery at init — and
    taking the chip from the process that trains on it — bought nothing.
    Collective
    entry points raise with a pointer at the full engine."""

    def __init__(self) -> None:
        self.ps_exchange = None
        self.ps_world = 1
        self.timeline = None
        self.debug_sample = ""
        self._handles: dict = {}

    def _no_mesh(self, api: str):
        raise RuntimeError(
            f"{api} needs a device mesh, but the runtime was initialised "
            "host-only (BPS_HOST_ONLY / byteps_tpu.torch). Re-init via "
            "byteps_tpu.init() (or BPS_HOST_ONLY=0) for the collective "
            "engine.")

    def push_pull(self, *a, **k):
        self._no_mesh("push_pull")

    def push_pull_async(self, *a, **k):
        self._no_mesh("push_pull_async")

    def poll(self, *a, **k):
        self._no_mesh("poll")

    def synchronize(self, *a, **k):
        self._no_mesh("synchronize")

    def broadcast(self, *a, **k):
        self._no_mesh("broadcast")


class GlobalState:
    _instance: Optional["GlobalState"] = None
    _lock = threading.Lock()

    def __init__(self, config: Config, mesh=None) -> None:
        from ..telemetry import PushPullSpeed
        from ..timeline import Timeline

        self.config = config
        self.registry = NameRegistry()
        self.telemetry = PushPullSpeed() if config.telemetry_on else None
        self.timeline = Timeline(config) if config.trace_on else None
        # observability: re-resolve the metrics master switch for THIS
        # init (the bench's BPS_STATS on/off A/B re-inits between
        # variants) and stand up the per-step StepStats emitter
        from ..obs import metrics as obs_metrics
        obs_metrics.configure(config.stats_on)
        from ..obs import flight as obs_flight
        obs_flight.configure()       # re-read BPS_FLIGHT_RECORDER* too
        # watchtower (obs/watchtower.py): re-resolve BPS_AUTOTUNE +
        # BPS_WATCH_* for this init and drop the previous run's
        # incidents — the detector thresholds must reflect THIS init's
        # env, exactly like the metrics master switch above
        from ..obs import watchtower as obs_watchtower
        obs_watchtower.configure()
        # two-class wire send scheduler (server/admission.py): resolve the
        # byte credit for THIS init, before any backend is constructed,
        # so every transport client sees the same gate
        from ..server.admission import configure_send
        configure_send(config.scheduling_credit)
        # the newest trainer's record of its own start
        # (common/setup_record.py), for a reader that is handed no trainer
        self.setup_record = None
        self.stats = None
        if config.stats_on:
            from ..obs.stats import StepStatsEmitter
            self.stats = StepStatsEmitter(
                stats_file=config.stats_file or None,
                every=config.stats_every)
        if config.host_only:
            if mesh is not None:
                raise ValueError(
                    "host_only config with an explicit mesh is "
                    "contradictory — drop BPS_HOST_ONLY (or the mesh) ")
            # host-only: PS plane without any accelerator backend —
            # jax.devices() is never touched, so torch PS workers init
            # without claiming a chip (the numpy path never needed one)
            self.mesh = None
            self.engine = _HostOnlyEngine()
        else:
            from ..parallel.mesh import make_mesh
            from ..parallel.collectives import PushPullEngine
            self.mesh = mesh if mesh is not None else make_mesh()
            self.engine = PushPullEngine(
                self.mesh, partition_bytes=config.partition_bytes,
                registry=self.registry, telemetry=self.telemetry,
                scheduling_credit=config.scheduling_credit)
        self.engine.timeline = self.timeline
        self.engine.debug_sample = config.debug_sample_tensor
        self.ps_backend = None
        self.plane_rebalancer = None
        if config.enable_ps:
            # PS deployment (reference architecture): workers are
            # independent processes with LOCAL meshes; the cross-worker
            # hop is the host service, not a collective. In-process
            # backend at world 1; TCP to standalone servers otherwise.
            from ..server.ps_mode import PSGradientExchange
            if config.server_addrs:
                from ..server.transport import RemotePSBackend
                addrs = [a.strip() for a in config.server_addrs.split(",")
                         if a.strip()]
                nic = None
                if config.emu_nic_rate > 0:
                    from ..server.throttle import Nic
                    nic = Nic(config.emu_nic_rate,
                              latency=config.emu_nic_latency)
                if config.plane_replicas > 0 and len(addrs) > 1:
                    # managed server plane: one single-address client
                    # per shard, routed through the byte-weighted ring
                    # with versioned epochs, each key's rounds forward-
                    # logged to its backup shard (failover = reroute +
                    # replay, docs/server-plane.md)
                    from ..server.plane import PlanePSBackend, Rebalancer
                    # lazy_dial: an elastic replacement must be able to
                    # join a fleet that already lost a shard — the
                    # plane's failover, not a constructor crash, owns
                    # dead-addr handling (docs/elasticity.md)
                    shards = [RemotePSBackend(
                        [a], async_mode=config.enable_async, nic=nic,
                        lazy_dial=True)
                        for a in addrs]
                    self.ps_backend = PlanePSBackend(
                        shards, num_workers=config.num_worker,
                        replicas=config.plane_replicas,
                        vnodes=config.plane_vnodes or 64,
                        owns_shards=True,
                        worker_id=config.worker_id)
                    if config.plane_rebalance_sec > 0:
                        if config.num_worker > 1:
                            # each worker holds its own placement view;
                            # independent rebalancers would migrate
                            # different keys and the views diverge
                            # (same key pushed to different shards =
                            # torn sums). Failover stays safe — its
                            # reassignment is a deterministic pure
                            # function of the shared ring. A server-
                            # side placement controller is the
                            # multi-worker path (docs/server-plane.md).
                            get_logger().warning(
                                "BPS_PLANE_REBALANCE_SEC ignored with "
                                "%d workers: per-worker rebalancers "
                                "would diverge the placement views",
                                config.num_worker)
                        else:
                            self.plane_rebalancer = Rebalancer(
                                self.ps_backend,
                                interval_sec=config.plane_rebalance_sec
                            ).start()
                else:
                    if config.plane_replicas > 0:
                        # replication was asked for but there is
                        # nothing to replicate across — say so, or a
                        # mistyped BPS_SERVER_ADDRS silently downgrades
                        # "server death = reroute + replay" to restart
                        get_logger().warning(
                            "BPS_PLANE_REPLICAS=%d ignored: %d server "
                            "address(es) — the plane needs >1 shard",
                            config.plane_replicas, len(addrs))
                    self.ps_backend = RemotePSBackend(
                        addrs, hash_fn=config.key_hash_fn,
                        async_mode=config.enable_async, nic=nic)
            else:
                if config.num_worker > 1:
                    raise ValueError(
                        "BPS_ENABLE_PS with BPS_NUM_WORKER>1 needs "
                        "BPS_SERVER_ADDRS (standalone servers reachable by "
                        "every worker) — a private in-process backend would "
                        "wait forever for the other workers' pushes")
                from ..server.engine import HostPSBackend
                self.ps_backend = HostPSBackend(
                    num_servers=1, num_workers=config.num_worker,
                    engine_threads=config.server_engine_threads,
                    enable_schedule=config.server_enable_schedule,
                    async_mode=config.enable_async, hash_fn=config.key_hash_fn)
            if not config.enable_async:
                # sync PS: the eager push_pull takes the host hop. Async PS
                # is driven by server.ps_mode.AsyncPSWorker (weight deltas,
                # no barrier) against gs.ps_backend — summing GRADIENTS into
                # the async store would accumulate forever.
                self.engine.ps_exchange = PSGradientExchange(
                    self.ps_backend, partition_bytes=config.partition_bytes,
                    registry=self.registry,
                    min_compress_bytes=config.min_compress_bytes,
                    watchdog_sec=config.watchdog_sec,
                    compress=config.compress)
                self.engine.ps_exchange.timeline = self.timeline
                self.engine.ps_world = config.num_worker
        # fleet telemetry plane (obs/fleet.py): scrape every PS shard's
        # registry + heartbeat on a cadence into the shard-labeled
        # local view; the rebalancer and the compression controller
        # pick it up via fleet.current(). Worker-role only concern —
        # every backend kind carries the stats() surface.
        self.fleet = None
        if (config.fleet_scrape_sec > 0 and self.ps_backend is not None
                and hasattr(self.ps_backend, "stats")):
            from ..obs.fleet import FleetScraper, set_current
            self.fleet = FleetScraper(
                self.ps_backend, interval_sec=config.fleet_scrape_sec,
                # liveness acted-on (BPS_PLANE_LIVENESS, default on): a
                # plane shard whose scrape goes stale is declared dead
                # server-side and failed over — note_stale itself
                # refuses (observed-only) when there is no replica log
                failover_backend=(
                    self.ps_backend if config.plane_liveness
                    and hasattr(self.ps_backend, "note_stale") else None))
            set_current(self.fleet)
            self.fleet.start()
        # metrics HTTP endpoint (obs/export.py): Prometheus text +
        # JSON over BPS_METRICS_PORT. A bind failure (port taken)
        # degrades with a warning — an exporter must not kill training.
        self.metrics_server = None
        if config.metrics_port:
            from ..obs.export import MetricsHTTPServer
            try:
                self.metrics_server = MetricsHTTPServer(
                    config.metrics_port).start()
            except OSError as e:
                get_logger().warning(
                    "BPS_METRICS_PORT=%d unavailable (%s) — metrics "
                    "endpoint disabled", config.metrics_port, e)
        if self.mesh is None:
            self.dp = config.num_worker
        else:
            from ..parallel.mesh import dp_size
            self.dp = dp_size(self.mesh)
        self.step = 0
        log.info("BPS init: role=%s mesh=%s dp=%d partition_bytes=%d",
                 config.role,
                 "host-only" if self.mesh is None else dict(self.mesh.shape),
                 self.dp, config.partition_bytes)

    # -- lifecycle ----------------------------------------------------------
    @classmethod
    def init(cls, config: Optional[Config] = None, mesh=None) -> "GlobalState":
        with cls._lock:
            if cls._instance is not None:
                return cls._instance
            cfg = config or Config.from_env()
            if (not cfg.host_only and cfg.coordinator_address
                    and cfg.num_processes and cfg.num_processes > 1):
                jax.distributed.initialize(
                    coordinator_address=cfg.coordinator_address,
                    num_processes=cfg.num_processes, process_id=cfg.process_id)
            cls._instance = GlobalState(cfg, mesh=mesh)
            return cls._instance

    @classmethod
    def get(cls) -> "GlobalState":
        if cls._instance is None:
            raise RuntimeError("byteps_tpu not initialised; call bps.init() first")
        return cls._instance

    @classmethod
    def initialized(cls) -> bool:
        return cls._instance is not None

    @classmethod
    def shutdown(cls) -> None:
        with cls._lock:
            inst = cls._instance
            if inst is None:
                return
            if inst.timeline is not None:
                inst.timeline.flush()
            if inst.stats is not None:
                inst.stats.flush()      # final rolling-dump write
            if inst.engine._handles:
                log.warning(
                    "shutdown with %d unsynchronized push_pull_async "
                    "handle(s) — their results are lost%s",
                    len(inst.engine._handles),
                    "; in PS mode peers may block on the missing pushes"
                    if inst.ps_backend is not None else "")
            if inst.engine.ps_exchange is not None:
                inst.engine.ps_exchange.close()
            if getattr(inst, "plane_rebalancer", None) is not None:
                inst.plane_rebalancer.stop()
            cls._stop_obs(inst)
            if inst.ps_backend is not None:
                inst.ps_backend.close()
            cls._instance = None

    @classmethod
    def _stop_obs(cls, inst) -> None:
        """Tear down the fleet scraper + metrics endpoint (before the
        backend closes — the scraper reads it)."""
        if getattr(inst, "fleet", None) is not None:
            from ..obs.fleet import current, set_current
            inst.fleet.stop()
            if current() is inst.fleet:
                set_current(None)
            inst.fleet = None
        if getattr(inst, "metrics_server", None) is not None:
            try:
                inst.metrics_server.stop()
            except Exception:   # noqa: BLE001 — best-effort teardown
                pass
            inst.metrics_server = None

    @classmethod
    def suspend(cls) -> Optional[list]:
        """Tear down but remember declarations for resume (reference:
        byteps_suspend, operations.cc:114-119)."""
        with cls._lock:
            inst = cls._instance
            if inst is None:
                return None
            decls = [(d.name, d.priority, d.compression_kwargs)
                     for d in (inst.registry.get(n) for n in inst.registry.declared_names())]
            if inst.engine.ps_exchange is not None:
                inst.engine.ps_exchange.close()
            if getattr(inst, "plane_rebalancer", None) is not None:
                inst.plane_rebalancer.stop()
            cls._stop_obs(inst)
            if inst.ps_backend is not None:
                inst.ps_backend.close()
            cls._instance = None
            return decls

    @classmethod
    def resume(cls, decls, config: Optional[Config] = None, mesh=None) -> "GlobalState":
        """Re-init with new membership, replaying declarations in original
        order for stable name→key (reference: ReDeclareTensor)."""
        inst = cls.init(config, mesh=mesh)
        for name, priority, kwargs in decls or []:
            inst.registry.declare(name, priority=priority, **kwargs)
        return inst

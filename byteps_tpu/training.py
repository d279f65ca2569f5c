"""High-level distributed trainer.

The reference's gluon ``DistributedTrainer`` (reference:
mxnet/__init__.py:164-345) owns the optimizer, rescales gradients by
batch-size×world-size, push_pulls every parameter, and steps locally. The
TPU-native analogue owns the whole jitted train step: it shard_maps the
user's loss over the mesh (batch split on the data axes, params
replicated), computes per-replica grads, reduces them via
``distributed_optimizer`` (each leaf as it is on an ICI mesh, flat
buckets where a reducer needs a flat buffer:
``parallel.collectives.exchange_form``), and applies updates identically
on every replica. One compiled XLA program per step. The all-reduces run
after the backward, exposed (23 ms of BERT-large's step at dp=4 on the
v5e, PERF.md section 5): hiding them behind it is ROADMAP A3(b).
"""

from __future__ import annotations

import functools
import os
import time
import weakref
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .common import setup_record as _record
from .common.global_state import GlobalState
from .obs.metrics import observe_stage
from .optim import distributed_optimizer
from .parallel.collectives import Reducer, exchange_form, psum_reducer
from .parallel.mesh import data_axes, make_mesh
from .parallel.sharding import spec_axes as _spec_axes


def _log_exchange_form(axes, reducer, compression) -> None:
    """One line a trainer, beside GlobalState's ``BPS init``: which form
    the jitted step's gradient exchange takes, and why."""
    from .common.logging import get_logger
    form, why = exchange_form(axes, reducer, compression)
    get_logger().info("BPS exchange: form=%s axes=%s (%s)", form,
                      tuple(axes), why)


def _recorded(init):
    """Decorator of a trainer's ``__init__``: its set-up record
    (``common/setup_record.py``) opens on entry, ``bps.setup.init`` spans
    the constructor, and the first ``step`` call goes through an
    instance-bound wrapper that spans it (``bps.setup.first_step``),
    closes the record and removes itself: from the second call on
    ``step`` is the class's own, and nothing of the record runs in it.
    Where that step dispatched the one jitted program (``_step_fn``; the
    PS branches have none), the wrapper reads the compiler's account of
    the executable it ran into the record (``step_memory``) before it
    closes, and keeps the step's trace for ``step_account``."""
    @functools.wraps(init)
    def __init__(self, *args, **kwargs):
        rec = self._setup = _record.open_record()
        rec["trainer"] = weakref.ref(self)
        self._step_traced = None
        try:
            with _record.span(rec, "bps.setup.init"):
                init(self, *args, **kwargs)
        except BaseException:
            _record.close(rec)
            raise
        rec["step_funs"] = tuple(
            getattr(self, a).__name__
            for a in ("_step_fn", "_grad_fn", "_apply_fn") if hasattr(self, a))

        def first_step(batch):
            if rec["closed"]:           # a caller kept the bound wrapper
                return type(self).step(self, batch)
            _record.open_record(rec)    # another trainer may have opened its
            fn, handed = vars(self).get("_step_fn"), []

            def dispatch(params, opt_state, *batch):
                handed[:] = batch       # as the step hands it on: placed
                return fn(params, opt_state, *batch)

            try:
                with _record.span(rec, "bps.setup.first_step",
                                  step_num=self.step_count):
                    if fn is not None:
                        self._step_fn = dispatch
                    loss = type(self).step(self, batch)
                    if handed:
                        self._step_traced = _record.note_step_memory(
                            rec, fn, self.params, self.opt_state, *handed)
                    return loss
            finally:
                if fn is not None:
                    self._step_fn = fn
                _record.close(rec)
                self.__dict__.pop("step", None)

        self.step = first_step
    return __init__


def _batch_samples(batch) -> Optional[int]:
    """Global sample count of a batch (leading axis of its first
    non-scalar leaf) for StepStats throughput; None when unknowable."""
    for leaf in jax.tree_util.tree_leaves(batch):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 1:
            return int(shape[0])
    return None


class DistributedTrainer:
    """Owns params + optimizer state and a compiled distributed train step.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar`` on a *local* batch shard.
      params: initial parameter pytree (will be broadcast-consistent by
        construction: the same host value is replicated to every device).
      tx: inner optax transformation (e.g. ``optax.adamw(1e-3)``).
      mesh: device mesh; defaults to the global one from ``bps.init()``.
      partition_bytes: bucket size (default ``BPS_PARTITION_BYTES``, 4 MB)
        wherever the exchange runs in buckets: the PS path, a ``dcn``
        mesh, a custom ``reducer``, ``compression``. On the default path
        (ICI-only mesh, ``psum_reducer``) each gradient leaf is reduced as
        it is and the value changes nothing; it is accepted all the same.
        The constructor logs which form was taken (``BPS exchange:``).
      backward_passes_per_step: local gradient accumulation (reference:
        torch/__init__.py:83-113).
      reducer: collective strategy over ``(flat bucket, axes)`` — plain
        psum by default (and then, on an ICI-only mesh, no bucket is
        built), a compressing reducer from byteps_tpu.ops.compression
        otherwise.
      name: stable tensor-declaration name for the PS exchange; defaults
        to a hash of the parameter tree's structure+shapes+dtypes (stable
        across restarts, unlike a bare creation counter). When several
        trainers share a structure, later ones get positional suffixes
        (-1, -2, …) by per-structure creation order — deterministic
        given the same program order, but a worker restarted MID-JOB
        replays that order from zero, so elastic PS setups with multiple
        same-structure trainers must pass explicit names.
    """

    # per-structure-hash creation counts (never pruned: freeing a name on
    # GC would let a later same-structure trainer reuse it against a
    # live PS server still holding the dead trainer's keys)
    _name_counts: dict = {}

    @property
    def params(self):
        """The parameter tree. Reading it is a synchronization point:
        with the cross-step pipeline engaged (``BPS_CROSS_STEP``) any
        in-flight straggler tail is drained first, so external readers
        (checkpointing, metrics, tests) always observe fully-applied
        weights — the pipeline is invisible except to the clock. A
        trainer whose tail FAILED keeps raising here: the weights are
        partially stepped and must never be read as if healthy."""
        d = getattr(self, "_cross_driver", None)
        if d is not None and (d.pending or d.failed):
            d.drain()
        return self._params

    @params.setter
    def params(self, value):
        # an external write (checkpoint restore) must not race the
        # in-flight tails — and must not be refused on a POISONED
        # trainer, since installing fresh state is exactly the
        # documented remedy: join the tails without raising, lift the
        # partial-state error, and mark the driver for resync (the
        # next cross step re-reads the tree and re-syncs opt state)
        d = getattr(self, "_cross_driver", None)
        if d is not None:
            d.supersede()
        self._params = value

    @staticmethod
    def _default_name(params) -> str:
        """Structure-derived default so a restarted worker maps onto the
        same PS keys regardless of trainer creation order — a counter
        default would silently alias one trainer's gradients onto
        another's equal-sized buckets after a mid-job restart."""
        import hashlib
        leaves = jax.tree_util.tree_leaves(params)
        treedef = jax.tree_util.tree_structure(params)
        sig = str(treedef) + "|" + "|".join(
            f"{tuple(getattr(l, 'shape', ()))}:"
            f"{getattr(l, 'dtype', type(l).__name__)}" for l in leaves)
        return "trainer-" + hashlib.sha1(sig.encode()).hexdigest()[:10]

    @_recorded
    def __init__(self, loss_fn: Callable, params, tx: optax.GradientTransformation,
                 mesh: Optional[Mesh] = None, partition_bytes: Optional[int] = None,
                 backward_passes_per_step: int = 1,
                 reducer: Reducer = psum_reducer,
                 compression: Optional[dict] = None,
                 min_compress_bytes: Optional[int] = None,
                 donate: bool = True, name: Optional[str] = None,
                 shard_rank: Optional[int] = None) -> None:
        if mesh is None:
            # a MirroredStrategy scope takes precedence over the global mesh
            from .strategy import current_strategy
            strat = current_strategy()
            if strat is not None:
                mesh = strat.mesh
            else:
                mesh = (GlobalState.get().mesh if GlobalState.initialized()
                        else make_mesh())
        if partition_bytes is None:
            partition_bytes = (GlobalState.get().config.partition_bytes
                               if GlobalState.initialized() else 4 << 20)
        if min_compress_bytes is None:
            min_compress_bytes = (GlobalState.get().config.min_compress_bytes
                                  if GlobalState.initialized() else 65536)
        self.mesh = mesh
        self.axes = data_axes(mesh)
        self.backward_passes_per_step = backward_passes_per_step
        gs = GlobalState._instance if GlobalState.initialized() else None
        if name is None:
            # structure-derived default: stable across restarts and
            # creation order. Same-structure trainers get positional
            # suffixes (base, base-1, base-2, … in creation order) — a
            # restart replays the same sequence ONLY if the whole
            # program replays, so warn when the PS backend can
            # transparently reconnect (a worker restarted mid-job could
            # alias an earlier same-structure trainer's keys).
            base = self._default_name(params)
            n = DistributedTrainer._name_counts.get(base, 0)
            DistributedTrainer._name_counts[base] = n + 1
            name = base if n == 0 else f"{base}-{n}"
            if n > 0:
                pb = gs.ps_backend if gs is not None else None
                if pb is not None and getattr(pb, "reconnect_secs", 0) > 0:
                    from .common.logging import get_logger
                    get_logger().warning(
                        "multiple trainers share a parameter structure and "
                        "rely on creation-order default names (%s) while PS "
                        "reconnect is enabled — pass explicit name= so a "
                        "restarted worker cannot alias another trainer's "
                        "keys", name)
        self._name = name
        if (gs is not None and gs.config.pp_stages > 1):
            # MPMD pipeline parallelism has its own driver: the model
            # is cut across WORKERS and this trainer's whole-model
            # step would silently train only replicas. Refuse loudly.
            raise ValueError(
                f"BPS_PP_STAGES={gs.config.pp_stages}: DistributedTrainer "
                f"is the data-parallel step — pipeline-parallel jobs "
                f"run byteps_tpu.pipeline.PipelineStageDriver (one per "
                f"stage worker, docs/pipeline-parallelism.md); PP × DP "
                f"composes by giving each stage's driver this trainer's "
                f"PS exchange for its per-stage gradient sum")
        eng = gs.engine if gs is not None else None
        self._ps_engine = (eng if eng is not None and
                           getattr(eng, "ps_exchange", None) is not None
                           else None)
        self._async_worker = None
        if (gs is not None and gs.ps_backend is not None
                and getattr(gs.ps_backend, "async_mode", False)):
            # Async-PS (BPS_ENABLE_ASYNC): the reference async
            # DistributedOptimizer — each worker steps its LOCAL optimizer,
            # pushes the weight DELTA, and pulls fresh global weights, with
            # no inter-worker barrier (torch/__init__.py:186-214,
            # server.cc:310-314). Optimizer state stays worker-local.
            if reducer is not psum_reducer:
                raise ValueError(
                    "custom reducers run on the collective path and would "
                    "be silently unused in async-PS mode")
            if compression:
                raise ValueError(
                    "compression is not supported in async-PS mode (the "
                    "reference's async server folds raw weight deltas, "
                    "server.cc:310-314) — drop BPS_ENABLE_ASYNC or the "
                    "compression kwargs")
            self.tx = tx
            self._place_params(params)
            self._ostate_spec = P()
            self._init_opt_state()
            self._loss_fn = loss_fn
            with _record.span(self._setup, "bps.setup.build_step"):
                self._grad_fn, self._apply_fn = self._build_ps_step(
                    donate=False)
            from .server.ps_mode import AsyncPSWorker
            # server-side init is idempotent (first init allocates, later
            # inits are no-ops — NOT a rendezvous), so every worker seeds
            # with the same initial values and proceeds immediately
            self._async_worker = AsyncPSWorker(gs.ps_backend, self.params,
                                               name=self._name,
                                               init_store=True,
                                               registry=gs.registry)
            # the wire-dtype cast fuses into the jitted subtract, so a
            # bf16 wire (BPS_ASYNC_WIRE_DTYPE) halves D2H bytes too
            wire = os.environ.get("BPS_ASYNC_WIRE_DTYPE") or None

            def _delta(a, b):
                d = jnp.subtract(a, b)
                return d.astype(wire) if wire else d

            self._delta_fn = jax.jit(
                lambda new, old: jax.tree_util.tree_map(_delta, new, old))
            self._accum = None
            self.step_count = 0
            return
        if self._ps_engine is not None:
            # PS deployment (BPS_ENABLE_PS, sync): the reference
            # DistributedOptimizer split — framework computes grads, the
            # push_pull hop syncs them across worker processes, the
            # optimizer steps locally (torch/__init__.py:115-174). Here:
            # jitted grad step with LOCAL-mesh pmean (the intra-node NCCL
            # stage), host PS exchange (compressed when ``compression``
            # kwargs are declared), jitted apply step. Accumulation for
            # backward_passes_per_step happens host-side between sync
            # boundaries, so no wire bandwidth is spent mid-window.
            if reducer is not psum_reducer:
                raise ValueError(
                    "custom reducers run on the collective path and would "
                    "be silently unused in PS mode — express lossy "
                    "exchange via compression kwargs instead")
            if compression:
                gs.registry.declare(self._name, **compression)
            # trainer-private exchange: same backend + registry (stable
            # keys), but own plans/round counters and THIS trainer's
            # partition/compression thresholds
            from .server.ps_mode import PSGradientExchange
            self._ps_exchange = PSGradientExchange(
                gs.ps_backend, partition_bytes=partition_bytes,
                registry=gs.registry, min_compress_bytes=min_compress_bytes,
                watchdog_sec=gs.config.watchdog_sec,
                compress=gs.config.compress)
            self._ps_exchange.timeline = gs.timeline
            self._ps_world = eng.ps_world
            # streamed step tail (pull → H2D → chunked apply pipelined
            # per bucket); BPS_APPLY_CHUNKED=0 restores the monolithic
            # wait-all → device_put-all → fused-apply tail for A/B
            self._apply_chunked = os.environ.get(
                "BPS_APPLY_CHUNKED", "1") != "0"
            # streamed step HEAD (staged backward → incremental ingest:
            # bwd(group k+1) ∥ D2H/push(group k)); BPS_BWD_STAGED=0
            # restores the monolithic one-program backward for A/B,
            # BPS_BWD_GROUPS caps the number of backward segments
            self._bwd_staged = os.environ.get(
                "BPS_BWD_STAGED", "1") != "0"
            self._bwd_groups = int(os.environ.get("BPS_BWD_GROUPS", "0")
                                   or 0)
            # cross-step pipeline (BPS_CROSS_STEP=0 for draining A/B
            # barrier steps): step() hands the straggler pull/apply
            # tail to a background thread and the NEXT step's staged
            # segments gate on per-leaf param readiness — see
            # cross_step.CrossStepDriver. Engages on top of the staged
            # head + chunked tail; falls back with them.
            self._cross_step = os.environ.get(
                "BPS_CROSS_STEP", "1") != "0"
            self._cross_driver = None
            self._staged = None      # active signature's StagedGrad /
            #                          False (fell back) / None (unbuilt)
            self._staged_cache = {}  # batch signature -> StagedGrad|False
            #                          (per-sig, like jit's retrace cache:
            #                          alternating shapes must not
            #                          rebuild, and one unstageable shape
            #                          must not disable the others)
            self._staged_cache_cap = max(
                1, int(os.environ.get("BPS_STAGED_CACHE", "8") or 8))
            self._staged_cache_warned = False
            self._ps_donate = donate
            self._chunked = None        # built on first streamed step
            self._h2d_ex = None         # lazy single-thread H2D dispatcher
            self._opt_state_at_init = None   # set below: restore detection
            self.tx = tx          # plain inner optimizer: sync is the hop
            self._place_params(params)
            self._ostate_spec = P()
            self._init_opt_state()
            self._opt_state_at_init = self.opt_state
            self._loss_fn = loss_fn
            with _record.span(self._setup, "bps.setup.build_step"):
                self._grad_fn, self._apply_fn = self._build_ps_step(donate)
            self._accum = None
            self.step_count = 0
            # ZeRO-style sharded weight update (BPS_SHARDED_UPDATE,
            # byteps_tpu.sharded_update): partition the bucket groups
            # across the dp replicas — pull/apply only the owned shard
            # (optimizer state allocated for it alone), publish the
            # updated params, fetch the rest. Probe-or-fallback. Built
            # at the FIRST step (not here): tests and the bench swap
            # the exchange's backend right after construction, and the
            # probe's plan/init_key must land on the final backend —
            # but before the first round is created, so even step 1
            # restricts its pulls.
            self._sharded = None
            self._sharded_epoch = 0
            cfg = gs.config
            self._sharded_cfg = None
            if cfg.sharded_update and self._apply_chunked \
                    and backward_passes_per_step == 1:
                world = cfg.shard_world or self._ps_world
                rank = (shard_rank if shard_rank is not None
                        else (cfg.shard_rank if cfg.shard_rank >= 0
                              else cfg.worker_id))
                self._sharded_cfg = (rank, world)
            elif cfg.sharded_update:
                from .sharded_update import _fallback
                _fallback("BPS_APPLY_CHUNKED=0 or "
                          "backward_passes_per_step>1 (the sharded "
                          "tail is the chunked tail)")
            return
        # Size-1 data axes reduce to identity psums; dropping them leaves
        # the exchange nothing to do on a single chip (the gradients pass
        # through, whatever form the axes' names would have chosen).
        # Lossy paths keep them — compression and custom reducers must see
        # the gradient even at world 1 (reference: BYTEPS_FORCE_DISTRIBUTED
        # tests run 1-worker compressed) — and so keep their buckets.
        lossless = compression is None and reducer is psum_reducer
        comm_axes = (tuple(a for a in self.axes if mesh.shape[a] > 1)
                     if lossless else self.axes)
        reduce_world = 1
        for a in comm_axes:
            reduce_world *= mesh.shape[a]
        self.tx = distributed_optimizer(tx, axes=comm_axes,
                                        partition_bytes=partition_bytes,
                                        backward_passes_per_step=backward_passes_per_step,
                                        reducer=reducer,
                                        compression=compression,
                                        min_compress_bytes=min_compress_bytes,
                                        compression_state_world=mesh.size,
                                        compression_reduce_world=reduce_world)
        _log_exchange_form(comm_axes, reducer, compression)
        self._place_params(params)
        if compression:
            # compressor state (EF error, momentum) is per-device: leading
            # device axis sharded over the whole mesh (see _make_compressed)
            from .parallel.sharding import opt_state_specs
            self._ostate_spec = opt_state_specs(
                self.tx, self.params,
                jax.tree_util.tree_map(lambda _: P(), self.params),
                comp_axes=tuple(mesh.axis_names))
        else:
            self._ostate_spec = P()
        self._init_opt_state()
        self._loss_fn = loss_fn
        with _record.span(self._setup, "bps.setup.build_step"):
            self._step_fn = self._build_step(donate)
        self.step_count = 0

    def _place_params(self, params) -> None:
        """A copy of the parameters on the mesh, not an alias: the step
        donates its param buffers, and device_put aliases when the sharding
        already matches — donation must never invalidate the caller's
        arrays."""
        from .data import _host_bytes
        replicated = NamedSharding(self.mesh, P())
        with _record.span(self._setup, "bps.setup.place_params",
                          bytes=_host_bytes(params)):
            self.params = jax.tree_util.tree_map(
                lambda x: jax.device_put(jnp.array(x), replicated), params)

    def _init_opt_state(self) -> None:
        from .parallel.sharding import init_sharded_state
        with _record.span(self._setup, "bps.setup.opt_init"):
            self.opt_state = init_sharded_state(self.tx, self.params,
                                                self._ostate_spec, self.mesh)

    def setup_record(self) -> dict:
        """What this trainer's start took, what JAX compiled for it and
        which kernels its trace chose (``common/setup_record.py``,
        docs/timeline.md)."""
        return self._setup

    def step_account(self) -> dict:
        """``{"step_memory", "kept"}`` of the set-up record: the compiled
        step's bytes by the compiler's categories, there since the first
        step, and what the step's forward hands its backward by checkpoint
        name and ``bps.*`` scope, read from the step's jaxpr on the first call
        here and kept (``common/kept_values.py``; docs/timeline.md). Before
        the first step, and on the PS branches, ``{}`` and None."""
        rec, traced = self._setup, self._step_traced
        if traced is not None:
            from .common.kept_values import kept
            rec["kept"], self._step_traced = kept(traced.jaxpr), None
        return {"step_memory": rec["step_memory"], "kept": rec["kept"]}

    def _build_step(self, donate: bool):
        axes, mesh, loss_fn, tx = self.axes, self.mesh, self._loss_fn, self.tx
        batch_spec = P(axes) if axes else P()
        # size-1 axes are identity means — keep them out of the lowered
        # collective (they cost an HLO op and a fusion barrier for nothing)
        loss_axes = tuple(a for a in axes if mesh.shape[a] > 1)

        def step(params, opt_state, batch):
            # bps.* scopes name the step's phases in a profiler trace
            # (tx.update opens bps.exchange and bps.optimizer itself)
            with jax.named_scope("bps.model"):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            updates, opt_state = tx.update(grads, opt_state, params)
            with jax.named_scope("bps.optimizer"):
                params = optax.apply_updates(params, updates)
            # loss is per-shard; report the global mean
            if loss_axes:
                loss = jax.lax.pmean(loss, loss_axes)
            return params, opt_state, loss

        shard_fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), self._ostate_spec, batch_spec),
            out_specs=(P(), self._ostate_spec, P()),
            check_vma=False)
        donate_argnums = (0, 1) if donate else ()
        # Explicit in_shardings let step() hand a HOST batch straight to
        # the jitted call — placement happens inside the one dispatch,
        # like a plain jitted step — instead of paying a separate eager
        # device_put dispatch per step (measured as the entire
        # vs_baseline gap on the flagship bench, docs/performance.md).
        rep = NamedSharding(mesh, P())
        ostate_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self._ostate_spec,
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(
            shard_fn,
            in_shardings=(rep, ostate_shardings,
                          NamedSharding(mesh, batch_spec)),
            donate_argnums=donate_argnums)

    def _build_ps_step(self, donate: bool):
        """Split step for PS deployments: grads and update are separate
        XLA programs with the host exchange hop in between."""
        axes, mesh, loss_fn, tx = self.axes, self.mesh, self._loss_fn, self.tx
        batch_spec = P(axes) if axes else P()

        def gstep(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            if axes:
                # intra-worker stage (the reference's local NCCL reduce):
                # grads leave this jit already averaged over the LOCAL mesh
                grads = jax.lax.pmean(grads, axes)
                loss = jax.lax.pmean(loss, axes)
            return loss, grads

        grad_fn = jax.jit(jax.shard_map(
            gstep, mesh=mesh, in_specs=(P(), batch_spec),
            out_specs=(P(), P()), check_vma=False))

        def astep(params, opt_state, grads):
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        apply_fn = jax.jit(astep,
                           donate_argnums=(0, 1) if donate else ())
        return grad_fn, apply_fn

    def _accumulate(self, grads):
        """Host-side running mean over the backward_passes_per_step window
        (matches optax.MultiSteps on the collective path). Returns None
        mid-window — no comm, no update — and the accumulated grads at
        the sync boundary. Increments step_count."""
        k = self.backward_passes_per_step
        i = self.step_count % k
        self.step_count += 1
        if k == 1:
            return grads
        host_g = jax.tree_util.tree_map(np.asarray, grads)
        if i == 0:
            self._accum = host_g
        else:
            self._accum = jax.tree_util.tree_map(
                lambda acc, g, n=i + 1: acc + (g - acc) / n,
                self._accum, host_g)
        if i + 1 < k:
            return None
        out, self._accum = self._accum, None
        return out

    def _next_shard_epoch(self) -> int:
        """One shared, monotonic epoch counter for the sharded tail's
        ``mark_epoch``/``wait_epoch`` bookkeeping, whichever path runs
        the step — a draining step amid cross steps must not mark an
        epoch below what the cross tails already published."""
        d = getattr(self, "_cross_driver", None)
        base = max(self._sharded_epoch,
                   d._epoch if d is not None else 0)
        self._sharded_epoch = base + 1
        return self._sharded_epoch

    def _sharded_active(self):
        """The live ShardedUpdateState, or None — re-checked at every
        round creation so a disable (externally restored opt_state, a
        failed probe) can never leave a round with restricted pulls and
        an unsharded tail."""
        st = getattr(self, "_sharded", None)
        if st is None:
            return None
        if (self._chunked is None
                and self._opt_state_at_init is not None
                and self.opt_state is not self._opt_state_at_init):
            # opt_state was replaced before the first step: the tail
            # will keep the fused apply (see _ensure_streamed_tail) —
            # owned-shard state cannot honor the restored full tree
            from .sharded_update import _fallback
            _fallback("opt_state was replaced before the first step "
                      "(restored full-tree state needs the fused apply)")
            st.close()
            self._sharded = None
            return None
        if self._chunked is not None and not self._chunked.decomposable:
            st.close()
            self._sharded = None
            return None
        return st

    def _ps_step(self, batch) -> jnp.ndarray:
        batch = self.shard_batch(batch)
        if self._sharded_cfg is not None:
            rank, world = self._sharded_cfg
            self._sharded_cfg = None
            gs0 = GlobalState._instance
            from .sharded_update import build_sharded_state
            self._sharded = build_sharded_state(
                self._ps_exchange, self.params, self.tx, self._name,
                rank, world,
                timeline=gs0.timeline if gs0 is not None else None)
            mem = getattr(self, "_restored_membership", None)
            if self._sharded is not None and mem:
                # sharded checkpoint carried a membership view: the
                # owner map is the authoritative shared state — install
                # it verbatim (no handoff; the slices came from disk)
                self._sharded.adopt_membership(
                    mem["owner"], mem["member_epoch"],
                    live=mem.get("live"))
                self._restored_membership = None
        if (self._bwd_staged and self._apply_chunked
                and self.backward_passes_per_step == 1):
            # the staged program is shape-specialized; each new batch
            # signature (structure/shape/dtype) builds once and is
            # cached, like a jit retrace — including a per-signature
            # False for shapes that don't stage (bounded: real loops
            # cycle few signatures; an unbounded shape stream would
            # already be retracing every jit in the step)
            sig = jax.tree_util.tree_structure(batch), tuple(
                (tuple(l.shape), str(l.dtype))
                for l in jax.tree_util.tree_leaves(batch))
            staged = self._staged_cache.get(sig)
            if staged is None and sig not in self._staged_cache:
                if len(self._staged_cache) < self._staged_cache_cap:
                    self._build_staged_head(batch)
                    self._staged_cache[sig] = staged = self._staged
                elif not self._staged_cache_warned:
                    # silent before: the 9th signature just stopped
                    # staging with no trace of why
                    self._staged_cache_warned = True
                    from .common.logging import get_logger
                    get_logger().warning(
                        "staged-head signature cache is full (%d batch "
                        "signatures): new shapes run the monolithic "
                        "head from here on — raise BPS_STAGED_CACHE if "
                        "the input pipeline legitimately cycles more "
                        "shapes", self._staged_cache_cap)
            self._staged = staged if staged is not None else False
            if staged not in (None, False):
                if self._cross_step:
                    if (self._cross_driver is None
                            and self._chunked is not None
                            and self._chunked.decomposable):
                        # first staged step ran the draining path and
                        # built the chunked groups; engage the
                        # cross-step pipeline from here on
                        from .cross_step import CrossStepDriver
                        self._cross_driver = CrossStepDriver(self)
                    if self._cross_driver is not None:
                        self.step_count += 1
                        loss = self._cross_driver.step(staged, batch)
                        gs = GlobalState._instance
                        if gs is not None and gs.timeline is not None:
                            gs.timeline.set_step(self.step_count)
                        return loss
                return self._ps_step_staged(batch)
        loss, grads = self._grad_fn(self.params, batch)
        grads = self._accumulate(grads)
        if grads is None:
            return loss
        # k==1 hands the jax arrays straight to exchange — it starts all
        # copy_to_host_async transfers before reading any, so the D2H
        # copies overlap instead of serializing per leaf
        gs = GlobalState._instance
        tl = gs.timeline if gs is not None else None
        if tl is not None:
            t0 = time.time()
            jax.block_until_ready(grads)
            observe_stage("REDUCE_WAIT", time.time() - t0)
            tl.record(self._name, "REDUCE_WAIT", t0, time.time() - t0)
        if self._apply_chunked:
            loss2 = self._ps_step_streamed(grads, loss, tl)
            if tl is not None:
                tl.set_step(self.step_count)
            return loss2
        # monolithic tail (BPS_APPLY_CHUNKED=0): wait for every bucket,
        # one whole-tree device_put, one fused apply
        t0 = time.time()
        summed = self._ps_exchange.exchange(grads, name=self._name)
        observe_stage("PS_PUSH_PULL", time.time() - t0)
        if tl is not None:
            tl.record(self._name, "PS_PUSH_PULL", t0, time.time() - t0)
        if self._ps_world > 1:
            summed = jax.tree_util.tree_map(
                lambda x: x / self._ps_world, summed)
        rep = NamedSharding(self.mesh, P())
        gdev = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, rep), summed)
        self.params, self.opt_state = self._apply_fn(
            self.params, self.opt_state, gdev)
        if tl is not None:
            tl.set_step(self.step_count)
        return loss

    def _ensure_streamed_tail(self, grads) -> None:
        """First streamed step: derive the exchange's bucket groups and
        build the chunked apply (or learn that the tx isn't leafwise-
        decomposable and keep the fused apply for the tail)."""
        if self._chunked is not None:
            self._sync_chunk_states()
            return
        from .optim import ChunkedApply
        groups = self._ps_exchange.leaf_groups(grads, name=self._name)
        st = getattr(self, "_sharded", None)
        self._chunked = ChunkedApply(
            self.tx, self.params, groups, donate=self._ps_donate,
            owned=st.plan.owned_set if st is not None else None)
        if (self._chunked.decomposable
                and self.opt_state is not self._opt_state_at_init):
            # the caller installed its own state (checkpoint restore)
            # between construction and the first step: a chunked
            # re-init would silently discard it, so keep the fused
            # apply, which consumes self.opt_state as-is
            from .common.logging import get_logger
            get_logger().info(
                "opt_state was replaced before the first step — keeping "
                "the fused optimizer apply so the restored state is "
                "honored (streamed H2D overlap stays on)")
            self._chunked.decomposable = False
            self._chunked.states = None   # unused duplicate: free it
        if self._chunked.decomposable:
            # per-group states REPLACE the fused full-tree state (same
            # per-leaf init values; count scalars live per group) — the
            # source of truth the chunked applies update in place, and
            # what checkpoints of a chunked-mode trainer round-trip
            self.opt_state = self._chunked.states
        # sharded checkpoint restore (restore_sharded): the per-group
        # slices install over the fresh states now that they exist
        self._install_restored_groups()
        # the restore-detection compare above is one-shot; keeping the
        # alias would pin a full optimizer-state tree (2× params for
        # adam) on device for the trainer's lifetime
        self._opt_state_at_init = None
        if self._h2d_ex is None:
            from concurrent.futures import ThreadPoolExecutor
            self._h2d_ex = ThreadPoolExecutor(
                1, thread_name_prefix="bps-ps-h2d")

    def _sync_chunk_states(self) -> None:
        """Adopt an external write to the public ``opt_state`` attribute
        after chunked mode engaged (e.g. restoring a checkpoint of a
        chunked-mode trainer, whose state IS the per-group list).
        A write whose structure doesn't match the group states can't be
        split generically — fail loudly instead of silently ignoring it."""
        if not self._chunked.decomposable \
                or self.opt_state is self._chunked.states:
            return
        import jax as _jax
        if (_jax.tree_util.tree_structure(list(self.opt_state))
                == _jax.tree_util.tree_structure(self._chunked.states)):
            self._chunked.states = list(self.opt_state)
            self.opt_state = self._chunked.states
            return
        raise ValueError(
            "opt_state was replaced mid-training with a structure that "
            "doesn't match the chunked per-group states — restore the "
            "state before the first step, or set BPS_APPLY_CHUNKED=0 "
            "to keep the fused full-tree optimizer state")

    def _build_staged_head(self, batch) -> None:
        """First staged step: build the K-segment backward (staged_grad)
        from the exchange's bucket groups, or learn why we can't and
        pin the monolithic head. The build probes the staged program
        against ``_grad_fn`` on this real (params, batch) and keeps it
        only on BITWISE equality, so flipping ``BPS_BWD_STAGED`` can
        never change training numerics."""
        from .common.logging import get_logger
        self._staged = False
        if self.mesh.size != 1:
            # the staged segments run outside shard_map, so the
            # intra-worker pmean stage has nowhere to live — the staged
            # head targets the classic one-chip-per-worker PS geometry
            # where the host hop is the only reduction
            get_logger().info(
                "staged PS head falls back: local mesh has %d devices "
                "(the staged backward bypasses the intra-worker pmean)",
                self.mesh.size)
            return
        from .staged_grad import build_staged_grad
        groups = self._ps_exchange.leaf_groups(self.params,
                                               name=self._name)
        # cross-step mode also cuts the FORWARD at group boundaries
        # (roughly doubling the useful segment count), so next-step
        # forward segments can gate on individual groups' applies
        if self._cross_step:
            max_seg = self._bwd_groups or max(2, min(16, 2 * len(groups)))
        else:
            max_seg = self._bwd_groups or max(2, min(8, len(groups)))
        staged = build_staged_grad(
            self._loss_fn, self.params, batch, groups=groups,
            fused_fn=self._grad_fn, max_segments=max_seg,
            name=self._name, forward_cuts=self._cross_step)
        if staged is not None:
            self._staged = staged

    def _ps_step_staged(self, batch) -> jnp.ndarray:
        """Streamed step HEAD: run the backward as K jitted segments and
        feed each group's gradients to the exchange the moment its
        segment finishes — D2H + pack + push of group k overlap the
        differentiation of group k+1 (the reference's per-tensor push
        interception), then the PR-1 streamed tail consumes the same
        handle (pull → H2D → chunked apply). Composed, the full BytePS
        pipeline: bwd ∥ push ∥ server-sum ∥ pull ∥ apply."""
        gs = GlobalState._instance
        tl = gs.timeline if gs is not None else None
        self.step_count += 1
        t_ex = time.time()
        st = self._sharded_active()
        handle = self._ps_exchange.exchange_ingest(
            self.params, name=self._name,
            sharded=st.plan.round_view() if st is not None else None)
        loss = None
        try:
            for seg in self._staged.run(self.params, batch):
                observe_stage("PS_BWD_SEG", seg.dur)
                if tl is not None:
                    tl.record(self._name, "PS_BWD_SEG", seg.t0, seg.dur,
                              seg.index)
                if seg.loss is not None:
                    loss = seg.loss
                if seg.leaf_ids:
                    handle.feed(seg.leaf_ids, seg.grads)
            handle.finish()
        except BaseException as e:
            handle.abort(e)     # unblock the tail consumer
            raise
        loss = self._ps_step_streamed(self.params, loss, tl,
                                      handle=handle, t_ex=t_ex)
        if tl is not None:
            tl.set_step(self.step_count)
        return loss

    def drain(self) -> None:
        """Synchronize the cross-step pipeline (no-op otherwise): join
        every in-flight straggler tail and publish the final weights —
        the explicit end-of-training barrier. Reading ``params`` does
        the same implicitly."""
        d = getattr(self, "_cross_driver", None)
        if d is not None and (d.pending or d.failed):
            d.drain()
        st = getattr(self, "_sharded", None)
        if st is not None:
            # a dead publisher means frames this trainer OWED its peers
            # never shipped — surface it at the sync point, loudly
            st.check_publisher()

    def reshard(self, live, weights=None,
                handoff_timeout_ms: Optional[int] = None):
        """Live membership change (JOIN/LEAVE) for the sharded update:
        drain this trainer's in-flight tails to a step boundary, then
        bump the membership epoch — ownership re-shards over ``live``
        with minimal movement and moved groups' optimizer state hands
        off through the param mailbox (docs/elasticity.md). EVERY
        participating replica's trainer must make the same call at the
        same step boundary; ``weights=None`` re-balances from the live
        per-layer byte counters when they agree across replicas (falls
        back to the static plan bytes on a cold registry)."""
        st = getattr(self, "_sharded", None)
        if st is None:
            raise RuntimeError(
                "reshard needs an engaged sharded update "
                "(BPS_SHARDED_UPDATE=1, dp>1, at least one step run) — "
                "see docs/elasticity.md")
        self.drain()
        if weights is None:
            from .sharded_update import live_group_weights
            gs = GlobalState._instance
            compress = (gs.config.compress if gs is not None else "none")
            if compress != "auto":
                # pinned codecs (incl. none) push identical frame sizes
                # on every replica, so the cumulative counters agree;
                # "auto" traces diverge per worker — static bytes keep
                # the plans deterministic (live_group_weights docs)
                weights = live_group_weights(st.plan, self._name)
        flat, treedef = jax.tree_util.tree_flatten(self._params)
        out = st.reshard(self._chunked, flat, live, weights=weights,
                         handoff_timeout_ms=handoff_timeout_ms)
        return out

    def restore_sharded(self, path: str) -> dict:
        """Restore a SHARDED checkpoint (``save_sharded_checkpoint``:
        full params + per-group 1/dp opt_state slices + membership
        meta) WITHOUT tripping the restored-full-tree fallback: params
        install now; the per-group optimizer slices and the saved
        membership (owner map, member epoch) install when the first
        step builds the sharded tail — so training continues sharded,
        composed with ``BPS_SHARDED_UPDATE=1``, never silently dropping
        to the full apply. Call between construction and the first
        step. Returns the checkpoint meta."""
        if getattr(self, "_chunked", None) is not None:
            raise RuntimeError(
                "restore_sharded must run before the first step — the "
                "chunked tail already built its optimizer states")
        from .checkpoint import restore_sharded_checkpoint
        params, blobs, step, meta = restore_sharded_checkpoint(
            path, self._params)
        rep = NamedSharding(self.mesh, P())
        self.params = jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), rep), params)
        self.step_count = int(step)
        # deliberately NOT touching self.opt_state: the identity check
        # in _sharded_active/_ensure_streamed_tail is exactly the
        # full-tree fallback this path exists to avoid
        self._restored_groups = dict(blobs)
        self._restored_membership = meta.get("sharded")
        return meta

    def _install_restored_groups(self) -> None:
        """First streamed step, after the chunked states exist: unpack
        the sharded checkpoint's per-group opt_state slices into the
        owned groups' states (bitwise resume). Non-owned groups'
        slices are ignored here — their owners install their own."""
        blobs = getattr(self, "_restored_groups", None)
        if not blobs:
            return
        if not self._chunked.decomposable:
            raise RuntimeError(
                "sharded checkpoint restore needs the decomposable "
                "chunked tail (it holds per-group optimizer state) — "
                "the optimizer changed since the save, or "
                "BPS_APPLY_CHUNKED=0")
        from .sharded_update import unpack_opt_state
        st = getattr(self, "_sharded", None)
        flat = jax.tree_util.tree_leaves(self._params)
        for gi, payload in sorted(blobs.items()):
            if gi >= len(self._chunked.groups):
                raise ValueError(
                    f"sharded checkpoint has a slice for group {gi} "
                    f"but the plan has {len(self._chunked.groups)} "
                    f"groups — different bucket plans")
            if st is not None and gi not in st.plan.owned_set:
                continue
            template = self._chunked.states[gi]
            if template is None:
                template = self._chunked.init_group(
                    gi, [flat[li] for li in self._chunked.groups[gi]])
            self._chunked.adopt_group(
                gi, unpack_opt_state(payload, template))
        missing = [gi for gi in
                   (st.plan.owned if st is not None
                    else range(len(self._chunked.groups)))
                   if gi not in blobs]
        if missing:
            from .common.logging import get_logger
            get_logger().warning(
                "sharded checkpoint restore: no slice for owned "
                "group(s) %s — their optimizer moments restart from "
                "init (the owner's save was lost?)", missing)
        self._restored_groups = None

    def close(self) -> None:
        """Release the trainer's PS-tail resources (H2D dispatch thread,
        private exchange executors). Idempotent; only meaningful for
        PS-mode trainers — collective-path and async-PS trainers hold
        none of these (getattr: their __init__ branches never create
        the attributes). Drains the cross-step pipeline first — the
        tails need the executors being shut down."""
        try:
            self.drain()
        finally:
            st = getattr(self, "_sharded", None)
            try:
                if st is not None:
                    self._sharded = None
                    st.close()    # flushes queued frames; raises on a
                    #               dead publisher (loud, after flush)
            finally:
                h2d = getattr(self, "_h2d_ex", None)
                if h2d is not None:
                    h2d.shutdown(wait=False)
                    self._h2d_ex = None
                ex = getattr(self, "_ps_exchange", None)
                if ex is not None:
                    ex.close()

    def _ps_step_streamed(self, grads, loss, tl, handle=None,
                          t_ex: Optional[float] = None) -> jnp.ndarray:
        """Streamed step tail: consume the exchange's leaf-ready stream,
        device_put each leaf from a dispatch thread the moment it lands
        (H2D overlaps still-in-flight pulls of later buckets), and
        jit-apply the optimizer per bucket group as its leaves arrive —
        bucket 0's weights update while bucket N is still on the wire.
        Non-decomposable optimizers keep the fused apply at the end but
        still get the streamed H2D overlap.

        ``handle``: a pre-started leaf-ready stream (the staged head's
        ``exchange_ingest`` round, whose pushes began mid-backward);
        ``grads`` then only serves as the structure template for the
        first-step group derivation. None = start an
        ``exchange_stream`` round from the full ``grads`` tree."""
        if handle is None:
            st0 = self._sharded_active()
            self._ensure_streamed_tail(grads)
            handle = self._ps_exchange.exchange_stream(
                grads, name=self._name,
                sharded=(st0.plan.round_view()
                         if st0 is not None else None))
        else:
            self._ensure_streamed_tail(grads)
        if t_ex is None:
            t_ex = time.time()
        rep = NamedSharding(self.mesh, P())
        flat, treedef = jax.tree_util.tree_flatten(self.params)
        shapes = [l.shape for l in flat]
        world = self._ps_world
        name = self._name

        def h2d(li: int, arr: np.ndarray):
            t0 = time.time()
            a = arr.reshape(shapes[li])
            if world > 1:
                a = a / world         # same host-side divide per leaf as
            d = jax.device_put(a, rep)  # the monolithic tail's tree_map
            observe_stage("PS_H2D", time.time() - t0)
            if tl is not None:
                tl.record(name, "PS_H2D", t0, time.time() - t0, li)
            return d

        chunked = self._chunked
        rnd_state = getattr(handle, "round_state", None)
        if rnd_state is not None and rnd_state.sharded is not None:
            # sharded weight update: owned groups pull+apply+publish,
            # the rest install from the owners' param frames. The
            # draining step stays fully synchronous — run_tail returns
            # only once every group (owned or fetched) is installed.
            st = self._sharded
            if st is None:
                raise RuntimeError(
                    "sharded round created but the sharded state is "
                    "gone — this is a bug in the enable/disable path")
            e = self._next_shard_epoch()
            seq = st.next_seq()
            try:
                st.run_tail(handle, chunked, flat, e, seq, h2d,
                            st.param_installer(rep), self._h2d_ex, tl)
            except BaseException as exc:
                raise RuntimeError(
                    f"sharded PS step failed — params and optimizer "
                    f"state may be PARTIALLY stepped (owned groups "
                    f"apply and fetched groups install independently); "
                    f"do not retry this step on the same trainer "
                    f"(restore a checkpoint, or run with "
                    f"BPS_SHARDED_UPDATE=0)") from exc
            finally:
                self.params = jax.tree_util.tree_unflatten(treedef, flat)
                observe_stage("PS_PUSH_PULL", time.time() - t_ex)
                if tl is not None:
                    tl.record(name, "PS_PUSH_PULL", t_ex,
                              time.time() - t_ex)
            return loss
        futs: dict = {}
        remaining = [len(g) for g in chunked.groups]
        applied = 0
        try:
            for li, arr in handle.ready():
                futs[li] = self._h2d_ex.submit(h2d, li, arr)
                gi = chunked.leaf_group.get(li)
                if gi is None or not chunked.decomposable:
                    continue
                remaining[gi] -= 1
                if remaining[gi] == 0:
                    group = chunked.groups[gi]
                    gdev = [futs.pop(i).result() for i in group]
                    t0 = time.time()
                    new = chunked.apply_group(
                        gi, [flat[i] for i in group], gdev)
                    if tl is not None:
                        tl.record(name, "PS_APPLY_CHUNK", t0,
                                  time.time() - t0, gi)
                    for i, leaf in zip(group, new):
                        flat[i] = leaf
                    applied += 1
            if not chunked.decomposable:
                # fused fallback: streamed H2D overlapped the pulls;
                # the apply itself stays one program
                gdev = jax.tree_util.tree_unflatten(
                    treedef, [futs.pop(i).result()
                              for i in range(len(flat))])
                t0 = time.time()
                new_params, self.opt_state = self._apply_fn(
                    self.params, self.opt_state, gdev)
                observe_stage("PS_APPLY_CHUNK", time.time() - t0)
                if tl is not None:
                    tl.record(name, "PS_APPLY_CHUNK", t0,
                              time.time() - t0)
                flat = jax.tree_util.tree_leaves(new_params)
        except BaseException as e:
            if applied:
                # the chunked tail is NOT atomic like the fused one: a
                # failure after any group applied leaves params/opt
                # state partially stepped. Blind-retrying the step
                # would apply the early groups twice — surface the
                # partial state loudly instead of letting that happen
                raise RuntimeError(
                    f"streamed PS step failed after {applied}/"
                    f"{len(chunked.groups)} optimizer groups applied — "
                    f"params and optimizer state are PARTIALLY stepped; "
                    f"do not retry this step on the same trainer "
                    f"(restore a checkpoint, or run with "
                    f"BPS_APPLY_CHUNKED=0 for an all-or-nothing tail)"
                ) from e
            raise
        finally:
            # applied groups' old leaves were donated: rebuild params
            # from the live leaf list even on a mid-stream failure so
            # the trainer never holds invalidated buffers
            self.params = jax.tree_util.tree_unflatten(treedef, flat)
            observe_stage("PS_PUSH_PULL", time.time() - t_ex)
            if tl is not None:
                tl.record(name, "PS_PUSH_PULL", t_ex, time.time() - t_ex)
        return loss

    def _async_ps_step(self, batch) -> jnp.ndarray:
        """Async-PS step: local grads → local optimizer step → push the
        weight delta → pull fresh global weights. No worker barrier; the
        server folds deltas into the store as they arrive."""
        batch = self.shard_batch(batch)
        loss, grads = self._grad_fn(self.params, batch)
        acc = self._accumulate(grads)
        if acc is None:
            return loss
        if acc is not grads:     # host accumulation: back onto the mesh
            rep = NamedSharding(self.mesh, P())
            acc = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, rep), acc)
        new_params, self.opt_state = self._apply_fn(
            self.params, self.opt_state, acc)
        gs = GlobalState._instance
        tl = gs.timeline if gs is not None else None
        t0 = time.time() if tl is not None else 0.0
        # delta computed on-device (fused subtract, one tree over D2H)
        self._async_worker.push_delta_tree(
            self._delta_fn(new_params, self.params))
        fresh = self._async_worker.pull_weights()
        if tl is not None:
            tl.record(self._name, "ASYNC_PS_PUSH_PULL", t0,
                      time.time() - t0)
            tl.set_step(self.step_count)
        rep = NamedSharding(self.mesh, P())
        self.params = jax.tree_util.tree_map(
            lambda x: jax.device_put(np.asarray(x), rep), fresh)
        return loss

    def shard_batch(self, batch):
        """Place a host batch onto the mesh, split along the data axes."""
        from .data import shard_batch
        return shard_batch(batch, self.mesh)

    def step(self, batch) -> jnp.ndarray:
        """One training step on a (host or device) global batch; returns
        loss. With stats enabled (``BPS_STATS``, default on) each step
        also emits a ``StepStats`` record — wall time, per-stage deltas,
        throughput — through ``GlobalState.stats``."""
        # bps.* annotations are host spans in the profiler's own trace:
        # a flag test when no profiler session runs (docs/timeline.md)
        with jax.profiler.StepTraceAnnotation("bps.step",
                                              step_num=self.step_count):
            gs = GlobalState._instance
            em = gs.stats if gs is not None else None
            if em is None:
                return self._step_impl(batch)
            t0 = time.time()
            loss = self._step_impl(batch)
            # PS/async paths are host-synchronous by construction, so
            # their loss is already materialized and float() is free; the
            # collective path dispatches asynchronously and floating its
            # loss would add a per-step device sync — report None there
            sync_loss = (self._ps_engine is not None
                         or self._async_worker is not None)
            with jax.profiler.TraceAnnotation("bps.stats"):
                em.on_step(self.step_count, time.time() - t0,
                           loss=loss if sync_loss else None,
                           samples=_batch_samples(batch),
                           timeline=gs.timeline if gs is not None else None)
            return loss

    def _step_impl(self, batch) -> jnp.ndarray:
        if self._async_worker is not None:
            return self._async_ps_step(batch)
        if self._ps_engine is not None:
            return self._ps_step(batch)
        if (jax.process_count() > 1
                or any(isinstance(l, jax.Array)
                       for l in jax.tree_util.tree_leaves(batch))):
            # committed device arrays must be resharded eagerly (jit's
            # explicit in_shardings rejects a mismatched committed
            # array rather than resharding it; device_put is a no-op
            # when the placement already matches, e.g. prefetch_to_mesh)
            # — and multi-process meshes can't place raw numpy through
            # in_shardings at all ("non-trivial shardings for numpy
            # inputs"), so they always take the device_put path
            with jax.profiler.TraceAnnotation("bps.shard_batch"):
                batch = self.shard_batch(batch)
        # single-process host (numpy) batches go straight in: the step's
        # in_shardings place them inside the jit dispatch — one dispatch
        # per step
        with jax.profiler.TraceAnnotation("bps.dispatch"):
            self.params, self.opt_state, loss = self._step_fn(
                self.params, self.opt_state, batch)
        self.step_count += 1
        gs = GlobalState._instance
        if gs is not None and gs.timeline is not None:
            with jax.profiler.TraceAnnotation("bps.stats"):
                gs.timeline.set_step(self.step_count)
        return loss


class ShardedTrainer:
    """Full multi-way trainer: data × tensor × sequence parallelism.

    Generalizes DistributedTrainer to sharded parameters. Per-leaf grad
    synchronization is derived from the param spec: a gradient must be
    summed over every mesh axis its computation was sharded on *except*
    the axes that shard the leaf itself (those grads are owned per-shard).
    The data-axis allreduce then runs through ``distributed_optimizer``
    like the pure-DP path: per-shard leaves as they are on an ICI mesh,
    flat buckets across ``dcn`` or under compression.

      - params sharded per ``param_specs`` (TP axes inside the spec)
      - batch sharded over (data..., seq) with leading batch dim on data
        and sequence dim on the sp axis
      - optimizer state sharded to match params (opt_state_specs)

    With ``backward_passes_per_step=k``, gradient accumulators hold
    PER-REPLICA local gradients between sync boundaries (that locality is
    the bandwidth saving — reference: torch/__init__.py:83-113 accumulates
    worker-locally too). Checkpoint or host-read ``opt_state`` only at
    sync boundaries (``step_count % k == 0``); mid-window reads observe
    one replica's accumulators.
    """

    @_recorded
    def __init__(self, loss_fn: Callable, params, param_spec_tree,
                 tx: optax.GradientTransformation, mesh: Mesh,
                 batch_spec: Optional[P] = None,
                 partition_bytes: int = 4 << 20,
                 backward_passes_per_step: int = 1,
                 compression: Optional[dict] = None,
                 min_compress_bytes: int = 65536,
                 donate: bool = True) -> None:
        from .parallel.sharding import (init_sharded_state, local_leaf_specs,
                                        opt_state_specs, shard_tree)

        self.mesh = mesh
        self.dp_axes = data_axes(mesh)
        other_axes = tuple(ax for ax in mesh.axis_names
                           if ax not in self.dp_axes)
        # Compression composes with TP/SP/PP: the plan is built from the
        # LOCAL (per-shard) leaf shapes gradients have inside shard_map,
        # and compressor state is per-device (leading axis over the mesh).
        comp_specs = (local_leaf_specs(params, param_spec_tree, mesh)
                      if compression else None)
        comm_axes = (self.dp_axes if compression else
                     tuple(a for a in self.dp_axes if mesh.shape[a] > 1))
        reduce_world = 1
        for a in comm_axes:
            reduce_world *= mesh.shape[a]
        self.tx = distributed_optimizer(
            tx, axes=comm_axes, partition_bytes=partition_bytes,
            backward_passes_per_step=backward_passes_per_step,
            compression=compression, min_compress_bytes=min_compress_bytes,
            compression_leaf_specs=comp_specs,
            compression_state_world=mesh.size,
            compression_reduce_world=reduce_world)
        _log_exchange_form(comm_axes, psum_reducer, compression)
        self.pspec = param_spec_tree
        self.ospec = opt_state_specs(
            self.tx, params, param_spec_tree,
            comp_axes=tuple(mesh.axis_names) if compression else None)
        if batch_spec is None:
            seq_ax = "seq" if "seq" in mesh.axis_names else None
            batch_spec = P(self.dp_axes if self.dp_axes else None, seq_ax)
        self.batch_spec = batch_spec
        from .data import _host_bytes
        with _record.span(self._setup, "bps.setup.place_params",
                          bytes=_host_bytes(params)):
            self.params = shard_tree(params, self.pspec, mesh)
        with _record.span(self._setup, "bps.setup.opt_init"):
            self.opt_state = init_sharded_state(self.tx, params, self.ospec,
                                                mesh)
        loss_axes = tuple(ax for ax in mesh.axis_names
                          if ax in _spec_axes(batch_spec))

        flat_specs = jax.tree_util.tree_leaves(
            param_spec_tree, is_leaf=lambda x: isinstance(x, P))
        import math
        other_prod = math.prod(mesh.shape[a] for a in other_axes) if other_axes else 1

        def step(params, opt_state, batch):
            with jax.named_scope("bps.model"):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            # Per-leaf grad sync over the non-dp axes the leaf is NOT
            # sharded on, then a uniform 1/prod(other_axes) rescale.
            # Why the rescale: inside shard_map the VJP of a forward psum
            # delivers the *sum* of all ranks' cotangents, so when the loss
            # value is replicated across an axis of size n, every gradient
            # path through that psum comes out n-times the true gradient —
            # uniformly, for sharded and replicated leaves alike (the loss
            # itself must be truly global, see lm_loss's sp handling).
            # P is a tuple subclass, so flatten both trees explicitly.
            g_leaves, g_def = jax.tree_util.tree_flatten(grads)
            synced = []
            with jax.named_scope("bps.exchange"):
                for g, s in zip(g_leaves, flat_specs):
                    axes = tuple(a for a in other_axes
                                 if a not in _spec_axes(s))
                    g = jax.lax.psum(g, axes) if axes else g
                    if other_prod > 1:
                        g = g / other_prod
                    synced.append(g)
            grads = jax.tree_util.tree_unflatten(g_def, synced)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            with jax.named_scope("bps.optimizer"):
                params = optax.apply_updates(params, updates)
            if loss_axes:
                loss = jax.lax.pmean(loss, loss_axes)
            return params, opt_state, loss

        shard_fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(self.pspec, self.ospec, batch_spec),
            out_specs=(self.pspec, self.ospec, P()),
            check_vma=False)
        with _record.span(self._setup, "bps.setup.build_step"):
            self._step_fn = jax.jit(shard_fn,
                                    donate_argnums=(0, 1) if donate else ())
        self.step_count = 0

    setup_record = DistributedTrainer.setup_record
    step_account = DistributedTrainer.step_account

    def shard_batch(self, batch):
        from .data import shard_batch
        return shard_batch(batch, self.mesh, self.batch_spec)

    def step(self, batch):
        with jax.profiler.StepTraceAnnotation("bps.step",
                                              step_num=self.step_count):
            gs = GlobalState._instance
            em = gs.stats if gs is not None else None
            t0 = time.time() if em is not None else 0.0
            with jax.profiler.TraceAnnotation("bps.shard_batch"):
                batch = self.shard_batch(batch)
            with jax.profiler.TraceAnnotation("bps.dispatch"):
                self.params, self.opt_state, loss = self._step_fn(
                    self.params, self.opt_state, batch)
            self.step_count += 1
            if em is not None:
                # loss is still in flight (async dispatch): None, not a
                # sync
                with jax.profiler.TraceAnnotation("bps.stats"):
                    em.on_step(self.step_count, time.time() - t0,
                               samples=_batch_samples(batch),
                               timeline=gs.timeline)
            return loss



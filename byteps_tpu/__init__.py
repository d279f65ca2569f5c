"""byteps_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA rebuild of the capabilities of BytePS (reference:
/root/reference — a PS-architecture data-parallel trainer for
GPU clusters). The public surface keeps the reference's Horovod-style
function names (reference: byteps/common/__init__.py:59-139,
byteps/torch/__init__.py) so users can map one API onto the other:

    import byteps_tpu as bps
    bps.init()
    grads = bps.push_pull(grads)            # bucketed, priority-scheduled
    params = bps.broadcast_parameters(params)
    tx = bps.DistributedOptimizer(optax.adam(1e-3))

but the machinery underneath is mesh + shard_map + XLA collectives, not a
queue pipeline — see byteps_tpu/parallel/collectives.py.
"""

from __future__ import annotations

from typing import Optional

import jax

from .common.config import Config
from .common.global_state import GlobalState
from .common import naming
from .version import __version__

_suspended_decls = None
_warned_rank_granularity = False


# -- lifecycle (reference: operations.cc:34-129) ----------------------------

def init(config: Optional[Config] = None, mesh=None) -> None:
    """Initialise the runtime (reference: byteps_init, operations.cc:36-88)."""
    GlobalState.init(config, mesh=mesh)


def shutdown() -> None:
    GlobalState.shutdown()


_suspended_config = None


def suspend() -> None:
    """Tear down, remembering tensor declarations (reference: byteps_suspend)."""
    global _suspended_decls, _suspended_config
    if GlobalState.initialized():
        _suspended_config = GlobalState.get().config
    _suspended_decls = GlobalState.suspend()


def resume(num_worker: Optional[int] = None, config: Optional[Config] = None,
           mesh=None) -> None:
    """Re-init after membership change, replaying declarations so name→key
    stays stable (reference: byteps_resume, operations.cc:96-112)."""
    global _suspended_decls
    if config is None:
        import os
        overrides = {}
        if num_worker is not None:
            overrides["num_worker"] = num_worker
        # host_only is sticky across suspend/resume: torch init sets it
        # PROGRAMMATICALLY (default-on, no env var), so a from-env
        # rebuild would silently drop it and resume() would start
        # accelerator discovery in a process that never asked for a
        # device. An explicit env var wins.
        if _suspended_config is not None \
                and "BPS_HOST_ONLY" not in os.environ:
            overrides["host_only"] = _suspended_config.host_only
        config = Config.from_env(**overrides)
    GlobalState.resume(_suspended_decls, config, mesh=mesh)
    _suspended_decls = None


# -- topology queries (reference: operations.cc:121-129) --------------------

def rank() -> int:
    """First data-parallel replica index owned by this process, in
    ``[0, size())``. Single-controller JAX drives all local replicas from
    one process, so unlike the reference (one process per GPU) a process
    owns ``size() // jax.process_count()`` consecutive replica slots; for
    dataset sharding use ``rank()`` with ``local_size()`` replicas, or just
    ``DistributedTrainer.shard_batch`` which handles placement."""
    if _host_only():
        return GlobalState.get().config.worker_id
    slots = size() // max(jax.process_count(), 1)
    global _warned_rank_granularity
    if slots > 1 and not _warned_rank_granularity:
        _warned_rank_granularity = True
        import warnings
        warnings.warn(
            "bps.rank() is process-granular: this process owns "
            f"{slots} data-parallel replica slots, so sharding a dataset "
            "by rank()/size() Horovod-style covers only 1/"
            f"{slots} of this process's replicas. Shard by "
            "replica_ranks() (all owned slots) or use "
            "DistributedTrainer.shard_batch.", stacklevel=2)
    return jax.process_index() * slots


def size() -> int:
    """Total number of data-parallel replicas (reference: byteps_size)."""
    if GlobalState.initialized():
        return GlobalState.get().dp
    return jax.device_count()


def _host_only() -> bool:
    return GlobalState.initialized() and GlobalState.get().config.host_only


def local_rank() -> int:
    cfg = GlobalState.get().config if GlobalState.initialized() else Config.from_env()
    return cfg.local_rank


def local_size() -> int:
    if _host_only():
        return GlobalState.get().config.local_size
    return jax.local_device_count()


def replica_ranks() -> range:
    """ALL data-parallel replica slots this process owns, e.g. for
    dataset sharding: ``shard = data[list(bps.replica_ranks())]``.

    The reference runs one process per GPU so its ``rank()`` is unique
    per replica; single-controller JAX drives many replicas per process,
    making a ported ``rank()``-based shard silently process-granular.
    This helper is the safe primitive (see also ``data.shard_batch`` /
    ``shard_local_batch``, which handle placement directly)."""
    per_proc = size() // max(jax.process_count(), 1)
    start = jax.process_index() * per_proc
    return range(start, start + per_proc)


# -- observability ----------------------------------------------------------

def get_metrics():
    """The process-wide observability metrics registry (counters,
    gauges, per-stage latency histograms — docs/observability.md).
    Always available; recording obeys ``BPS_STATS``."""
    from .obs.metrics import get_registry
    return get_registry()


# -- data plane -------------------------------------------------------------

def declare_tensor(name: str, priority: Optional[int] = None, **kwargs) -> int:
    """Pre-declare a tensor (reference: byteps_declare_tensor / IsTensorDeclared);
    returns its stable key."""
    return GlobalState.get().registry.declare(name, priority=priority, **kwargs).declared_key


def push_pull(tree, average: bool = True, name: Optional[str] = None):
    """Synchronise a pytree of stacked [dp, ...] gradients across the data
    axes — the reference's push_pull ≡ allreduce (common/__init__.py:83-100).
    """
    return GlobalState.get().engine.push_pull(tree, average=average, name=name)


def push_pull_async(tree, average: bool = True,
                    name: Optional[str] = None) -> int:
    """Dispatch push_pull, return an int handle (reference:
    torch/ops.py push_pull_async + handle_manager)."""
    return GlobalState.get().engine.push_pull_async(tree, average=average,
                                                    name=name)


def push_pull_rowsparse(indices, rows, num_rows: int,
                        average: bool = False,
                        name: str = "rowsparse"):
    """Row-sparse push_pull: each worker pushes only the touched
    (row index, row value) pairs of a [num_rows, cols] table; returns
    the dense summed table. Duplicate indices within a push sum
    (scatter-add). The reference RESERVED this request type
    (kRowSparsePushPull, common.h:267-271) but shipped no handler —
    here it rides the PS path (BPS_ENABLE_PS=1, sync mode), where the
    server scatters each worker's rows into the dense store and the
    engine merges. Distinct tables need distinct ``name``s."""
    gs = GlobalState.get()
    eng = gs.engine
    if eng.ps_exchange is None:
        if gs.ps_backend is not None:
            raise NotImplementedError(
                "row-sparse push_pull needs SYNC PS mode — drop "
                "BPS_ENABLE_ASYNC (the async store folds weight deltas, "
                "not per-round gradient merges)")
        raise NotImplementedError(
            "row-sparse push_pull rides the PS path — run with "
            "BPS_ENABLE_PS=1 (sync mode); the collective path has no "
            "sparse win (XLA psum is dense)")
    rsx = getattr(eng, "_rs_exchange", None)
    if rsx is None:
        from .server.ps_mode import RowSparseExchange
        rsx = eng._rs_exchange = RowSparseExchange(gs.ps_backend,
                                                   gs.registry)
    out = rsx.exchange(indices, rows, num_rows, name)
    if average and eng.ps_world > 1:
        out = out / eng.ps_world
    return out


def poll(handle: int) -> bool:
    """True once the handle's reduction has completed on device."""
    return GlobalState.get().engine.poll(handle)


def synchronize(handle: int):
    """Block until the handle's reduction is done; return the result."""
    return GlobalState.get().engine.synchronize(handle)


def broadcast_parameters(tree, root_rank: int = 0,
                         stacked: Optional[bool] = None):
    """Broadcast root's parameters to all ranks (reference:
    torch/__init__.py:259-291).

    Leaves following the stacked eager convention (committed [dp, ...]
    arrays sharded on the data axis — or any [dp, ...] leaf when
    ``stacked=True``) are broadcast from root's row; replicated leaves
    (plain numpy / unsharded / model-sharded) are already rank-consistent
    under single-controller JAX and pass through (multi-process: broadcast
    from the root's process). See PushPullEngine.broadcast."""
    return GlobalState.get().engine.broadcast(tree, root_rank, stacked)


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              stacked: Optional[bool] = None):
    """Broadcast root's optimizer state to all ranks (reference:
    torch/__init__.py:293-409, which tensor-izes scalar state before its
    torch broadcast — optax state is already arrays, so this is the same
    per-leaf semantics as ``broadcast_parameters``: stacked [dp, ...]
    data-sharded leaves — or any [dp, ...] leaf with ``stacked=True`` —
    take root's row; replicated leaves are rank-consistent already and
    pass through; non-array leaves (None, callables) untouched)."""
    return GlobalState.get().engine.broadcast(opt_state, root_rank, stacked)


def get_pushpull_speed() -> float:
    """MB/s over a 10 s sliding window (reference: global.cc:697-752)."""
    t = GlobalState.get().telemetry
    return t.mbps() if t is not None else 0.0


# -- high-level wrappers ----------------------------------------------------

def DistributedOptimizer(*args, **kwargs):
    from .optim import DistributedOptimizer as _DO
    return _DO(*args, **kwargs)


def DistributedTrainer(*args, **kwargs):
    from .training import DistributedTrainer as _DT
    return _DT(*args, **kwargs)


def MirroredStrategy(*args, **kwargs):
    """Strategy-style API (reference: docs/MirroredStrategy.md)."""
    from .strategy import MirroredStrategy as _MS
    return _MS(*args, **kwargs)


# Reference-named compat classes (torch DDP / tf2 tape / Compression —
# see byteps_tpu/compat.py). Exposed lazily as REAL classes so
# isinstance/subclassing work, while keeping import light.
_COMPAT_EXPORTS = ("DistributedDataParallel", "DistributedGradientTape",
                   "Compression")


def __getattr__(name):
    if name in _COMPAT_EXPORTS:
        from . import compat
        return getattr(compat, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "init", "shutdown", "suspend", "resume", "rank", "size", "local_rank",
    "local_size", "replica_ranks", "declare_tensor", "push_pull",
    "push_pull_async",
    "push_pull_rowsparse", "poll", "synchronize", "broadcast_parameters",
    "broadcast_optimizer_state", "get_pushpull_speed",
    "DistributedOptimizer", "DistributedTrainer", "MirroredStrategy",
    "DistributedDataParallel", "DistributedGradientTape", "Compression",
    "Config", "__version__",
]

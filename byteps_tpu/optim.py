"""Distributed optimizer wrappers.

The reference wraps each framework's optimizer so that every gradient is
push_pulled before the local update (reference: torch/__init__.py:115-174
_DistributedOptimizer; tf/__init__.py:185-278; mxnet/__init__.py:35-121),
with gradient accumulation via ``backward_passes_per_step``
(torch/__init__.py:83-113).

The TPU-native equivalent is an ``optax.GradientTransformation`` that
inserts a cross-replica allreduce in front of the inner transformation
(``parallel.collectives.tree_allreduce``: the leaves as they are on an
ICI-only mesh, flat buckets where a reducer needs a flat buffer). It must be applied *inside* a shard_map'd train step, where
the mesh data axes are live — that is the idiomatic JAX seam, exactly where
autodiff hands you raw per-replica gradients (the same seam the reference
hooks with grad-accumulator callbacks).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import optax

from .parallel.collectives import Reducer, psum_reducer, tree_allreduce


def _make(inner: optax.GradientTransformation, axes: Tuple[str, ...],
          average: bool, partition_bytes: int, reducer: Reducer):
    def init_fn(params):
        return inner.init(params)

    def update_fn(grads, state, params=None, **extra):
        with jax.named_scope("bps.exchange"):
            grads = tree_allreduce(grads, axes=axes,
                                   partition_bytes=partition_bytes,
                                   average=average, reducer=reducer)
        with jax.named_scope("bps.optimizer"):
            return inner.update(grads, state, params, **extra)

    return optax.GradientTransformation(init_fn, update_fn)


def _make_compressed(inner: optax.GradientTransformation, axes: Tuple[str, ...],
                     average: bool, partition_bytes: int,
                     compression: dict, min_compress_bytes: int,
                     leaf_specs=None, state_world: int = 1,
                     reduce_world: int = 1):
    """Compressed-allreduce wrapper.

    ``leaf_specs``: LOCAL per-shard leaf shapes (from
    parallel.sharding.local_leaf_specs) when composing with TP/SP/PP;
    defaults to the global shapes of the params passed to init (correct
    for pure DP, where params are replicated).

    ``state_world``: compressor state (EF error, momentum) diverges on
    every device — the gradients it tracks are per-shard. State leaves get
    a leading device axis of this size, sharded over all mesh axes by the
    trainer; inside shard_map each rank sees (and updates) its [1, ...]
    row. A replicated spec here would be silently wrong: XLA may
    canonicalize "replicated" state to one rank's copy, losing every other
    rank's error memory.
    """
    import jax.numpy as jnp
    from .ops.compression.reducer import CompressionPlan
    plan_holder = {}

    def _plan_for(params):
        kw = {k: str(v) for k, v in compression.items()}
        if leaf_specs is not None:
            return CompressionPlan(leaf_specs, partition_bytes, kw,
                                   min_compress_bytes, world=reduce_world)
        return CompressionPlan.for_tree(params, partition_bytes, kw,
                                        min_compress_bytes,
                                        world=reduce_world)

    def init_fn(params):
        # rebuild per init: re-initing with a different tree must not
        # reuse a stale bucket plan
        plan = plan_holder["plan"] = _plan_for(params)
        comp = jax.tree_util.tree_map(
            lambda z: jnp.broadcast_to(z, (state_world,) + jnp.shape(z)),
            plan.init_state())
        return {"inner": inner.init(params), "bps_comp": comp}

    def update_fn(grads, state, params=None, **extra):
        plan = plan_holder["plan"]
        local = jax.tree_util.tree_map(lambda x: x[0], state["bps_comp"])
        with jax.named_scope("bps.exchange"):
            grads, comp_state = plan.reduce_tree(grads, local, axes,
                                                 average=average)
        comp_state = jax.tree_util.tree_map(lambda x: x[None],
                                            comp_state)
        with jax.named_scope("bps.optimizer"):
            updates, inner_state = inner.update(grads, state["inner"],
                                                params, **extra)
        return updates, {"inner": inner_state, "bps_comp": comp_state}

    return optax.GradientTransformation(init_fn, update_fn)


def distributed_optimizer(inner: optax.GradientTransformation,
                          axes: Sequence[str] = ("data",),
                          average: bool = True,
                          partition_bytes: int = 4 << 20,
                          backward_passes_per_step: int = 1,
                          reducer: Reducer = psum_reducer,
                          compression: dict | None = None,
                          min_compress_bytes: int = 65536,
                          compression_leaf_specs=None,
                          compression_state_world: int = 1,
                          compression_reduce_world: int = 1):
    """Wrap an optax transformation with cross-replica gradient sync.

    ``backward_passes_per_step > 1`` accumulates locally and only
    communicates + applies every k-th step (reference:
    torch/__init__.py:83-113) — implemented with optax.MultiSteps so the
    allreduce itself sits under the every-k branch and no bandwidth is
    spent on intermediate passes.

    ``partition_bytes`` sizes the flat buckets where the exchange runs in
    buckets: ``compression``, a custom ``reducer``, a ``"dcn"`` axis among
    ``axes``. With the default ``psum_reducer`` on ICI axes each leaf is
    reduced in its own shape and the value has no meaning (and no effect).

    ``compression`` is a string-kwargs dict in the reference's format
    (docs/gradient-compression.md "Interface"), e.g.
    ``{"compressor_type": "onebit", "compressor_onebit_scaling": "true",
    "ef_type": "vanilla"}``; buckets under ``min_compress_bytes`` skip
    compression (reference: BYTEPS_MIN_COMPRESS_BYTES).
    """
    if compression:
        gt = _make_compressed(inner, tuple(axes), average, partition_bytes,
                              compression, min_compress_bytes,
                              leaf_specs=compression_leaf_specs,
                              state_world=compression_state_world,
                              reduce_world=compression_reduce_world)
    else:
        gt = _make(inner, tuple(axes), average, partition_bytes, reducer)
    if backward_passes_per_step > 1:
        gt = optax.MultiSteps(gt, every_k_schedule=backward_passes_per_step)
    return gt


# Horovod/BytePS-style alias: bps.DistributedOptimizer(optax.adam(1e-3))
def DistributedOptimizer(inner: optax.GradientTransformation, **kwargs):  # noqa: N802
    return distributed_optimizer(inner, **kwargs)


# ------------------------------------------------------- chunked apply
#
# The sync-PS step tail used to be a barrier: wait for EVERY bucket's
# pull, device_put the whole tree, one monolithic optimizer jit. The
# weight update itself is decomposable for the common optimizers
# (PAPERS.md: "Automatic Cross-Replica Sharding of Weight Update in
# Data-Parallel Training" decomposes it across replicas; here the same
# observation is applied across BUCKETS in time): applying adam to leaf
# group k needs nothing from group j, so group 0's weights can update
# while group N's gradients are still on the wire.

def leafwise_decomposable(inner: optax.GradientTransformation,
                          leaves, groups) -> bool:
    """Cheap numeric probe: is ``inner``'s update for a leaf independent
    of the other leaves, so per-group apply equals fused apply?

    Runs the transformation on a tiny same-structure tree (one (2,)
    vector per leaf, deterministic pseudo-random values) fused and
    per-group, and compares the per-leaf updates. Value-coupled
    transformations (``clip_by_global_norm``: the norm spans the tree)
    diverge on any non-degenerate values and are caught here;
    structure-coupled ones (path-keyed masks) raise on the list-shaped
    probe and are caught by the except. A transformation that is
    coupled ONLY on inputs the probe can't reach would slip through —
    acceptable for the stock optax chains this targets, and the
    ``BPS_APPLY_CHUNKED=0`` escape hatch covers the exotic rest."""
    import numpy as np
    rng = np.random.RandomState(0)

    def tiny(leaf):
        dt = np.dtype(getattr(leaf, "dtype", np.float32))
        return (rng.standard_normal(2)).astype(dt)

    probe = [tiny(l) for l in leaves]
    grads = [tiny(l) for l in leaves]
    try:
        fused_u, _ = inner.update(grads, inner.init(probe), probe)
        fused = [np.asarray(u) for u in fused_u]
        for g in groups:
            sub_p = [probe[i] for i in g]
            sub_g = [grads[i] for i in g]
            part_u, _ = inner.update(sub_g, inner.init(sub_p), sub_p)
            for li, u in zip(g, part_u):
                if not np.allclose(fused[li], np.asarray(u),
                                   rtol=1e-6, atol=1e-8):
                    return False
    except Exception:       # noqa: BLE001 — structure-coupled tx, or a
        return False        # tx that can't run on list pytrees: fused
    return True


class ChunkedApply:
    """Per-group jitted optimizer apply over a fixed partition of the
    parameter tree's flat leaves (the exchange's bucket groups,
    ``PSGradientExchange.leaf_groups``).

    The same groups serve BOTH ends of the streamed PS step: the staged
    backward (``staged_grad.build_staged_grad``) places its candidate
    segment cuts where each group's last gradient is produced, and this
    class applies the optimizer per group as the pulls land — so one
    bucket partition defines the whole pipeline's granularity
    (bwd seg ∥ push ∥ server ∥ pull ∥ apply all advance per group).

    When ``inner`` is leafwise-decomposable (probe above), optimizer
    state is held PER GROUP (``inner.init`` on each group's leaf list)
    and ``apply_group`` updates one group as its gradients arrive —
    bit-identical to the fused apply for elementwise chains because
    each leaf sees the exact same op sequence either way. Otherwise
    ``decomposable`` is False and the caller keeps its fused apply
    (streamed H2D still overlaps; only the apply stays monolithic).

    One jitted callable serves every group: jax retraces per input
    structure, so each group compiles once and reuses thereafter.
    """

    def __init__(self, inner: optax.GradientTransformation, params,
                 groups, donate: bool = True, owned=None) -> None:
        import threading
        self.inner = inner
        leaves, _ = jax.tree_util.tree_flatten(params)
        self.groups = [tuple(g) for g in groups if g]
        self.leaf_group = {}
        for gi, g in enumerate(self.groups):
            for li in g:
                self.leaf_group[li] = gi
        covered = sorted(self.leaf_group) == list(range(len(leaves)))
        self.decomposable = covered and leafwise_decomposable(
            inner, leaves, self.groups)
        # sharded weight update (byteps_tpu.sharded_update): optimizer
        # state is allocated ONLY for this replica's owned groups — the
        # ~1/dp optimizer-state memory reduction is exactly this line.
        # Applying a non-owned group is a contract violation (its state
        # lives on the owner), refused loudly in apply_group.
        self.owned = None if owned is None else frozenset(owned)
        # per-leaf readiness EPOCH table (cross-step gating): entry li
        # is the last step whose optimizer apply for leaf li has been
        # dispatched. The cross-step driver launches step k+1's staged
        # segments the moment every param leaf a segment reads shows
        # epoch >= k — the TPU-native form of the reference
        # cross-barrier's per-parameter locks (torch/cross_barrier.py).
        self.ready_epoch = [0] * len(leaves)
        self._epoch_cv = threading.Condition()
        self.states = None
        self._apply = None
        if not self.decomposable:
            return
        self.states = [inner.init([leaves[i] for i in g])
                       if self.owned is None or gi in self.owned
                       else None
                       for gi, g in enumerate(self.groups)]

        def _apply(plist, state, glist):
            updates, state = inner.update(glist, state, plist)
            return optax.apply_updates(plist, updates), state

        self._apply = jax.jit(
            _apply, donate_argnums=(0, 1) if donate else ())

    def init_group(self, gi: int, params_list):
        """A fresh ``inner.init`` state for group ``gi``'s current
        leaves — the unpack template for a membership handoff frame or
        a sharded-checkpoint slice, and the crashed-leave fallback."""
        return self.inner.init(list(params_list))

    def adopt_group(self, gi: int, state) -> None:
        """Install optimizer state for a group this replica is taking
        OWNERSHIP of (membership reshard handoff / sharded-checkpoint
        restore). Leaves are placed on device so the donating jitted
        apply never consumes host buffers."""
        import jax.numpy as jnp
        if not self.decomposable:
            raise RuntimeError(
                "adopt_group on a non-decomposable tail — sharded "
                "ownership never engages there")
        self.states[gi] = jax.tree_util.tree_map(jnp.asarray, state)

    def release_group(self, gi: int) -> None:
        """Drop a group's optimizer state after handing ownership away
        (the ~1/dp memory contract holds through membership changes)."""
        if self.states is not None:
            self.states[gi] = None

    def set_owned(self, owned) -> None:
        """Flip the owned-group set at a membership epoch boundary —
        the callers (ShardedUpdateState.reshard) adopt gained groups'
        state BEFORE flipping and release lost groups' after."""
        self.owned = None if owned is None else frozenset(owned)

    def apply_group(self, gi: int, params_list, grads_list):
        """Update group ``gi``'s leaves; returns the new leaf list.
        ``params_list``/``grads_list`` follow ``self.groups[gi]`` order.
        The old leaves and the group's state are donated when the
        ChunkedApply was built with ``donate=True``.

        Cross-step callers publish the group via ``mark_epoch`` ONLY
        after installing the returned leaves wherever gated readers
        look them up — marking at dispatch would open a window where a
        gate observes the epoch but still reads the pre-apply array."""
        import time
        from .obs.metrics import observe_stage
        if self.owned is not None and gi not in self.owned:
            raise RuntimeError(
                f"apply_group({gi}) on a non-owned group: this replica "
                f"holds no optimizer state for it (sharded update) — "
                f"non-owned groups are installed from the owner's "
                f"param frames, never applied locally")
        t0 = time.time()
        new, self.states[gi] = self._apply(params_list, self.states[gi],
                                           grads_list)
        # dispatch latency of the per-group apply (the same span the
        # PS_APPLY_CHUNK timeline rows show) — always-on
        observe_stage("PS_APPLY_CHUNK", time.time() - t0)
        return new

    def mark_epoch(self, leaf_ids, epoch: int) -> None:
        """Publish ``leaf_ids`` as applied through step ``epoch``."""
        with self._epoch_cv:
            for li in leaf_ids:
                self.ready_epoch[li] = epoch
            self._epoch_cv.notify_all()

    def wait_epoch(self, leaf_ids, epoch: int, should_abort=None) -> float:
        """Block until every leaf in ``leaf_ids`` reaches ``epoch``;
        returns the seconds spent waiting (the cross-step gate span).
        ``should_abort()`` is polled so a dead tail thread cannot leave
        the gate waiting on marks that will never come."""
        import time
        t0 = time.time()
        with self._epoch_cv:
            while not all(self.ready_epoch[li] >= epoch
                          for li in leaf_ids):
                if should_abort is not None and should_abort():
                    break
                self._epoch_cv.wait(0.05)
        return time.time() - t0

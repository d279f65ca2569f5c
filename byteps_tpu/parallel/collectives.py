"""Gradient synchronization: the TPU-native push_pull.

The reference moves every gradient through a 12-stage pipeline of priority
queues and background threads (NCCL reduce-scatter → D2H → push → server
sum → pull → H2D → all-gather; reference: common.h:88-102 QueueType,
core_loops.cc). On TPU, all of those stages collapse into XLA collectives
over a device mesh. What is left of the design is decided by what a
reduction needs, not by what the reference did:

  1. **The leaves as they are** (``leaf_allreduce``): on an ICI-only mesh
     the lossless exchange inside the jitted step is one ``psum`` a
     gradient leaf, in the leaf's own shape and layout, and XLA's
     all-reduce combiner does what bucketing did by hand. A stacked leaf
     ``f32[24, 1024, 4096]`` in the TPU's tiled layout is not the bytes of
     a flat ``f32[100663296]``: the ravel, the slices into 4 MB buffers
     and the way back are copies, and the reshape from flat fuses into
     the optimizer as a relayout. On BERT-large at dp=4 on four v5e chips
     that cost 44.8 ms of a 635.4 ms step (PERF.md section 6, PR 37).
  2. **Bucketing** (``bucketed_allreduce``; reference: tensor partitioning,
     operations.cc:140-180 — inverted, see byteps_tpu/common/partition.py)
     stays where a reducer needs a flat buffer: ``psum_reducer``'s
     dcn × ici hierarchy (reduce-scatter, all-reduce of the shard,
     all-gather over a 1-D buffer), a custom ``reducer`` (its contract is
     ``(flat buffer, axes)``), compression (``optim._make_compressed``),
     and the eager engine below. Buckets are planned in reverse layer
     order (reference: scheduled_queue.cc:82-102), which orders the eager
     engine's dispatches; inside one jitted step whose layers run under
     ``lax.scan`` every gradient is ready at once, so neither form
     overlaps the backward yet (ROADMAP A3(b)).

``exchange_form`` is the one place that decides between the two, from the
reducer's identity and the axes' names. Entry points:

  - ``tree_allreduce`` — call *inside* your shard_map'd train step; takes
    the form ``exchange_form`` names. This is the primary, fully-jitted
    path (``optim.distributed_optimizer`` calls it).
  - ``PushPullEngine`` — an eager, Horovod-style engine: per-bucket jitted
    programs dispatched in priority order. This is the analogue of the
    reference's ``EnqueueTensor`` API and supports cross-barrier-style
    overlap with the next forward pass, because JAX dispatch is async.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.partition import Bucket, LeafSpec, plan_buckets
from ..common.naming import NameRegistry
from ..common.setup_record import note_choice
from .mesh import data_axes, dp_size

Reducer = Callable[[jnp.ndarray, Tuple[str, ...]], jnp.ndarray]


def psum_reducer(x: jnp.ndarray, axes: Tuple[str, ...]) -> jnp.ndarray:
    """Default reducer.

    ICI-only meshes get a plain psum (XLA's ring allreduce is already
    bandwidth-optimal at 2(n-1)/n bytes/chip). Hybrid dcn+ici meshes get
    the explicit hierarchy the reference builds out of NCCL-then-PS
    (core_loops.cc:232-268 + 538-618), in its bandwidth-optimal TPU
    form: reduce_scatter inside the slice → cross-slice all_reduce on
    the 1/ici-sized shard → all_gather inside the slice. Only bytes/ici
    ever cross the slow DCN tier — a flat psum over both axes leaves
    that decomposition to the whims of the partitioner, and the scaling
    model (parallel/scaling_model.py) pins this schedule in lowered HLO.
    """
    if not axes:
        return x
    dcn = tuple(a for a in axes if a == "dcn")
    ici = tuple(a for a in axes if a != "dcn")
    if not dcn or not ici or x.ndim != 1:
        return jax.lax.psum(x, axes)
    n = x.shape[0]
    ici_n = 1
    for a in ici:
        ici_n *= jax.lax.axis_size(a)
    if ici_n == 1 or n < ici_n:
        return jax.lax.psum(x, axes)
    pad = (-n) % ici_n
    xp = jnp.pad(x, (0, pad)) if pad else x
    s = jax.lax.psum_scatter(xp, ici, scatter_dimension=0, tiled=True)
    s = jax.lax.psum(s, dcn)
    y = jax.lax.all_gather(s, ici, axis=0, tiled=True)
    return y[:n] if pad else y


# ---------------------------------------------------------------------------
# In-jit form
# ---------------------------------------------------------------------------

def allreduce(x: jnp.ndarray, axes: Sequence[str], average: bool = True) -> jnp.ndarray:
    """Plain allreduce for use inside shard_map/pjit."""
    axes = tuple(axes)
    if not axes:
        return x
    y = jax.lax.psum(x, axes)
    if average:
        n = 1
        for ax in axes:
            n *= jax.lax.axis_size(ax)
        y = y / n
    return y


def _pack_bucket(flat_leaves: List[jnp.ndarray], bucket: Bucket) -> jnp.ndarray:
    parts = [jax.lax.dynamic_slice_in_dim(flat_leaves[s.leaf_index], s.leaf_offset,
                                          s.length) for s in bucket.segments]
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _unpack_bucket(buf: jnp.ndarray, bucket: Bucket,
                   flat_leaves: List[jnp.ndarray]) -> None:
    """Scatter reduced bucket back into (mutable list of) flat leaves."""
    for s in bucket.segments:
        piece = jax.lax.dynamic_slice_in_dim(buf, s.bucket_offset, s.length)
        flat_leaves[s.leaf_index] = jax.lax.dynamic_update_slice_in_dim(
            flat_leaves[s.leaf_index], piece, s.leaf_offset, axis=0)


def leaf_specs_of_tree(tree) -> List[LeafSpec]:
    leaves_with_path = jax.tree_util.tree_leaves_with_path(tree)
    return [LeafSpec(name=jax.tree_util.keystr(path), size=int(np.prod(leaf.shape)),
                     dtype=str(np.dtype(leaf.dtype)))
            for path, leaf in leaves_with_path]


def bucketed_allreduce(tree, axes: Sequence[str], partition_bytes: int = 4 << 20,
                       average: bool = True, reducer: Reducer = psum_reducer):
    """Bucketed gradient allreduce for use inside a shard_map'd step.

    Flattens the grad pytree, packs leaves into ~partition_bytes buckets in
    reverse declaration order, reduces each bucket with ``reducer``, and
    scatters back: the form for a reducer that needs a flat buffer
    (``exchange_form``). The pack and the way back are copies on the TPU
    (a tiled leaf is not its ravel), which is why the default ICI path is
    ``leaf_allreduce``; and the bucket reduces, independent ops though they
    are, all become ready together when the layers run under ``lax.scan``,
    so nothing here hides behind the backward (ROADMAP A3(b)).
    """
    axes = tuple(ax for ax in axes if ax)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves or not axes:
        return tree
    specs = leaf_specs_of_tree(tree)
    buckets = plan_buckets(specs, partition_bytes, reverse_order=True)
    shapes = [l.shape for l in leaves]
    flat = [l.ravel() for l in leaves]
    n = 1
    for ax in axes:
        n *= jax.lax.axis_size(ax)
    for b in buckets:
        with jax.named_scope("bps.exchange.pack"):
            buf = _pack_bucket(flat, b)
        with jax.named_scope("bps.exchange.reduce"):
            buf = reducer(buf, axes)
            if average:
                buf = buf / n
        with jax.named_scope("bps.exchange.unpack"):
            _unpack_bucket(buf, b, flat)
    out = [f.reshape(s) for f, s in zip(flat, shapes)]
    return jax.tree_util.tree_unflatten(treedef, out)


def leaf_allreduce(tree, axes: Sequence[str], average: bool = True):
    """Lossless gradient allreduce for use inside a shard_map'd step: one
    ``psum`` a leaf, each in its own shape and layout, the mean a leaf's
    own division. No ravel, slice, concatenate or update-slice: combining
    small all-reduces is left to XLA."""
    axes = tuple(ax for ax in axes if ax)
    with jax.named_scope("bps.exchange.reduce"):
        return jax.tree_util.tree_map(
            lambda x: allreduce(x, axes, average), tree)


def exchange_form(axes: Sequence[str], reducer: Reducer = psum_reducer,
                  compression: Optional[dict] = None) -> Tuple[str, str]:
    """Which form the in-jit exchange over ``axes`` takes, and why:
    ``("leaves", reason)`` or ``("buckets", reason)``. Decided by what
    makes a flat buffer necessary and by nothing else: the reducer's
    identity, the axes' names, whether the gradients are compressed."""
    if compression:
        return "buckets", "compression encodes and reduces flat buckets"
    if reducer is not psum_reducer:
        return "buckets", "a custom reducer takes (flat buffer, axes)"
    if "dcn" in axes:
        return "buckets", ("psum_reducer's dcn x ici hierarchy is written "
                           "over a 1-D buffer")
    if not tuple(ax for ax in axes if ax):
        return "leaves", "no axis to reduce over: the gradients pass through"
    return "leaves", ("lossless psum on an ICI-only mesh: each leaf in its "
                      "own shape and layout")


def tree_allreduce(tree, axes: Sequence[str], partition_bytes: int = 4 << 20,
                   average: bool = True, reducer: Reducer = psum_reducer):
    """The in-jit gradient exchange, in the form ``exchange_form`` names:
    ``leaf_allreduce`` on an ICI-only mesh with the default reducer,
    ``bucketed_allreduce`` for a ``dcn`` axis or a custom reducer.
    ``partition_bytes`` sizes the buckets where there are buckets and means
    nothing on the leaf path."""
    form, _ = exchange_form(axes, reducer)
    note_choice("exchange", form, tuple(axes))
    if form == "leaves":
        return leaf_allreduce(tree, axes, average=average)
    return bucketed_allreduce(tree, axes, partition_bytes=partition_bytes,
                              average=average, reducer=reducer)


# ---------------------------------------------------------------------------
# Eager Horovod-style engine
# ---------------------------------------------------------------------------

class PushPullEngine:
    """Eager bucketed push_pull over a mesh (reference API analogue:
    EnqueueTensor + queue pipeline, operations.cc:182-281).

    Input convention: every leaf has a leading "replica" axis of size
    ``dp_size(mesh)`` holding the per-rank values (device-sharded along the
    mesh's data axes). ``push_pull`` returns the same shape with every
    replica slice equal to the (averaged) sum — Horovod semantics.

    Per-bucket jitted programs are dispatched in priority order; JAX's
    async dispatch means later buckets (and the caller's next step) proceed
    while earlier collectives are in flight — the cross-barrier overlap of
    the reference (cross_barrier.py) without a poller thread.
    """

    def __init__(self, mesh: Mesh, partition_bytes: int = 4 << 20,
                 average: bool = True, reducer: Reducer = psum_reducer,
                 registry: Optional[NameRegistry] = None,
                 telemetry: Optional[object] = None,
                 scheduling_credit: int = 0) -> None:
        self.mesh = mesh
        self.axes = data_axes(mesh)
        self.dp = dp_size(mesh)
        self.partition_bytes = partition_bytes
        self.average = average
        self.reducer = reducer
        self.registry = registry or NameRegistry()
        self.telemetry = telemetry
        # Byte-credit flow control (reference: BYTEPS_SCHEDULING_CREDIT,
        # scheduled_queue.cc:33-45 — 0 disables). Bounds the bytes of
        # in-flight bucket collectives; when exceeded, dispatch blocks on
        # the oldest outstanding bucket before issuing the next.
        self.scheduling_credit = scheduling_credit
        self.timeline = None
        self.debug_sample = ""   # tensor-name substring to sample-log
        self.ps_exchange = None  # PS mode: host exchange across workers
        self.ps_world = 1        # worker-process count for PS averaging
        self._programs: Dict[Tuple, Tuple] = {}  # structure key → compiled plan
        self._bcast_fns: Dict[int, Callable] = {}
        # handle manager (reference: torch handle_manager.{cc,h} — int
        # handles mapped to in-flight results; JAX dispatch is already
        # async so a handle just pins the dispatched output arrays)
        self._handles: Dict[int, object] = {}
        self._next_handle = 0
        # handles whose PS host hop is deferred, in DISPATCH order —
        # synchronize() drains this queue front-first so pushes pair
        # across workers even when synchronize order diverges
        self._ps_pending: List[int] = []

    # -- plan & compile one program set per tree structure -------------------
    def _plan(self, tree, average: bool, name: Optional[str] = None):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        key = (treedef, average, name,
               tuple((l.shape, str(l.dtype)) for l in leaves))
        if key in self._programs:
            return self._programs[key]
        prefix = f"{name}." if name else ""
        paths = [prefix + jax.tree_util.keystr(p)
                 for p, _ in jax.tree_util.tree_leaves_with_path(tree)]
        decls = [self.registry.declare(p) for p in paths]
        specs = [LeafSpec(name=p, size=int(np.prod(l.shape[1:])), dtype=str(np.dtype(l.dtype)))
                 for p, l in zip(paths, leaves)]
        # Per-tensor priorities from the registry (user-settable via
        # bps.declare_tensor(name, priority=...)); the default assignment
        # (-declared_key in declaration order) reduces to reverse leaf order,
        # the backward-readiness order.
        prios = [d.priority for d in decls]
        if all(p == -d.declared_key for p, d in zip(prios, decls)):
            buckets = plan_buckets(specs, self.partition_bytes, reverse_order=True)
        else:
            buckets = plan_buckets(specs, self.partition_bytes, priorities=prios)

        mesh, axes, avg, dp, reducer = self.mesh, self.axes, average, self.dp, self.reducer

        progs = []
        for b in buckets:
            leaf_idxs = sorted({s.leaf_index for s in b.segments})
            remap = {li: i for i, li in enumerate(leaf_idxs)}
            segs = b.segments

            def bucket_fn(*args, _segs=segs, _remap=remap, _b=b):
                flat = [a.reshape(-1) for a in args]
                parts = [jax.lax.dynamic_slice_in_dim(flat[_remap[s.leaf_index]],
                                                      s.leaf_offset, s.length)
                         for s in _segs]
                buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
                buf = reducer(buf, axes)
                if avg:
                    buf = buf / dp
                outs = []
                for a, li in zip(args, sorted(_remap, key=_remap.get)):
                    new = flat[_remap[li]]
                    for s in _segs:
                        if s.leaf_index == li:
                            piece = jax.lax.dynamic_slice_in_dim(buf, s.bucket_offset, s.length)
                            new = jax.lax.dynamic_update_slice_in_dim(new, piece, s.leaf_offset, 0)
                    outs.append(new.reshape(a.shape))
                return tuple(outs)

            spec = P(axes) if axes else P()
            shard_fn = jax.shard_map(bucket_fn, mesh=mesh,
                                     in_specs=spec, out_specs=spec,
                                     check_vma=False)
            # No donation: the engine does not own the caller's buffers, and
            # Horovod semantics let the caller reuse its gradient arrays.
            progs.append((jax.jit(shard_fn), leaf_idxs, b))

        plan = (treedef, progs, [l.shape for l in leaves])
        self._programs[key] = plan
        return plan

    def _maybe_sample(self, result, name: Optional[str]) -> None:
        """Numeric debugging sampler (reference: BYTEPS_DEBUG_SAMPLE_TENSOR
        prints tensor values per stage, core_loops.cc:37-67). Runs on the
        FINAL values — post-PS-hop on every path."""
        if not (self.debug_sample and name and self.debug_sample in name):
            return
        from ..common.logging import get_logger
        for p, leaf in jax.tree_util.tree_leaves_with_path(result):
            arr = np.asarray(leaf)
            get_logger().info("SAMPLE %s%s mean=%.6g std=%.6g first=%.6g",
                              name, jax.tree_util.keystr(p),
                              arr.mean(), arr.std(), arr.ravel()[0])

    def _ps_hop(self, result, avg: bool, name: Optional[str]):
        """PS mode's cross-worker hop (reference: PUSH/PULL stages after
        the local NCCL reduce, core_loops.cc:538-618). ``result`` is the
        locally reduced stacked tree — every replica row is identical, so
        row 0 is exchanged through the host service (summed across worker
        processes) and broadcast back to the stacked layout. avg=True:
        each worker contributed its local mean; dividing the PS sum by
        the worker count yields the global mean (equal local batches).

        This hop is host-synchronous (D2H readback + RPCs), so the sync
        path runs it inline while ``push_pull_async`` defers it to
        ``synchronize()`` — dispatch stays non-blocking and the device
        reduce overlaps the caller's work (the cross-barrier pattern)."""
        if self.timeline is not None:
            # separate the wait-for-device-reduce from the actual D2H copy,
            # else the copy span would absorb the whole async dispatch
            t0 = time.time()
            jax.block_until_ready(result)
            self.timeline.record(name or "push_pull", "REDUCE_WAIT", t0,
                                 time.time() - t0)
            t0 = time.time()
        row0 = jax.tree_util.tree_map(
            lambda x: np.asarray(x[0]) if x.ndim else np.asarray(x), result)
        if self.timeline is not None:
            self.timeline.record(name or "push_pull", "COPYD2H", t0,
                                 time.time() - t0)
            t0 = time.time()
        summed = self.ps_exchange.exchange(row0, name=name)
        if self.timeline is not None:
            # one span for the PUSH+server-sum+PULL legs (reference stages
            # PUSH/PULL, core_loops.cc:538-618)
            self.timeline.record(name or "push_pull", "PS_PUSH_PULL", t0,
                                 time.time() - t0)
        if avg and self.ps_world > 1:
            summed = jax.tree_util.tree_map(
                lambda x: x / self.ps_world, summed)
        return jax.tree_util.tree_map(
            lambda old, r: jax.device_put(
                np.broadcast_to(r, old.shape), old.sharding),
            result, summed)

    def push_pull(self, tree, average: Optional[bool] = None,
                  name: Optional[str] = None, sync: bool = True,
                  _defer_ps: bool = False):
        """Reduce a pytree of [dp, ...] stacked arrays; returns same shapes
        with every replica slice equal to the reduction.

        ``sync=False`` (the async-handle path) skips the blocking
        telemetry/timeline readback — recording then happens at
        ``synchronize()`` so enabling the timeline doesn't silently
        serialize the overlap it is meant to measure. ``_defer_ps``
        (internal, push_pull_async only) additionally postpones the PS
        hop to ``synchronize()``; direct callers always get the full
        cross-worker result."""
        avg = self.average if average is None else average
        _, progs, _ = self._plan(tree, avg, name)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        nbytes = sum(l.nbytes for l in leaves)
        t0 = time.time() if (self.telemetry or self.timeline) else 0.0
        out = list(leaves)
        # Priority order: progs is already bucket-index order == priority desc.
        # Credit gating applies only to the synchronous path: the async
        # handle API promises non-blocking dispatch, and its caller owns
        # the in-flight set via poll/synchronize.
        credit = self.scheduling_credit if sync else 0
        inflight: List[Tuple[int, list]] = []   # (bucket bytes, results)
        inflight_bytes = 0
        bucket_runs: List[Tuple[int, float, tuple]] = []  # (key, t, results)
        for fn, leaf_idxs, bucket in progs:
            if credit > 0 and inflight and inflight_bytes > credit:
                tc = time.time()
                while inflight and inflight_bytes > credit:
                    done_bytes, done_results = inflight.pop(0)
                    jax.block_until_ready(done_results)
                    inflight_bytes -= done_bytes
                if self.timeline is not None:
                    # make the stall visible in the trace — it is the whole
                    # point of tuning the credit knob
                    self.timeline.record(name or "push_pull", "CREDIT_BLOCK",
                                         tc, time.time() - tc,
                                         key=bucket.index)
            tb = time.time() if self.timeline is not None else 0.0
            results = fn(*[out[i] for i in leaf_idxs])
            for i, r in zip(leaf_idxs, results):
                out[i] = r
            if credit > 0:
                b = int(bucket.nbytes)
                inflight.append((b, results))
                inflight_bytes += b
            if self.timeline is not None:
                self.timeline.record(name or "push_pull", "DISPATCH",
                                     tb, time.time() - tb, key=bucket.index)
                bucket_runs.append((bucket.index, tb, results))
        if sync and self.timeline is not None:
            # per-bucket REDUCE rows: dispatch → device completion (queue
            # wait + execution — the reference's per-key stage intervals,
            # scheduled_queue.cc:105-123). Measured BEFORE any PS hop so
            # the rows never absorb the blocking host exchange; buckets
            # complete in dispatch order on TPU, so blocking in order
            # gives each bucket its own completion time.
            for bidx, tb, res in bucket_runs:
                jax.block_until_ready(res)
                self.timeline.record(name or "push_pull", "REDUCE",
                                     tb, time.time() - tb, key=bidx)
        result = jax.tree_util.tree_unflatten(treedef, out)
        if self.ps_exchange is not None:
            if _defer_ps:
                # async handles: pin PS key-declaration order to program
                # order NOW; the blocking hop itself runs at synchronize(),
                # which drains deferred hops in dispatch order (so workers
                # may synchronize in different orders safely)
                row0_struct = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape[1:] if x.ndim else x.shape, x.dtype), result)
                self.ps_exchange.plan_for(row0_struct, name=name)
            else:
                result = self._ps_hop(result, avg, name)
                self._maybe_sample(result, name)
        else:
            self._maybe_sample(result, name)
        if sync and (self.telemetry is not None or self.timeline is not None):
            jax.block_until_ready(result)
            dt = time.time() - t0
            if self.telemetry is not None:
                self.telemetry.record(nbytes, dt)
            if self.timeline is not None:
                self.timeline.record(name or "push_pull", "PUSH_PULL", t0, dt)
        return result

    # -- async handle API (reference: torch ops.py push_pull_async /
    #    poll / synchronize, handle_manager.cc) ----------------------------
    def push_pull_async(self, tree, average: Optional[bool] = None,
                        name: Optional[str] = None) -> int:
        """Dispatch the bucketed reduction and return an int handle.

        The collectives are enqueued on the device; the caller's host
        thread continues immediately (the cross-barrier overlap of the
        reference, minus the poller thread). Telemetry/timeline recording
        is deferred to ``synchronize`` so it never blocks dispatch.

        EVERY handle must be synchronized (torch contract: the result is
        undefined before synchronize). In PS mode the cross-worker pushes
        happen at ``synchronize()``, which drains ALL deferred hops in
        dispatch order — so synchronizing any later handle also pushes
        this one's contribution, and divergent synchronize orders across
        workers still pair pushes correctly."""
        avg = self.average if average is None else average
        result = self.push_pull(tree, average=avg, name=name, sync=False,
                                _defer_ps=True)
        h = self._next_handle
        self._next_handle += 1
        nbytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(tree))
        self._handles[h] = (result, time.time(), nbytes, name, avg)
        if self.ps_exchange is not None:
            self._ps_pending.append(h)
        return h

    def poll(self, handle: int) -> bool:
        """True once every array behind ``handle`` has finished computing
        (reference: byteps_torch_poll → handle_manager PollHandle). In PS
        mode "ready" means the device reduce finished; the host hop runs
        at synchronize()."""
        result, _, _, _, _ = self._handles[handle]
        return all(leaf.is_ready() for leaf in
                   jax.tree_util.tree_leaves(result)
                   if isinstance(leaf, jax.Array))

    def _drain_ps_hops(self, handle: int) -> None:
        """Run deferred PS host hops in DISPATCH order up to ``handle``.

        Dispatch order is the same on every worker (same program), so
        pushing in that order pairs each worker's round-k push with the
        peers' round-k pushes regardless of synchronize() call order.
        A handle is only dequeued after its hop succeeds: a pull timeout
        (slow/crashed peer) leaves it pending with the device result
        intact, so poll() keeps working and synchronize can be retried."""
        while self._ps_pending:
            h = self._ps_pending[0]
            result, t0, nbytes, name, avg = self._handles[h]
            hopped = self._ps_hop(result, avg, name)
            self._maybe_sample(hopped, name)   # deferred with the hop;
            # non-PS async already sampled at dispatch
            self._handles[h] = (hopped, t0, nbytes, name, avg)
            self._ps_pending.pop(0)
            if h == handle:
                break

    def synchronize(self, handle: int):
        """Block until done and return the reduced tree; the handle is
        released (reference: synchronize(handle), ops.py:204-236). In PS
        mode the deferred cross-worker host hops happen here, drained in
        dispatch order through this handle."""
        if handle in self._ps_pending:
            self._drain_ps_hops(handle)
        result, t0, nbytes, name, avg = self._handles.pop(handle)
        result = jax.block_until_ready(result)
        if self.telemetry is not None or self.timeline is not None:
            dt = time.time() - t0
            if self.telemetry is not None:
                self.telemetry.record(nbytes, dt)
            if self.timeline is not None:
                self.timeline.record(name or "push_pull", "PUSH_PULL", t0, dt)
        return result

    def _bcast_program(self, root_rank: int):
        """Cached jitted broadcast program per root (jit's own cache then
        handles per-shape retraces — the function identity stays stable)."""
        fn = self._bcast_fns.get(root_rank)
        if fn is not None:
            return fn
        axes, mesh = self.axes, self.mesh

        def bcast_fn(x):
            idx = jax.lax.axis_index(axes[0]) if len(axes) == 1 else (
                jax.lax.axis_index(axes[0]) * jax.lax.axis_size(axes[1])
                + jax.lax.axis_index(axes[1]))
            masked = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
            return jax.lax.psum(masked, axes)

        spec = P(axes)
        fn = jax.jit(jax.shard_map(bcast_fn, mesh=mesh, in_specs=spec,
                                   out_specs=spec, check_vma=False))
        self._bcast_fns[root_rank] = fn
        return fn

    def _stacked_leaf(self, leaf) -> bool:
        """True iff ``leaf`` follows the stacked eager convention: a
        committed [dp, ...] array sharded over the data axis. Plain numpy /
        uncommitted / model-sharded leaves are NOT stacked — treating a
        replicated [dp, k] weight as per-rank rows would corrupt it."""
        if not isinstance(leaf, jax.Array) or leaf.ndim < 1 \
                or leaf.shape[0] != self.dp:
            return False
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if not spec:
            return False
        s0 = spec[0]
        names = (s0,) if isinstance(s0, str) else tuple(s0 or ())
        return any(a in names for a in self.axes)

    def broadcast(self, tree, root_rank: int = 0,
                  stacked: Optional[bool] = None):
        """Replicate root's slice to all ranks (reference:
        broadcast_parameters = zero-non-root + push_pull sum,
        torch/__init__.py:259-291 — here a native select + psum).

        Per-leaf semantics by ``stacked``:
          - ``None`` (auto): leaves committed to the data axis with a
            leading [dp, ...] replica dim get the masked-psum broadcast;
            leaves committed to the mesh otherwise (replicated /
            model-sharded) are globally consistent already and pass
            through; host-local leaves pass through single-process (warned
            when ambiguous, i.e. shape[0] == dp) and are broadcast from
            root's process when there are several processes.
          - ``True``: every array leaf with shape[0] == dp is committed to
            the data sharding and broadcast (caller asserts the stacked
            convention).
          - ``False``: no leaf is treated as stacked.
        """
        nproc = jax.process_count()
        if not self.axes and nproc == 1:
            return tree
        # no data axes (model-parallel-only mesh): no stacked leaves exist,
        # but host-local leaves must still be made process-consistent below
        fn = self._bcast_program(root_rank) if self.axes else None
        stacked_sh = (jax.sharding.NamedSharding(self.mesh, P(self.axes))
                      if self.axes else None)
        warned = []

        def committed_to_mesh(x) -> bool:
            return isinstance(x, jax.Array) and isinstance(
                getattr(x, "sharding", None), jax.sharding.NamedSharding)

        def per_leaf(x):
            is_arr = hasattr(x, "dtype") or isinstance(x, np.ndarray)
            if not is_arr:
                return x
            leading_dp = (fn is not None and getattr(x, "ndim", 0) >= 1
                          and x.shape[0] == self.dp)
            if stacked is True and leading_dp:
                return fn(jax.device_put(x, stacked_sh))
            if stacked is None and fn is not None:
                if self._stacked_leaf(x):
                    return fn(x)
                if committed_to_mesh(x):
                    return x  # globally consistent by construction
                if leading_dp and not warned:
                    warned.append(True)
                    from ..common.logging import get_logger
                    get_logger().warning(
                        "broadcast: leaf with leading dim == dp=%d is not "
                        "committed to the data axis; treating it as "
                        "replicated. Pass stacked=True (or device_put with "
                        "a data-axis sharding) for per-rank row broadcast.",
                        self.dp)
            if nproc > 1 and not committed_to_mesh(x):
                from jax.experimental import multihost_utils
                src = jax.process_index() == (root_rank * nproc) // self.dp
                return multihost_utils.broadcast_one_to_all(x, is_source=src)
            return x

        return jax.tree_util.tree_map(per_leaf, tree)

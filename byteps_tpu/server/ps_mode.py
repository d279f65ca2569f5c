"""PS-mode data paths: device ↔ host reduction service.

Two modes, mirroring the reference's two PS deployments:

  - **Sync** (``PSGradientExchange``): gradients already reduced over the
    local ICI mesh hop to the host and are summed across worker processes
    by the sharded key stores — the reference's steady-state push/pull
    pipeline (core_loops.cc:538-618) with the ICI collective playing the
    role of the intra-node NCCL stage. Buckets are pushed in priority
    (backward-completion) order, so the server sums bucket k while
    bucket k+1 is still uploading (the reference's
    pipelining-by-partition, operations.cc:140-180); LANDED buckets are
    pulled by next-step first-use priority (forward order), and up to
    two rounds may be in flight per key (cross-step) under a per-key
    admission gate.

  - **Async** (``AsyncPSWorker``): no worker barrier at all — each worker
    pushes *weight deltas* and pulls fresh weights whenever it finishes a
    local step (reference: BYTEPS_ENABLE_ASYNC server.cc:310-314; torch
    `__init__.py`:186-214 pushing ``w_new - w_old``).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import jax
import numpy as np

from ..common.naming import NameRegistry
from ..common.partition import LeafSpec, plan_buckets
from ..obs import flight
from ..obs.metrics import get_registry, observe_stage
from .admission import LAG_BARRIER, AdmissionPlane
from .engine import HostPSBackend


class _PendingExchange:
    """Handle returned by ``PSGradientExchange.exchange_async``: the
    pushes are already in flight; ``result()`` drains the pulls."""

    __slots__ = ("_drain",)

    def __init__(self, drain) -> None:
        self._drain = drain

    def result(self):
        return self._drain()


class _StreamingExchange:
    """Handle returned by ``PSGradientExchange.exchange_stream``: the
    pushes are in flight and ``ready()`` yields ``(leaf_index, flat host
    array)`` in COMPLETION order, each the moment its last covering
    bucket's pull unpacks — the consumer can start H2D / apply work for
    early buckets while later buckets are still on the wire. A failed
    push or pull surfaces as an exception from the iterator (and from
    ``result()``)."""

    __slots__ = ("_r",)

    def __init__(self, round_) -> None:
        self._r = round_

    @property
    def round_state(self):
        """The underlying ``_Round`` (sharded-update tail plumbing)."""
        return self._r

    def ready(self):
        """Iterate (leaf_index, flat host array) as leaves complete."""
        return self._r.ready_iter()

    def result(self):
        """Drain every pull and return the assembled summed tree (usable
        with or without consuming ``ready()``)."""
        return self._r.drain()


class _IngestExchange:
    """Handle returned by ``PSGradientExchange.exchange_ingest``: the
    INGRESS mirror of ``exchange_stream``. The caller ``feed``s leaves
    the moment their values materialize (the staged backward hands over
    each layer group as its segment finishes); every bucket's D2H +
    pack + push is submitted the instant its last covering leaf arrives
    — no waiting for the full tree, the head analogue of the
    reference's per-tensor push interception. The pull side is the same
    leaf-completion stream as ``exchange_stream``: ``ready()`` /
    ``result()`` behave identically, so the streamed step tail composes
    unchanged. ``finish()`` asserts every leaf was fed; ``abort(exc)``
    unblocks a consumer when the producer dies mid-backward."""

    __slots__ = ("_r",)

    def __init__(self, round_) -> None:
        self._r = round_

    @property
    def round_state(self):
        """The underlying ``_Round`` (sharded-update tail plumbing)."""
        return self._r

    def feed(self, leaf_ids, values) -> None:
        """Hand over device (or host) arrays for ``leaf_ids`` (flat
        indices). Starts ``copy_to_host_async`` immediately; buckets
        completed by these leaves are packed+pushed on worker threads."""
        self._r.feed(leaf_ids, values)

    def finish(self) -> None:
        """Declare feeding complete; raises if any leaf is missing."""
        self._r.finish_feed()

    def abort(self, exc: BaseException) -> None:
        """Producer-side failure: wake ``ready()``/``result()`` with
        ``exc`` instead of leaving them blocked on leaves that will
        never complete."""
        self._r.abort(exc)

    def ready(self):
        """Iterate (leaf_index, flat host array) as leaves complete."""
        return self._r.ready_iter()

    def result(self):
        """Drain every pull and return the assembled summed tree."""
        return self._r.drain()


class _Round:
    """One sync exchange round's machinery, shared by the all-at-once
    paths (``exchange``/``exchange_async``/``exchange_stream``) and the
    incremental head path (``exchange_ingest``): lazily-materialized
    host leaves with PER-LEAF locks (one slow D2H can no longer block
    another bucket's pack worker behind a global lock), bucket
    pack+push, pull+unpack, and leaf-completion streaming."""

    def __init__(self, ex: "PSGradientExchange", tree,
                 name: Optional[str], stream: bool,
                 ingest: bool = False,
                 step: Optional[int] = None,
                 sharded=None) -> None:
        import queue as _queue
        self.ex = ex
        # sharded weight update (byteps_tpu.sharded_update): push EVERY
        # bucket (the server sum needs all contributions) but pull only
        # the buckets covering this replica's OWNED groups; the rest
        # sit in ``await_param`` until the owner's param frames land
        # and ``release_skipped`` frees their admission keys. None =
        # classic full-pull round.
        self.sharded = sharded
        self.skip_buckets = frozenset()     # filled once keyed is known
        # cross-step rounds tag their timeline spans with the TRUE
        # owning step: the round's spans outlive the step that started
        # it, and the overlap aggregates group per step
        self.step_tag = step
        self.decl_name, self.treedef, self.keyed = ex._plan(tree, name)
        # fused-compression decision trace, PINNED per round: the
        # controller (re)decides at this round boundary and the
        # snapshot below is what BOTH the push and the pull of every
        # bucket in this round use — with two rounds in flight
        # (cross-step) each carries its own trace, so a mid-round
        # re-decision can never make a worker pull a codec the server
        # didn't encode
        self.clevels = None
        if ex._cplane is not None:
            ex._cplane.on_round()
            self.clevels = [ex._cplane.level_of(pskey)
                            for pskey, _ in self.keyed]
        # device-side PS_COMPRESS plan (compress/device.py): buckets
        # whose pinned level has a device codec encode ON DEVICE and
        # D2H only the payload — their leaves skip the eager
        # copy_to_host_async (that copy is exactly what the device
        # encode exists to shrink); leaves any HOST bucket covers keep
        # it. Resolved per round from the pinned trace.
        self.dev_bucket = None
        self.host_leaves = None
        if self.clevels is not None and ex._device_encode_on():
            from ..compress.device import DEVICE_CODECS
            self.dev_bucket = [
                bool(lvl in DEVICE_CODECS and ex._cplane.active(pskey)
                     and pskey not in ex._chains)
                for (pskey, _), lvl in zip(self.keyed, self.clevels)]
            if any(self.dev_bucket):
                need = set()
                for dev, (_, b) in zip(self.dev_bucket, self.keyed):
                    if not dev:
                        need.update(s.leaf_index for s in b.segments)
                self.host_leaves = need
            else:
                self.dev_bucket = None
        # epoch-tagged routing (server plane): the placement view this
        # round resolved its routes under. Every push/pull carries it;
        # a key that migrated since is refused with WrongEpoch (an
        # explicit reroute, never a torn assembly) and the exchange
        # refreshes + retries once. None = placement-less backend.
        self.route_epoch = (ex.backend.placement_epoch()
                            if hasattr(ex.backend, "placement_epoch")
                            else None)
        leaves, _ = jax.tree_util.tree_flatten(tree)
        self.shapes = [l.shape for l in leaves]
        # ingest rounds get their sources fed later; the template tree
        # (typically the params) only supplies structure/shapes/dtypes
        self.sources: List = [None] * len(leaves) if ingest else list(leaves)
        self.flat: List[Optional[np.ndarray]] = [None] * len(leaves)
        self.flat_locks = [threading.Lock() for _ in leaves]
        self.out = [np.empty(int(np.prod(l.shape)), np.dtype(l.dtype))
                    for l in leaves]
        self.rounds: List[Optional[int]] = [None] * len(self.keyed)
        # pull ORDER is decoupled from push order: pushes go out in
        # backward-completion (bucket) order, but landed buckets are
        # pulled by NEXT-STEP FIRST-USE priority — the bucket holding
        # the earliest-declared (input-side) leaves first, since those
        # params gate the next forward's first layers (the reference's
        # BYTEPS_SCHEDULING forward-position priority, here on the pull
        # side). Lower = pulled earlier among landed buckets.
        self.pull_prio = [min((s.leaf_index for s in b.segments),
                              default=0) for _, b in self.keyed]
        self.round_seq = ex._next_round_seq()
        if self.sharded is not None:
            self.skip_buckets = frozenset(
                i for i in range(len(self.keyed))
                if i not in self.sharded.pull_buckets)
        self._pulls_left = len(self.keyed) - len(self.skip_buckets)
        self._skips_left = len(self.skip_buckets)
        self._skip_lock = threading.Lock()
        # skipped buckets whose release arrived BEFORE their own push
        # landed (the owner can publish the moment every worker's push
        # reached the server, racing this worker's push bookkeeping)
        self._skip_release_pending: set = set()
        self._pull_lock = threading.Lock()
        self._pull_err: Optional[BaseException] = None
        self._pull_done = threading.Event()
        # per-bucket lifecycle for the watchdog's per-key diagnostic:
        # pending -> pushed -> pulled (or failed); sharded rounds add
        # pending -> await_param -> param_done for non-pulled buckets.
        # "pushed"/"await_param" forever is the wedge signature (a lost
        # pull — or a dead owner's missing param frame — holding the
        # admission gate).
        self.bucket_state = ["pending"] * len(self.keyed)
        self._finished = False
        if not self.keyed:
            self._pull_done.set()
        else:
            ex._register_round(self)
            if self._pulls_left <= 0:
                self._pull_done.set()
        self.aborted: Optional[BaseException] = None
        self.readyq = None
        if stream or ingest:
            self.readyq = _queue.Queue()
            self.seg_left = [0] * len(leaves)
            for _, b in self.keyed:
                for s in b.segments:
                    self.seg_left[s.leaf_index] += 1
            # sharded rounds stream only the OWNED groups' leaves —
            # the rest complete via the param-fetch path, and their
            # partial grad data (shared boundary buckets) must never
            # reach the consumer as if it were a finished leaf
            if self.sharded is not None:
                for li in range(len(leaves)):
                    if li not in self.sharded.stream_leaves:
                        self.seg_left[li] = -1      # never enqueued
            self._stream_n = sum(1 for n in self.seg_left if n >= 0)
            self.seg_lock = threading.Lock()
            for li, n in enumerate(self.seg_left):
                if n == 0:          # zero-size leaf: no covering bucket,
                    self.readyq.put((li, self.out[li]))  # ready at once
        self.ingest = ingest
        if ingest:
            self.dtypes = [np.dtype(l.dtype) for l in leaves]
            # bucket -> distinct covering leaves; a bucket is pushable
            # when all of them have been fed
            self.bucket_leaves = [
                sorted({s.leaf_index for s in b.segments})
                for _, b in self.keyed]
            self.bucket_need = [len(ls) for ls in self.bucket_leaves]
            self.leaf_buckets: Dict[int, List[int]] = {}
            for bi, ls in enumerate(self.bucket_leaves):
                for li in ls:
                    self.leaf_buckets.setdefault(li, []).append(bi)
            self.fed = [False] * len(leaves)
            self.feed_lock = threading.Lock()
            self.feed_done = False

    # ------------------------------------------------------ host leaves

    def get_flat(self, i: int) -> np.ndarray:
        v = self.flat[i]         # double-checked: a ready leaf never waits
        if v is not None:        # behind its own (or any) lock
            return v
        with self.flat_locks[i]:
            if self.flat[i] is None:
                import time
                t0 = time.time()
                # ascontiguousarray: the native pack does raw pointer
                # math (no-op for device readbacks). np.asarray blocks
                # on the leaf's D2H copy — only ITS OWN copy, per-leaf
                self.flat[i] = np.ascontiguousarray(
                    np.asarray(self.sources[i])).reshape(-1)
                observe_stage("PS_D2H", time.time() - t0)
                if self.ex.timeline is not None:
                    self.ex.timeline.record(self.decl_name, "PS_D2H", t0,
                                            time.time() - t0, i,
                                            step=self.step_tag)
            return self.flat[i]

    # ------------------------------------------------------ push / pull

    def push_one(self, idx: int) -> np.ndarray:
        import time
        ex = self.ex
        pskey, b = self.keyed[idx]
        self.rounds[idx] = ex._next_round(pskey)
        if self.dev_bucket is not None and self.dev_bucket[idx]:
            buf = ex._push_bucket_device(self, idx)
            if buf is not None:
                self.bucket_state[idx] = "pushed"
                ex._mark_progress()
                return buf
            # device fallback (host-fed leaf / kernel failure): the
            # eager D2H was skipped for device-only leaves, but
            # get_flat's np.asarray below blocks on its own copy —
            # slower, never wrong
        t0 = time.time()
        buf = np.empty(b.size, dtype=b.dtype)
        if ex._native_pack:
            # native gather: one GIL-released call per bucket instead
            # of a GIL-held numpy copy per segment (VERDICT r4 #5 — the
            # uncompressed hop's interpreter cost; reference
            # core_loops.cc:538-618 stages zero-copy in C++ too)
            item = np.dtype(b.dtype).itemsize
            from .engine import pack_segments
            pack_segments(
                [self.get_flat(s.leaf_index).ctypes.data
                 + s.leaf_offset * item for s in b.segments],
                [s.bucket_offset * item for s in b.segments],
                [s.length * item for s in b.segments], buf)
        else:
            for s in b.segments:
                buf[s.bucket_offset:s.bucket_offset + s.length] = \
                    self.get_flat(s.leaf_index)[
                        s.leaf_offset:s.leaf_offset + s.length]
        t0 = ex._record(self.decl_name, "PS_PACK", pskey, t0,
                        step=self.step_tag)
        # host-path D2H accounting: this bucket's segments crossed
        # PCIe dense (segments partition leaves, so per-bucket sums
        # tile the real copy exactly)
        ex._d2h_account(pskey, buf.nbytes)
        try:
            ex._push_bucket(pskey, b, buf, rnd=self, idx=idx)
        except Exception:
            # the round counter advanced but the push never landed: drop
            # the entry so a retried exchange() re-seeds from the
            # server's round instead of pulling a round that will never
            # complete (permanent sliced-pull timeout)
            with ex._key_rounds_lock:
                ex._key_rounds.pop(pskey, None)
            raise
        ex._record(self.decl_name, "PS_PUSH", pskey, t0,
                   step=self.step_tag, round=self.rounds[idx])
        self.bucket_state[idx] = "pushed"
        ex._mark_progress()
        return buf

    def pull_one(self, idx: int, buf: np.ndarray) -> None:
        import time
        ex = self.ex
        pskey, b = self.keyed[idx]
        t0 = time.time()
        merged = ex._pull_bucket(pskey, b, buf, self.rounds[idx],
                                 rnd=self, idx=idx)
        t0 = ex._record(self.decl_name, "PS_PULL", pskey, t0,
                        step=self.step_tag, round=self.rounds[idx])
        if ex._native_pack and merged.flags["C_CONTIGUOUS"]:
            item = np.dtype(b.dtype).itemsize
            from .engine import unpack_segments
            unpack_segments(
                merged,
                [s.bucket_offset * item for s in b.segments],
                [self.out[s.leaf_index].ctypes.data + s.leaf_offset * item
                 for s in b.segments],
                [s.length * item for s in b.segments])
        else:
            for s in b.segments:        # disjoint segments: thread-safe
                self.out[s.leaf_index][
                    s.leaf_offset:s.leaf_offset + s.length] = \
                    merged[s.bucket_offset:s.bucket_offset + s.length]
        ex._record(self.decl_name, "PS_UNPACK", pskey, t0,
                   step=self.step_tag)
        self.bucket_state[idx] = "pulled"
        ex._m_buckets.inc()
        ex._mark_progress()
        if self.readyq is not None:
            for s in b.segments:
                self._segment_done(s.leaf_index)

    def _segment_done(self, li: int) -> None:
        with self.seg_lock:
            if self.seg_left[li] < 0:    # sharded: non-streamed leaf
                return                   # (completes via param fetch)
            self.seg_left[li] -= 1
            done = self.seg_left[li] == 0
        if done:
            self.readyq.put((li, self.out[li]))

    def assemble(self):
        shaped = [o.reshape(shp) for o, shp in zip(self.out, self.shapes)]
        return jax.tree_util.tree_unflatten(self.treedef, shaped)

    def submit_bucket(self, idx: int) -> None:
        """Queue bucket ``idx``'s pack+push; its pull is enqueued into
        the exchange's priority scheduler when the push lands. The push
        is ADMITTED per PS key by the admission plane's KeyGate: at
        K=1 (two rounds in flight, cross-step) round k+1's push for a
        key waits until round k's pull of that key completed — the
        server publishes one round per key at a time, so an earlier
        push would overwrite the merge a straggler pull still needs
        (torn assembly). Under ``BPS_MAX_LAG=K`` the gate is a
        counting semaphore of depth K and the server versions rounds
        (docs/admission.md), so up to K+1 rounds overlap per key."""
        ex = self.ex
        pskey, _ = self.keyed[idx]
        ex.plane.gate.admit(pskey,
                            lambda: ex._push_ex.submit(self._push_task,
                                                       idx))

    def _push_task(self, idx: int) -> None:
        pskey, _ = self.keyed[idx]
        skip = idx in self.skip_buckets
        try:
            buf = self.push_one(idx)
        except BaseException as e:   # noqa: BLE001 — relayed to consumers
            self.bucket_state[idx] = "failed"
            self.ex.plane.gate.release(pskey)
            if skip:
                self._skip_finished(e)
            else:
                self._pull_finished(e)
            return
        if not skip:
            self.ex._enqueue_pull(self, idx, buf)
            return
        # sharded round, non-owned bucket: no pull — the admission key
        # stays held until the owner's param frames for every group this
        # bucket covers have landed (release_skipped). If the release
        # raced ahead of this push's bookkeeping, complete it inline.
        with self._skip_lock:
            self.bucket_state[idx] = "await_param"
            fire = idx in self._skip_release_pending
            if fire:
                self._skip_release_pending.discard(idx)
        if fire:
            self._finish_skip_release(idx)

    def release_skipped(self, idx: int) -> None:
        """Param frames for every group bucket ``idx`` covers have
        landed (sharded update): release the bucket's admission key so
        the next round's push can go, and COMMIT the compression
        plane's pending EF residual — the frame's arrival proves the
        owner consumed this round's merge, the same signal a pull gives
        the unsharded path."""
        if idx not in self.skip_buckets:
            raise ValueError(f"bucket {idx} is not a skipped bucket of "
                             f"this round")
        with self._skip_lock:
            if self.bucket_state[idx] == "param_done":
                return
            if self.bucket_state[idx] != "await_param":
                # the owner published before OUR push bookkeeping
                # finished (its publish only needs the push to have
                # REACHED the server): defer to the push task
                self._skip_release_pending.add(idx)
                return
        self._finish_skip_release(idx)

    def _finish_skip_release(self, idx: int) -> None:
        ex = self.ex
        pskey, _ = self.keyed[idx]
        plane = ex._cplane
        if plane is not None and plane.active(pskey):
            plane.commit(pskey, self.rounds[idx])
        self.bucket_state[idx] = "param_done"
        ex._mark_progress()
        ex.plane.gate.release(pskey)
        self._skip_finished(None)

    def _skip_finished(self, exc: Optional[BaseException]) -> None:
        if exc is not None:
            if self._pull_err is None:
                self._pull_err = exc
            if self.readyq is not None:
                self.readyq.put(exc)
        with self._pull_lock:
            self._skips_left -= 1
            done = self._pulls_left <= 0 and self._skips_left <= 0
        if done:
            self._mark_finished()

    def _pull_finished(self, exc: Optional[BaseException]) -> None:
        """Bucket-terminal accounting (pull done, or push/pull failed):
        completes ``drain()`` and surfaces the first failure to the
        ready-stream consumer."""
        if exc is not None:
            if self._pull_err is None:
                self._pull_err = exc
            if self.readyq is not None:
                self.readyq.put(exc)
        with self._pull_lock:
            self._pulls_left -= 1
            grads_done = self._pulls_left <= 0
            all_done = grads_done and self._skips_left <= 0
        if all_done:
            self._mark_finished()
        if grads_done:
            self._pull_done.set()

    def _mark_finished(self) -> None:
        """Terminal accounting for the rounds-in-flight gauge / watchdog
        (idempotent: a drained round that is later abort()ed must not
        double-decrement)."""
        with self._pull_lock:
            if self._finished:
                return
            self._finished = True
        self.ex._m_rounds.dec()

    def drain(self):
        if getattr(self, "aborted", None) is not None:
            raise self.aborted
        if self.ingest:
            # an incompletely-fed round never submits some buckets, so
            # their terminal accounting never fires — waiting would
            # hang; fail loudly instead (and an abort() racing this
            # drain must win over a silent partial result)
            with self.feed_lock:
                missing = sum(not f for f in self.fed)
            if missing:
                raise RuntimeError(
                    f"exchange_ingest result() with {missing} leaves "
                    f"never fed — call feed() for every leaf and "
                    f"finish() before draining")
        self._pull_done.wait()
        if self.aborted is not None:
            raise self.aborted
        if self._pull_err is not None:
            raise self._pull_err
        return self.assemble()

    def ready_iter(self):
        yielded = 0
        n = getattr(self, "_stream_n", len(self.out))
        while yielded < n:
            item = self.readyq.get()
            if isinstance(item, BaseException):
                raise item
            yield item
            yielded += 1

    # ------------------------------------------------------ ingest path

    def feed(self, leaf_ids, values) -> None:
        pairs = list(zip(leaf_ids, values))   # values may be one-shot
        for li, v in pairs:
            # the bucket plan's segment offsets were computed from the
            # template — a mismatched leaf would make the native pack's
            # pointer math read out of bounds, silently
            if (int(np.prod(getattr(v, "shape", ()))) !=
                    int(np.prod(self.shapes[li]))
                    or np.dtype(v.dtype) != self.dtypes[li]):
                raise ValueError(
                    f"fed leaf {li} is {getattr(v, 'shape', ())}/"
                    f"{v.dtype}, plan expects {self.shapes[li]}/"
                    f"{self.dtypes[li]}")
            if hasattr(v, "copy_to_host_async") and (
                    self.host_leaves is None or li in self.host_leaves):
                v.copy_to_host_async()   # start D2H before any pack —
                #                          skipped for leaves only
                #                          device-encoded buckets cover
                #                          (their payload IS the D2H)
        self.ex._mark_progress()
        fire: List[int] = []
        with self.feed_lock:
            if self.feed_done:
                raise RuntimeError("feed() after finish()")
            for li, v in pairs:
                if self.fed[li]:
                    raise ValueError(f"leaf {li} fed twice")
                self.fed[li] = True
                self.sources[li] = v
                for bi in self.leaf_buckets.get(li, ()):
                    self.bucket_need[bi] -= 1
                    if self.bucket_need[bi] == 0:
                        fire.append(bi)
        for bi in fire:
            self.submit_bucket(bi)

    def finish_feed(self) -> None:
        with self.feed_lock:
            missing = [li for li, f in enumerate(self.fed) if not f]
            self.feed_done = True
        if missing:
            raise ValueError(
                f"exchange_ingest round finished with {len(missing)} "
                f"leaves never fed (first missing: {missing[:5]}) — every "
                f"flat leaf must be handed over exactly once")

    def abort(self, exc: BaseException) -> None:
        self.aborted = exc
        if self.keyed:              # keep the in-flight gauge/watchdog
            self._mark_finished()   # from counting a dead round forever
        self._pull_done.set()       # a drain() blocked on straggler
        if self.readyq is not None:  # pulls must wake and raise
            self.readyq.put(exc)


class PSGradientExchange:
    """Sync-mode bucketed gradient exchange through the host PS service.

    The exchange is PIPELINED per bucket (BPS_PS_PIPELINE threads,
    default 4; ≤1 = serial): bucket k+1's pack+push runs while bucket
    k's pull is blocked on the server's merge, and the pull lands as
    soon as that merge publishes — the reference's free-running
    push/pull loops (core_loops.cc:538-618) rather than a
    push-everything-then-pull-everything barrier. Requires a transport
    with >1 connection per shard (RemotePSBackend pools,
    BPS_PS_CONNS) so a round-blocked PULL doesn't stall later PUSH
    frames; the in-process backend is natively concurrent."""

    def __init__(self, backend: HostPSBackend, partition_bytes: int = 4 << 20,
                 registry: Optional[NameRegistry] = None,
                 min_compress_bytes: int = 65536,
                 pipeline_depth: Optional[int] = None,
                 watchdog_sec: Optional[float] = None,
                 compress: Optional[str] = None,
                 max_lag: Optional[int] = None,
                 worker_id: Optional[int] = None) -> None:
        self.backend = backend
        self.partition_bytes = partition_bytes
        self.registry = registry or NameRegistry()
        self.min_compress_bytes = min_compress_bytes
        # fused compression plane (byteps_tpu.compress): per-bucket
        # codecs composed into THIS pipeline — compress on the pack
        # worker right before PUSH, decompress on the pull path feeding
        # the H2D/apply tail — with the codec level decided per layer
        # at round boundaries (BPS_COMPRESS=auto) or pinned
        # (=fp16|int8|topk). None (=none, the default) keeps the dense
        # path bit-identical to a plane-less build. The explicit arg
        # (Config.compress, wired by GlobalState and the trainer) wins;
        # the env fallback covers directly-constructed exchanges.
        from ..compress.plane import CompressionPlane
        self._cplane = CompressionPlane.from_config(
            compress, min_bytes=min_compress_bytes)
        if self._cplane is not None:
            # capability check at CONFIG time, not mid-training: with
            # auto mode an incapable backend would otherwise train fine
            # on an idle wire for hours and crash the moment the
            # controller first ratchets a layer up
            if not hasattr(backend, "push_fused"):
                raise ValueError(
                    f"BPS_COMPRESS={self._cplane.mode!r} needs a "
                    f"backend with push_fused/pull_fused; "
                    f"{type(backend).__name__} has neither")
            chk = getattr(backend, "_check_fused_shards", None)
            if chk is not None:
                chk()    # a plane backend also vets its shard list
        self.pipeline_depth = (int(os.environ.get("BPS_PS_PIPELINE", "4"))
                               if pipeline_depth is None else pipeline_depth)
        self.timeline = None            # set by GlobalState when tracing
        self._plans: Dict = {}
        # pskey -> per-layer ps/pull_bytes/<decl>.<bucket> counter,
        # registered at plan time (see _plan)
        self._pull_layer: Dict[int, object] = {}
        # pskey -> per-layer ps/d2h_bytes/<decl>.<bucket> counter —
        # bytes a bucket moved across D2H (its dense segments on the
        # host path, the encoded payload on the device-encode path)
        self._d2h_layer: Dict[int, object] = {}
        # can the backend carry the fused-managed declaration on init?
        # (duck-typed test backends may speak push_fused without it)
        import inspect as _inspect
        try:
            self._init_fused_ok = "fused" in _inspect.signature(
                backend.init_key).parameters
        except (TypeError, ValueError):
            self._init_fused_ok = False
        # device-side PS_COMPRESS (compress/device.py): resolved + probed
        # lazily at the first eligible bucket so CPU rigs with the
        # default auto mode never pay the probe
        self._dev_enc: Optional[bool] = None
        self._key_rounds: Dict[int, int] = {}
        self._key_rounds_lock = threading.Lock()
        self._push_ex: Optional[ThreadPoolExecutor] = None
        self._pull_ex: Optional[ThreadPoolExecutor] = None
        self._ex_lock = threading.Lock()
        # unified admission plane (server/admission.py): owns the
        # per-key push gate (depth K — a key with K pushed-but-unpulled
        # rounds holds later pushes in a per-key FIFO), the
        # landed-bucket pull priority queue, and — via the process
        # global — the two-class wire send scheduler. K=1 (the default)
        # is the classic two-rounds-in-flight cross-step window; K>1
        # routes dense rounds through the server's bounded-staleness
        # store (BPS_MAX_LAG / push_lag / pull_lag).
        self.plane = AdmissionPlane(max_lag=max_lag, worker_id=worker_id)
        if self.plane.max_lag > 1 and not hasattr(backend, "push_lag"):
            # config-time capability check, mirroring the compression
            # plane's: a backend without the versioned-round surface
            # would silently train at K=1 while the worker runs ahead
            raise ValueError(
                f"BPS_MAX_LAG={self.plane.max_lag} needs a backend "
                f"with declare_lag/push_lag/pull_lag; "
                f"{type(backend).__name__} has none")
        # per-PS-key worker compressor chain (momentum→ef→codec) — holds
        # EF error / momentum state, so it outlives the plan cache entry
        # (reference: per-partition compressor_list in BPSContext,
        # common.h:202, operations.cc:380-385)
        self._chains: Dict[int, object] = {}
        # native bucket pack/unpack (BPS_NATIVE_PACK=0 forces the numpy
        # per-segment path for A/B); falls back when the .so is absent
        self._native_pack = os.environ.get("BPS_NATIVE_PACK", "1") != "0"
        if self._native_pack:
            try:
                from .engine import _lib
                _lib()
            except Exception:   # noqa: BLE001 — toolchain-less install
                self._native_pack = False
        # observability: always-on registry handles (cached — the
        # registry lookup is locked, the hot-path inc/observe is not)
        # plus the stall watchdog (BPS_WATCHDOG_SEC>0), started with
        # the first exchange so idle constructions stay thread-free
        reg = get_registry()
        self._m_push_bytes = reg.counter("ps/push_bytes")
        self._m_pull_bytes = reg.counter("ps/pull_bytes")
        self._m_d2h_bytes = reg.counter("ps/d2h_bytes")
        self._m_buckets = reg.counter("ps/buckets_completed")
        self._m_rounds = reg.gauge("ps/rounds_in_flight")
        import time as _time
        # MONOTONIC: an NTP step on the wall clock must neither fake a
        # stall nor hide one (the watchdog diffs this against its own
        # monotonic now)
        self._progress_t = _time.monotonic()
        self._live_rounds: List = []      # weakrefs, pruned on register
        self._rounds_reg_lock = threading.Lock()
        self._watchdog = None
        # explicit arg (Config.watchdog_sec, wired by GlobalState and
        # the trainer) wins; the env fallback covers directly-
        # constructed exchanges (tests, scripts without bps.init)
        self._watchdog_sec = (float(watchdog_sec)
                              if watchdog_sec is not None else float(
                                  os.environ.get("BPS_WATCHDOG_SEC", "0")
                                  or 0))

    def close(self) -> None:
        """Stop the pipeline executors and the watchdog (idempotent).
        bps.shutdown() calls this — without it every init/shutdown
        cycle would strand 2×pipeline_depth idle threads."""
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        for ex in (self._push_ex, self._pull_ex):
            if ex is not None:
                ex.shutdown(wait=False)
        self._push_ex = self._pull_ex = None

    # -------------------------------------------- observability hooks

    def _mark_progress(self) -> None:
        """A bucket advanced (push landed / pull completed / leaf fed):
        re-arm the stall watchdog's clock (monotonic — see __init__)."""
        import time
        self._progress_t = time.monotonic()

    def _register_round(self, rnd: "_Round") -> None:
        import weakref
        with self._rounds_reg_lock:
            alive = []
            for ref in self._live_rounds:
                r = ref()           # deref once: the target may be
                if r is not None and not r._finished:   # GC'd between
                    alive.append(ref)                   # two calls
            alive.append(weakref.ref(rnd))
            self._live_rounds = alive
        self._m_rounds.inc()

    def in_flight_buckets(self) -> int:
        """Buckets of live rounds whose pull has not completed."""
        n = 0
        with self._rounds_reg_lock:
            for ref in self._live_rounds:
                r = ref()
                if r is not None and not r._finished:
                    # sharded rounds: buckets awaiting the owner's param
                    # publish are in flight too (their admission keys
                    # are held) — the watchdog must see a dead owner's
                    # wedge, not an idle exchange
                    n += max(0, r._pulls_left) + max(0, r._skips_left)
        return n

    def progress_state(self):
        """(last progress MONOTONIC timestamp, in-flight bucket count)
        — the StallWatchdog's poll target."""
        return self._progress_t, self.in_flight_buckets()

    def debug_state(self) -> dict:
        """Per-key snapshot of the live exchange state: every unfinished
        round's buckets (round number + pending/pushed/pulled/failed)
        and the admission gate's holders and queued waiters — what the
        watchdog dumps when the pipeline wedges."""
        rounds = []
        with self._rounds_reg_lock:
            live = [r() for r in self._live_rounds]
        for r in live:
            if r is None or r._finished:
                continue
            buckets = []
            for i, (pskey, _) in enumerate(r.keyed):
                b = {"pskey": pskey, "round": r.rounds[i],
                     "state": r.bucket_state[i]}
                if r.sharded is not None and i in r.skip_buckets:
                    # param-publish state (sharded update): name EVERY
                    # owner replica a frame must come from (boundary
                    # buckets can wait on two), so a dead-owner wedge
                    # is attributable from the dump
                    owners = r.sharded.skip_owner.get(i, ())
                    b["owner"] = (owners[0] if len(owners) == 1
                                  else list(owners))
                buckets.append(b)
            rounds.append({
                "name": r.decl_name,
                "step": r.step_tag,
                "seq": r.round_seq,
                "pulls_left": r._pulls_left,
                "skips_left": r._skips_left,
                "buckets": buckets,
            })
        return {"in_flight": self.in_flight_buckets(),
                "rounds": rounds, "admission": self.plane.gate.state()}

    def _ensure_watchdog(self) -> None:
        if self._watchdog is not None or self._watchdog_sec <= 0:
            return
        from ..obs.watchdog import StallWatchdog
        # locked check-and-create: two concurrent first exchanges must
        # not each start a watchdog thread (close() could only ever
        # stop the survivor)
        with self._rounds_reg_lock:
            if self._watchdog is None:
                self._watchdog = StallWatchdog(self, self._watchdog_sec)

    def _plan(self, tree, name: Optional[str]):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        key = (name, treedef, tuple((l.shape, str(l.dtype)) for l in leaves))
        if key in self._plans:
            return self._plans[key]
        # Distinct trees must land on distinct PS keys. Anonymous trees
        # get position-stable auto names, so key assignment matches across
        # workers as long as their exchange order matches — the same
        # declaration-order contract the reference has (global.cc:412-429).
        decl_name = name or f"grads{len(self._plans)}"
        decl = (self.registry.get(decl_name)
                if decl_name in self.registry.declared_names()
                else self.registry.declare(decl_name))
        paths = [jax.tree_util.keystr(p)
                 for p, _ in jax.tree_util.tree_leaves_with_path(tree)]
        specs = [LeafSpec(name=p, size=int(np.prod(l.shape)),
                          dtype=str(np.dtype(l.dtype)))
                 for p, l in zip(paths, leaves)]
        buckets = plan_buckets(specs, self.partition_bytes, reverse_order=True)
        # per-bucket PS keys: declared_key<<16 | bucket (reference:
        # operations.cc:301-317)
        keyed = [(decl.key_for_partition(b.index), b) for b in buckets]
        ckw = decl.compression_kwargs
        compress = bool(ckw.get("compressor_type"))
        for pskey, b in keyed:
            nbytes = b.size * np.dtype(b.dtype).itemsize
            # tensors below the floor skip compression (reference:
            # BYTEPS_MIN_COMPRESS_BYTES, operations.cc:362-364)
            if compress and nbytes >= self.min_compress_bytes:
                from ..ops.compression.host import create_host_chain
                if pskey not in self._chains:
                    self._chains[pskey] = create_host_chain(
                        ckw, b.size, b.dtype)
                self.backend.init_key(pskey, nbytes, b.dtype,
                                      compression=ckw)
                continue
            # fused-plane eligibility decided BEFORE init so the server
            # learns it with the declaration: fused-managed keys get
            # their rounds owned by the homogeneous sum store (legacy
            # kwargs chains keep precedence and stay dense-keyed)
            fused = (self._cplane is not None
                     and self._cplane.register(
                         pskey, b.size, b.dtype,
                         layer=f"{decl_name}.{b.index}"))
            if fused and self._init_fused_ok:
                self.backend.init_key(pskey, nbytes, b.dtype, fused=True)
            else:
                self.backend.init_key(pskey, nbytes, b.dtype)
        # per-layer pull-byte + D2H-byte counters, dynamically
        # registered at plan time exactly like the compress plane's
        # ps/push_bytes/<layer> — the 1/dp pull reduction of the
        # sharded update and the device-encode D2H halving are both
        # directly observable per layer
        for pskey, b in keyed:
            if pskey not in self._pull_layer:
                self._pull_layer[pskey] = get_registry().counter(
                    f"ps/pull_bytes/{decl_name}.{b.index}")
            if pskey not in self._d2h_layer:
                self._d2h_layer[pskey] = get_registry().counter(
                    f"ps/d2h_bytes/{decl_name}.{b.index}")
        if self.plane.max_lag > 1:
            # bounded staleness covers the DENSE path only: compressed
            # chains and fused-plane keys keep their classic one-round
            # stores (their codecs assume complete sums), so they stay
            # at the K=1 contract while dense keys absorb stragglers
            for pskey, b in keyed:
                if self._lag_routes(pskey):
                    self.backend.declare_lag(pskey, self.plane.max_lag)
        if hasattr(self.backend, "set_send_priority"):
            # two-class wire scheduler (admission plane): gradient
            # frames carry reverse-FIRST-USE priority — the bucket
            # holding the earliest-declared (input-side) leaves sends
            # first under BPS_SCHEDULING_CREDIT, the same order the
            # cross-step pull heap drains (pull_prio), so the send and
            # pull sides agree on who gates the next forward
            nleaves = len(leaves)
            for pskey, b in keyed:
                first = min((s.leaf_index for s in b.segments),
                            default=0)
                self.backend.set_send_priority(pskey, nleaves - first)
        plan = (decl_name, treedef, keyed)
        self._plans[key] = plan
        return plan

    def plan_for(self, tree, name: Optional[str] = None) -> None:
        """Pre-declare keys for ``tree`` NOW. Deferred-exchange callers
        (async handles) use this at dispatch so key assignment follows
        program order on every worker even if their synchronize order
        later diverges (the declaration-order contract above)."""
        self._plan(tree, name)

    def leaf_groups(self, tree, name: Optional[str] = None):
        """Partition ``tree``'s flat leaf indices into groups by the LAST
        bucket that covers each leaf — the bucket whose pull completes
        the leaf. Consumers that apply per group (chunked optimizer
        apply) see group k's leaves become ready together around bucket
        k's pull, so group-granular work pipelines with later buckets
        still in flight. Groups are returned in bucket order with empty
        groups dropped; together they cover every leaf exactly once."""
        _, _, keyed = self._plan(tree, name)
        nleaves = len(jax.tree_util.tree_leaves(tree))
        last: Dict[int, int] = {}
        for bi, (_, b) in enumerate(keyed):
            for s in b.segments:
                last[s.leaf_index] = bi       # ascending bi: max wins
        groups: List[List[int]] = [[] for _ in keyed]
        for li in sorted(last):
            groups[last[li]].append(li)
        extras = [li for li in range(nleaves) if li not in last]
        if extras:                  # zero-size leaves: no covering
            if not groups:          # bucket, ready immediately — group 0
                groups = [[]]
            groups[0].extend(sorted(extras))
        return [g for g in groups if g]

    def _record(self, name: str, stage: str, key: int, t0: float,
                step: Optional[int] = None,
                round: Optional[int] = None) -> float:
        """Timeline + stage-histogram helper; returns a fresh t0. The
        histogram observation is ALWAYS on (the latency distributions
        are the production signal); the timeline event only inside a
        trace window. ``round`` tags wire spans (PS_PUSH/PS_PULL) with
        their PS round so the merged trace / critical-path analyzer
        joins them against the server's (key, round) span records."""
        import time
        now = time.time()
        observe_stage(stage, now - t0)
        if self.timeline is not None:
            self.timeline.record(name, stage, t0, now - t0, key,
                                 step=step, round=round)
        return now

    def _next_round(self, pskey: int) -> int:
        """This push's round for ``pskey``, PER KEY. First use of a key
        seeds from the SERVER's completed round — elastic rejoin of a
        live job (the reference's is_recovery skip-barrier analog,
        global.cc:283-297): a predecessor may have died BETWEEN bucket
        pushes, leaving keys at different rounds, so a single per-decl
        seed would misalign the lagging keys forever. Fresh jobs see 0
        everywhere (one extra RPC per key, amortized across the
        pipeline workers). The per-key admission gate serializes two
        live rounds' tasks on one key, but the increment is still
        atomic under the lock — "one task per key per EXCHANGE" is no
        longer "one task per key in flight"."""
        with self._key_rounds_lock:
            cur = self._key_rounds.get(pskey)
        if cur is None:
            # the server RPC stays outside the lock; losing the seed
            # race is fine (both see the same server round)
            cur = (int(self.backend.round(pskey))
                   if hasattr(self.backend, "round") else 0)
        with self._key_rounds_lock:
            nxt = self._key_rounds.get(pskey, cur) + 1
            self._key_rounds[pskey] = nxt
        return nxt

    def _next_round_seq(self) -> int:
        return self.plane.pulls.next_round_seq()

    # ------------------------------------------------ pull scheduling
    #
    # Pushes keep backward-completion order (bucket 0 = output-side
    # layers, available first), but pulls drain by NEXT-STEP FIRST-USE
    # priority — the plane's PullQueue (see admission.PullQueue for the
    # why of that ordering).

    def _enqueue_pull(self, rnd: "_Round", idx: int, buf) -> None:
        self.plane.pulls.put(rnd.round_seq, rnd.pull_prio[idx],
                             (rnd, idx, buf))
        self._pull_ex.submit(self._pull_next)

    def _pull_next(self) -> None:
        """One pull slot: drain the highest-priority landed bucket
        (not necessarily the one whose push scheduled this slot)."""
        rnd, idx, buf = self.plane.pulls.pop()
        pskey, _ = rnd.keyed[idx]
        exc: Optional[BaseException] = None
        try:
            rnd.pull_one(idx, buf)
        except BaseException as e:   # noqa: BLE001 — relayed below
            exc = e
            rnd.bucket_state[idx] = "failed"
            # tail-failure postmortem: the error surfaces to the
            # caller at the next sync point, possibly seconds from
            # now — dump what HAPPENED on this key's path while the
            # flight ring still holds it
            from ..common.logging import get_logger
            flight.dump(get_logger(), keys=[pskey],
                        reason=f"pull failure key={pskey} "
                               f"round={rnd.rounds[idx]}: "
                               f"{type(e).__name__}: {e}")
        finally:
            self.plane.gate.release(pskey)
            rnd._pull_finished(exc)

    def _routed(self, rnd, op) -> None:
        """Run ``op(epoch)`` under the round's placement-epoch tag.
        WrongEpoch (the key migrated after the round resolved its
        routes) is an explicit reroute signal: refresh the view and
        retry ONCE with the fresh epoch — the plane's routing table is
        authoritative, so the second attempt lands on the new owner."""
        if rnd is None or rnd.route_epoch is None:
            return op(None)
        from .plane.placement import WrongEpoch
        try:
            return op(rnd.route_epoch)
        except WrongEpoch:
            rnd.route_epoch = self.backend.placement_epoch()
            return op(rnd.route_epoch)

    def _lag_routes(self, pskey: int) -> bool:
        """Does ``pskey`` ride the bounded-staleness path? Only with
        K>1, and only dense keys (see the _plan declaration note)."""
        return (self.plane.max_lag > 1
                and pskey not in self._chains
                and (self._cplane is None
                     or not self._cplane.active(pskey)))

    def _lag_verdict(self, pskey: int, rnd_num: int, flags: int) -> None:
        """Worker-side note of the server's serve verdict (the server
        records the DECISION; this names what this worker observed)."""
        if flags and flight.get_recorder().enabled:
            verdict = ("barrier" if flags & LAG_BARRIER
                       else "stale")
            flight.record("lag_admit",
                          detail=f"verdict={verdict} key={pskey} "
                                 f"round={rnd_num} (served)")

    def _round_level(self, rnd, idx: int) -> int:
        """The codec level this round's decision trace pinned for
        bucket ``idx`` (0 = none/dense)."""
        if (rnd is None or idx is None
                or getattr(rnd, "clevels", None) is None):
            return 0
        return rnd.clevels[idx]

    def _device_encode_on(self) -> bool:
        """Resolve (once) whether PS_COMPRESS runs on device —
        BPS_COMPRESS_DEVICE plus the bitwise probe-or-fallback
        (compress/device.py)."""
        if self._dev_enc is None:
            if self._cplane is None:
                self._dev_enc = False
            else:
                # a probe failure raises (naming the codec): never a
                # silent downgrade to the host codec
                from ..compress.device import device_encode_enabled
                self._dev_enc = device_encode_enabled()
        return self._dev_enc

    def _d2h_account(self, pskey: int, nbytes: int) -> None:
        self._m_d2h_bytes.inc(nbytes)
        m = self._d2h_layer.get(pskey)
        if m is not None:
            m.inc(nbytes)

    def _push_bucket_device(self, rnd, idx: int):
        """Device-side PS_COMPRESS: gather + EF fold + quantize ON
        DEVICE, D2H only the encoded payload, push it fused. Returns
        the pull staging buffer on success, None to signal the host
        fallback (a host-fed leaf, or a kernel failure — logged once).
        The encode runs BEFORE any state mutation commits, so a
        fallback never leaves a half-staged EF pending."""
        import time

        import jax
        pskey, b = rnd.keyed[idx]
        level = rnd.clevels[idx]
        parts = []
        for s in b.segments:
            src = rnd.sources[s.leaf_index]
            if not isinstance(src, jax.Array):
                return None
            parts.append((src, s.leaf_offset, s.length))
        t0 = time.time()
        try:
            payload, d2h = self._cplane.encode_on_device(
                pskey, parts, level, rnd.rounds[idx])
        except Exception as e:   # noqa: BLE001 — probe-or-fallback
            if not getattr(self, "_dev_warned", False):
                self._dev_warned = True
                from ..common.logging import get_logger
                get_logger().warning(
                    "device encode failed for key %d (%s: %s) — "
                    "falling back to the host codec", pskey,
                    type(e).__name__, e)
            return None
        self._record(rnd.decl_name, "PS_COMPRESS_DEV", pskey, t0,
                     step=rnd.step_tag)
        # honest D2H accounting: a leaf SHARED with a host bucket
        # crosses PCIe dense anyway (it is in host_leaves), so this
        # bucket's segments on such leaves saved nothing — count their
        # dense bytes on top of the payload, or the bench's d2h ratio
        # would report a saving that never physically happened
        if rnd.host_leaves:
            item = np.dtype(b.dtype).itemsize
            d2h += sum(s.length * item for s in b.segments
                       if s.leaf_index in rnd.host_leaves)
        self._d2h_account(pskey, d2h)
        self._m_push_bytes.inc(len(payload))
        try:
            self._routed(rnd, lambda epoch:
                         self.backend.push_fused(pskey, payload,
                                                 epoch=epoch)
                         if epoch is not None
                         else self.backend.push_fused(pskey, payload))
        except Exception as e:
            # mirror push_one's host-path handler: the round counter
            # advanced but the push never landed — drop the entry so a
            # retried exchange() re-seeds from the server's round
            # instead of pulling a round that will never complete
            flight.record("push", key=pskey, round=rnd.rounds[idx],
                          nbytes=len(payload), stage="PS_COMPRESS_DEV",
                          outcome=f"error:{type(e).__name__}")
            with self._key_rounds_lock:
                self._key_rounds.pop(pskey, None)
            raise
        flight.record("push", key=pskey, round=rnd.rounds[idx],
                      nbytes=len(payload), stage="PS_COMPRESS_DEV")
        # pull staging buffer (the fused pull path decodes into its own
        # array; np.empty is malloc-only)
        return np.empty(b.size, dtype=b.dtype)

    def _push_bucket(self, pskey, b, buf, rnd=None, idx=None) -> None:
        # flight-recorder envelope: one event per wire push with its
        # outcome — the postmortem's raw material (obs/flight.py)
        rnd_num = (rnd.rounds[idx]
                   if rnd is not None and idx is not None else None)
        try:
            self._push_bucket_impl(pskey, b, buf, rnd=rnd, idx=idx)
        except BaseException as e:   # noqa: BLE001 — re-raised
            flight.record("push", key=pskey, round=rnd_num,
                          nbytes=buf.nbytes,
                          outcome=f"error:{type(e).__name__}")
            raise
        flight.record("push", key=pskey, round=rnd_num,
                      nbytes=buf.nbytes)

    def _push_bucket_impl(self, pskey, b, buf, rnd=None, idx=None) -> None:
        chain = self._chains.get(pskey)
        if chain is not None:
            # legacy COMPRESS stage right before PUSH (reference:
            # core_loops.cc:498-536): wire bytes are compressed; the
            # server decompresses, dense-sums, recompresses the merge
            payload = chain.compress(buf)
            self._m_push_bytes.inc(len(payload))
            self.backend.push_bytes(pskey, payload)
            return
        plane = self._cplane
        if plane is not None and plane.active(pskey):
            import time
            round_tag = (rnd.rounds[idx]
                         if rnd is not None and idx is not None else 0)
            level = self._round_level(rnd, idx)
            if level:
                # fused PS_COMPRESS stage, on the pack worker the
                # moment the bucket's last leaf landed — EF residual
                # folded in, new residual staged for commit-on-pull.
                # (level > 0 implies a live rnd: levels come from the
                # round's pinned trace, so _record is always valid.)
                t0 = time.time()
                payload = plane.encode(pskey, buf, level, round_tag)
                self._record(rnd.decl_name, "PS_COMPRESS", pskey,
                             t0, step=rnd.step_tag)
                self._m_push_bytes.inc(len(payload))
                self._routed(rnd, lambda epoch:
                             self.backend.push_fused(pskey, payload,
                                                     epoch=epoch)
                             if epoch is not None
                             else self.backend.push_fused(pskey,
                                                          payload))
                return
            # dense round of a plane-managed key: per-layer byte
            # accounting keeps the controller's wire-load signal live
            # at level none (which is when up-ratchets consult it),
            # and any accumulated EF residual from a decayed level is
            # flushed into this dense round once
            plane.note_dense_push(pskey, buf.nbytes)
            buf = plane.fold_residual(pskey, buf, round_tag)
        self._m_push_bytes.inc(buf.nbytes)
        if (rnd is not None and idx is not None
                and self._lag_routes(pskey)):
            # versioned-round push: the server folds it into round
            # rounds[idx] (or the open round, if that one already
            # sealed without us — the late-fold contract)
            self.backend.push_lag(pskey, self.plane.worker_id,
                                  rnd.rounds[idx], buf)
            return
        self._routed(rnd, lambda epoch:
                     self.backend.push(pskey, buf, epoch=epoch)
                     if epoch is not None
                     else self.backend.push(pskey, buf))

    def _pull_layer_inc(self, pskey: int, n: int) -> None:
        m = self._pull_layer.get(pskey)
        if m is not None:
            m.inc(n)

    def _pull_bucket(self, pskey, b, buf, rnd_num, rnd=None, idx=None):
        try:
            out = self._pull_bucket_impl(pskey, b, buf, rnd_num,
                                         rnd=rnd, idx=idx)
        except BaseException as e:   # noqa: BLE001 — re-raised
            flight.record("pull", key=pskey, round=rnd_num,
                          outcome=f"error:{type(e).__name__}")
            from ..compress.wire import CodecError
            if isinstance(e, CodecError):
                # a refused decode is a peer/config divergence, not a
                # stall: dump the key's recent codec decisions and
                # rounds alongside the loud refusal
                from ..common.logging import get_logger
                flight.dump(get_logger(), keys=[pskey],
                            reason=f"CodecError on pull key={pskey} "
                                   f"round={rnd_num}: {e}")
            raise
        flight.record("pull", key=pskey, round=rnd_num,
                      nbytes=buf.nbytes)
        return out

    def _pull_bucket_impl(self, pskey, b, buf, rnd_num, rnd=None,
                          idx=None):
        chain = self._chains.get(pskey)
        if chain is not None:
            payload = self.backend.pull_bytes(pskey, round=rnd_num)
            self._m_pull_bytes.inc(len(payload))
            self._pull_layer_inc(pskey, len(payload))
            return chain.decompress(payload).astype(b.dtype)
        plane = self._cplane
        if plane is not None and plane.active(pskey):
            level = self._round_level(rnd, idx)
            if level:
                import time
                nbytes = b.size * np.dtype(b.dtype).itemsize
                div = plane.topk_div
                payload = self._routed(rnd, lambda epoch:
                                       self.backend.pull_fused(
                                           pskey, nbytes, str(b.dtype),
                                           level, round=rnd_num,
                                           epoch=epoch, div=div)
                                       if epoch is not None
                                       else self.backend.pull_fused(
                                           pskey, nbytes, str(b.dtype),
                                           level, round=rnd_num,
                                           div=div))
                self._m_pull_bytes.inc(len(payload))
                self._pull_layer_inc(pskey, len(payload))
                # PS_DECOMPRESS on the pull → H2D path feeding the
                # chunked apply; commits the round's EF residual.
                # (level > 0 implies a live rnd, as in _push_bucket.)
                t0 = time.time()
                merged = plane.decode(pskey, payload, rnd_num)
                self._record(rnd.decl_name, "PS_DECOMPRESS", pskey,
                             t0, step=rnd.step_tag)
                return merged
        if rnd_num and self._lag_routes(pskey):
            flags = self.backend.pull_lag(pskey, self.plane.worker_id,
                                          rnd_num, buf)
            self._lag_verdict(pskey, rnd_num, flags)
            self._m_pull_bytes.inc(buf.nbytes)
            self._pull_layer_inc(pskey, buf.nbytes)
            return buf
        self._routed(rnd, lambda epoch:
                     self.backend.pull(pskey, buf, round=rnd_num,
                                       epoch=epoch)
                     if epoch is not None
                     else self.backend.pull(pskey, buf, round=rnd_num))
        self._m_pull_bytes.inc(buf.nbytes)
        self._pull_layer_inc(pskey, buf.nbytes)
        if plane is not None:
            # dense round of a plane-managed key: still commit (a
            # residual flush pinned to this round clears on its pull)
            plane.commit(pskey, rnd_num)
        return buf

    def exchange(self, tree, name: Optional[str] = None):
        """One sync round (PER-KEY round counters, server-seeded on
        first use — see _next_round): every bucket is packed, pushed,
        and pulled, pipelined per bucket in priority order (see class
        docstring). Returns the summed tree."""
        return self._exchange_impl(tree, name, detach=False)

    def completed_rounds(self) -> int:
        """Rounds this exchange has COMPLETED — the max per-key round
        counter (0 before any exchange). After a rejoin the counters
        were seeded from the server, so a restarted worker reads how
        far the JOB is, not how far this process got: the fleet
        supervisor's restart path derives "steps remaining" from this
        (docs/launcher.md)."""
        with self._key_rounds_lock:
            return max(self._key_rounds.values(), default=0)

    def exchange_async(self, tree, name: Optional[str] = None):
        """Like ``exchange`` but returns as soon as every bucket's PUSH
        is submitted to the pipeline executors; call ``.result()`` on
        the returned handle to drain the pulls and get the summed tree.

        The contract callers rely on (torch _Dispatcher): this worker's
        pushes reach the wire without waiting for any pull, so a peer's
        round can always complete — a caller holding a scheduling slot
        through a blocking pull cannot deadlock the exchange the way a
        monolithic push+pull call can (two workers' slot pools wedged
        on disjoint key sets; the reference avoids the same geometry
        with free-running separate push/pull loops,
        core_loops.cc:538-618)."""
        return self._exchange_impl(tree, name, detach=True)

    def exchange_stream(self, tree, name: Optional[str] = None,
                        sharded=None):
        """Streaming sync round: returns a ``_StreamingExchange`` whose
        ``ready()`` iterator yields each leaf the moment its last
        covering bucket's pull unpacks. This makes leaf completion
        first-class: the trainer overlaps H2D upload and the chunked
        optimizer apply with still-in-flight pulls of later buckets —
        the step-tail analogue of the reference's free-running pull loop
        feeding the framework as partitions land (operations.cc:140-180).

        ``sharded``: a ``sharded_update`` round view — push every
        bucket, pull only the owned ones, stream only owned leaves."""
        return self._exchange_impl(tree, name, detach=True, stream=True,
                                   sharded=sharded)

    def exchange_ingest(self, template, name: Optional[str] = None,
                        step: Optional[int] = None, sharded=None):
        """Incremental-ingest sync round — the step-HEAD mirror of
        ``exchange_stream``. ``template`` is any tree with the grads'
        structure/shapes/dtypes (typically the param tree; no values
        are read from it). Returns an ``_IngestExchange``: the caller
        ``feed``s leaves group-by-group as the staged backward
        materializes them, each bucket's ``copy_to_host_async`` → pack
        → push fires the moment its last covering leaf arrives (instead
        of requiring the full tree up front), and pulls chase pushes so
        ``ready()``/``result()`` stream exactly like
        ``exchange_stream``. With PR 1's streamed tail this closes the
        full pipeline: bwd(group k+1) ∥ D2H/push(group k) ∥ server-sum
        ∥ pull/H2D/apply."""
        self._ensure_executors()
        self._ensure_watchdog()
        return _IngestExchange(_Round(self, template, name,
                                      stream=True, ingest=True,
                                      step=step, sharded=sharded))

    def _ensure_executors(self) -> None:
        # Creation is locked: the multi-channel torch dispatcher reaches
        # here concurrently, and a double-created pair would orphan
        # threads close() never shuts down
        with self._ex_lock:
            if self._push_ex is None:
                width = max(2, self.pipeline_depth)
                self._push_ex = ThreadPoolExecutor(
                    width, thread_name_prefix="bps-ps-push")
                self._pull_ex = ThreadPoolExecutor(
                    width, thread_name_prefix="bps-ps-pull")

    def _exchange_impl(self, tree, name: Optional[str], detach: bool,
                       stream: bool = False, sharded=None):
        self._ensure_watchdog()
        rnd = _Round(self, tree, name, stream=stream, sharded=sharded)
        for li, l in enumerate(rnd.sources):   # start ALL D2H copies first so
            if hasattr(l, "copy_to_host_async") and (   # transfers overlap
                    rnd.host_leaves is None or li in rnd.host_leaves):
                l.copy_to_host_async()   # device-encoded-only leaves skip —
                #                          their payload IS the D2H

        if not detach and not stream and (self.pipeline_depth <= 1
                                          or len(rnd.keyed) == 1):
            # serial: push everything (the server sums as they land),
            # then drain pulls in the same order
            bufs = [rnd.push_one(i) for i in range(len(rnd.keyed))]
            for i, buf in enumerate(bufs):
                rnd.pull_one(i, buf)
            return rnd.assemble()
        # pipelined (always, for the detached form: its no-deadlock
        # contract needs pushes on executor threads, not the caller's)
        self._ensure_executors()
        for i in range(len(rnd.keyed)):
            rnd.submit_bucket(i)
        if stream:
            return _StreamingExchange(rnd)
        if not detach:
            return rnd.drain()
        return _PendingExchange(rnd.drain)


class AsyncPSWorker:
    """Async-PS training worker: local step + weight-delta push + fresh
    weight pull, no inter-worker barrier.

    ``BPS_ASYNC_WIRE_DTYPE`` (e.g. ``bfloat16``) narrows the DELTA wire
    format: pushes cross the wire at half the bytes and the transport
    (or HostPSBackend) upcasts into the full-precision store. Deltas
    tolerate the rounding (one step's worth of error, folded into a
    fp32 accumulator); the weight PULL stays at store precision by
    default — set ``BPS_ASYNC_PULL_DTYPE`` too only if the model
    tolerates lossy weights."""

    def __init__(self, backend: HostPSBackend, params, name: str = "model",
                 init_store: bool = True,
                 registry: Optional[NameRegistry] = None) -> None:
        import os as _os
        self.backend = backend
        self.wire_dtype = _os.environ.get("BPS_ASYNC_WIRE_DTYPE") or None
        self.pull_dtype = _os.environ.get("BPS_ASYNC_PULL_DTYPE") or None
        if self.wire_dtype is not None:
            np.dtype(self.wire_dtype)     # fail fast on a typo
        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        self.shapes = [l.shape for l in leaves]
        self.dtypes = [str(np.dtype(l.dtype)) for l in leaves]
        self.sizes = [int(np.prod(l.shape)) for l in leaves]
        if registry is not None:
            # registry-assigned key space (declared_key<<16 | i) so several
            # async workers / other declared tensors never collide on PS
            # keys; the legacy bare range stays for single-model scripts
            decl = registry.declare(name)    # idempotent per name
            self.keys = [decl.key_for_partition(i)
                         for i in range(len(leaves))]
        else:
            self.keys = list(range(len(leaves)))
        if init_store:
            for k, l in zip(self.keys, leaves):
                arr = np.ascontiguousarray(np.asarray(l).reshape(-1))
                self.backend.init_key(k, arr.nbytes, str(arr.dtype), init=arr)

    def pull_weights(self):
        outs = []
        for k, n, dt, shp in zip(self.keys, self.sizes, self.dtypes, self.shapes):
            buf = np.empty(n, dtype=self.pull_dtype or dt)
            self.backend.pull(k, buf)
            outs.append(buf.astype(dt).reshape(shp)
                        if self.pull_dtype else buf.reshape(shp))
        return jax.tree_util.tree_unflatten(self.treedef, outs)

    def _wire(self, arr: np.ndarray) -> np.ndarray:
        if self.wire_dtype and str(arr.dtype) != self.wire_dtype:
            arr = arr.astype(self.wire_dtype)
        return np.ascontiguousarray(arr)

    def push_delta(self, new_params, old_params):
        """Push w_new - w_old; the server accumulates deltas into the
        global weights (reference: async push of ``w - prev_w``)."""
        new_l = jax.tree_util.tree_leaves(new_params)
        old_l = jax.tree_util.tree_leaves(old_params)
        for k, nw, od in zip(self.keys, new_l, old_l):
            delta = np.asarray(nw).reshape(-1) - np.asarray(od).reshape(-1)
            self.backend.push(k, self._wire(delta))

    def push_delta_tree(self, delta):
        """Push pre-computed deltas (e.g. produced on-device inside the
        jitted step, so the subtraction — and the wire-dtype cast, see
        DistributedTrainer._delta_fn — fuses and only ONE narrow tree
        crosses D2H instead of two wide ones)."""
        for k, d in zip(self.keys, jax.tree_util.tree_leaves(delta)):
            if hasattr(d, "copy_to_host_async"):
                d.copy_to_host_async()
        for k, d in zip(self.keys, jax.tree_util.tree_leaves(delta)):
            self.backend.push(
                k, self._wire(np.asarray(d).reshape(-1)))


class RowSparseExchange:
    """Sync row-sparse exchange: push touched (idx, rows), pull the dense
    merged table (reference: reserved kRowSparsePushPull,
    common.h:267-271 — no handler existed there; here it is the PS
    path's native sparse mode, implemented for embedding-style grads)."""

    def __init__(self, backend: HostPSBackend,
                 registry: Optional[NameRegistry] = None) -> None:
        self.backend = backend
        self.registry = registry or NameRegistry()
        self._inited: Dict[int, tuple] = {}     # key -> (num_rows, cols)
        self._rounds: Dict[int, int] = {}

    def exchange(self, idx, rows, num_rows: int, name: str) -> np.ndarray:
        """One sync round; returns the dense [num_rows, cols] sum across
        workers. Distinct tables need distinct names (one PS key each)."""
        idx = np.asarray(idx, np.int32).reshape(-1)
        rows = np.asarray(rows)
        if rows.ndim != 2:
            raise ValueError(f"rows must be [n, cols]; got {rows.shape}")
        cols, dtype = rows.shape[1], str(rows.dtype)
        key = self.registry.declare(name).key_for_partition(0)
        dense_nbytes = num_rows * cols * rows.dtype.itemsize
        prev = self._inited.get(key)
        if prev is None:
            self.backend.init_key(key, dense_nbytes, dtype)
            self._inited[key] = (num_rows, cols)
        elif prev != (num_rows, cols):
            raise ValueError(f"table {name!r} was {prev}, now "
                             f"{(num_rows, cols)} — shape must be stable")
        rnd = self._rounds.get(key)
        if rnd is None:
            # server-seeded like the dense exchange: an elastically
            # rejoined worker resumes at the live job's round, not 1
            # (pulling round 1 would return a stale table immediately).
            # Read BEFORE pushing — our own push may complete the round.
            rnd = (int(self.backend.round(key))
                   if hasattr(self.backend, "round") else 0)
        rnd += 1
        self._rounds[key] = rnd
        self.backend.push_rowsparse(key, idx, rows, dense_nbytes, dtype)
        out = np.empty(num_rows * cols, rows.dtype)
        self.backend.pull(key, out, round=rnd)
        return out.reshape(num_rows, cols)

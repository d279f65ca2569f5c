"""Python bindings for the native host reduction service.

The reference loads its server as a ctypes CDLL from ``import
byteps.server`` (reference: server/__init__.py:21-27); we do the same for
``libbps_server.so`` (built from csrc/ via make — no pip/pybind needed).

``PSServer`` is the per-process server shard; ``HostPSBackend`` drives a
set of shards from the worker side, giving push_pull a PS route: device →
host numpy → sharded key stores (placement by the same key hash as the
reference, byteps_tpu.common.naming.place_key) → summation engine → pull →
device. This models the reference's CPU-server bandwidth story and powers
async-PS mode (weight-delta push / fresh-weight pull, no worker barrier;
reference: BYTEPS_ENABLE_ASYNC, torch/__init__.py:186-214).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Dict, Optional

import numpy as np

from ..obs.metrics import metrics_enabled

_DTYPES = {"float32": 0, "float64": 1, "int32": 2, "int64": 3,
           "float16": 4, "bfloat16": 5, "uint8": 6}

_LIB: Optional[ctypes.CDLL] = None


def _lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is not None:
        return _LIB
    here = os.path.join(os.path.dirname(__file__), "csrc")
    so = os.path.join(here, "libbps_server.so")
    # run make unconditionally (not just when the .so is missing): the
    # Makefile's source dependency decides whether to rebuild, so the
    # library loaded is always the one the committed sources build. A
    # failed build is an error — never a silent load of a stale binary.
    try:
        subprocess.run(["make", "-C", here], check=True,
                       capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building the native PS server failed (make -C {here}):\n"
            f"{e.stderr}") from e
    lib = ctypes.CDLL(so)
    lib.bps_server_create.restype = ctypes.c_void_p
    lib.bps_server_create.argtypes = [ctypes.c_int] * 4
    lib.bps_server_destroy.argtypes = [ctypes.c_void_p]
    lib.bps_server_begin_shutdown.argtypes = [ctypes.c_void_p]
    lib.bps_server_init_key.restype = ctypes.c_int
    lib.bps_server_init_key.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_void_p]
    lib.bps_server_push.restype = ctypes.c_int
    lib.bps_server_push.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64]
    lib.bps_server_pull.restype = ctypes.c_int
    lib.bps_server_pull.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_int]
    lib.bps_server_round.restype = ctypes.c_uint64
    lib.bps_server_round.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.bps_server_engine_load.restype = ctypes.c_uint64
    lib.bps_server_engine_load.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.bps_server_key_thread.restype = ctypes.c_int
    lib.bps_server_key_thread.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.bps_reduce_sum.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int]
    lib.bps_server_push_onebit.restype = ctypes.c_int
    lib.bps_server_push_onebit.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64]
    lib.bps_server_pull_onebit.restype = ctypes.c_int
    lib.bps_server_pull_onebit.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_int, ctypes.c_int]
    lib.bps_server_push_topk.restype = ctypes.c_int
    lib.bps_server_push_topk.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64]
    lib.bps_server_pull_topk.restype = ctypes.c_int
    lib.bps_server_pull_topk.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_int]
    # standalone codec primitives (round 4): chain state stays in
    # Python, O(n) loops run here — see host.py's _native routing
    lib.bps_codec_onebit_decompress.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p]
    lib.bps_codec_topk_select.restype = ctypes.c_int
    lib.bps_codec_topk_select.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_void_p, ctypes.c_void_p]
    lib.bps_codec_scatter_f32.restype = ctypes.c_int
    lib.bps_codec_scatter_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_void_p]
    lib.bps_codec_xorshift_indices.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p]
    lib.bps_codec_dithering_compress.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_float, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p]
    lib.bps_pack_segments.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.c_void_p]
    lib.bps_unpack_segments.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_uint64]
    _LIB = lib
    return lib


def pack_segments(srcs, dst_offs, lens, dst: np.ndarray) -> None:
    """Gather ``len(srcs)`` byte ranges into ``dst`` natively (GIL
    released, OMP across segments). ``srcs``: raw source addresses;
    offsets/lengths in bytes."""
    n = len(srcs)
    _lib().bps_pack_segments(
        (ctypes.c_void_p * n)(*srcs),
        (ctypes.c_uint64 * n)(*dst_offs),
        (ctypes.c_uint64 * n)(*lens),
        n, dst.ctypes.data_as(ctypes.c_void_p))


def unpack_segments(src: np.ndarray, src_offs, dsts, lens) -> None:
    """Scatter byte ranges of ``src`` to raw destination addresses."""
    n = len(dsts)
    _lib().bps_unpack_segments(
        src.ctypes.data_as(ctypes.c_void_p),
        (ctypes.c_uint64 * n)(*src_offs),
        (ctypes.c_void_p * n)(*dsts),
        (ctypes.c_uint64 * n)(*lens), n)


def reduce_sum_inplace(dst: np.ndarray, src: np.ndarray) -> None:
    """dst += src via the native typed reducer (reference: CpuReducer::sum)."""
    assert dst.dtype == src.dtype and dst.nbytes == src.nbytes
    dt = _DTYPES[str(dst.dtype)]
    _lib().bps_reduce_sum(dst.ctypes.data_as(ctypes.c_void_p),
                          src.ctypes.data_as(ctypes.c_void_p),
                          dst.nbytes, dt)


class ServerClosed(RuntimeError):
    """The server is shutting down — transient from a client's view (a
    supervisor may restart it); the transport maps this to a GONE frame
    so workers reconnect instead of failing."""


class PSServer:
    """One native server shard (reference: byteps_server(), server.cc:441-514)."""

    def __init__(self, num_workers: int, engine_threads: int = 4,
                 enable_schedule: bool = False, async_mode: bool = False):
        import threading
        self._lib = _lib()
        self._h = self._lib.bps_server_create(
            num_workers, engine_threads, int(enable_schedule), int(async_mode))
        if not self._h:
            raise RuntimeError("bps_server_create failed")
        self.num_workers = num_workers
        self.engine_threads = engine_threads
        self.async_mode = async_mode
        # close() may race concurrent callers (transport handler threads
        # blocked in pull): a Python-side inflight count plus the native
        # two-phase shutdown (begin_shutdown wakes + refuses, destroy
        # frees only after the drain) makes close() safe under load
        self._cv = threading.Condition()
        self._inflight = 0
        self._closed = False
        self._key_dtypes: dict = {}   # key -> store dtype str (transcode)

    def _enter(self):
        with self._cv:
            if self._closed:
                raise ServerClosed("server closed")
            self._inflight += 1

    def _exit(self):
        with self._cv:
            self._inflight -= 1
            if self._inflight == 0:
                self._cv.notify_all()

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            h = self._h
        if h:
            # wake blocked pulls (they return rc=-5), then wait for every
            # in-flight ctypes call to leave before freeing the handle
            self._lib.bps_server_begin_shutdown(h)
            with self._cv:
                while self._inflight:
                    self._cv.wait(timeout=1.0)
            self._lib.bps_server_destroy(h)
            self._h = None

    def __del__(self):  # noqa: D105
        try:
            self.close()
        except Exception:
            pass

    def init_key(self, key: int, nbytes: int, dtype: str = "float32",
                 init: Optional[np.ndarray] = None) -> None:
        ptr = init.ctypes.data_as(ctypes.c_void_p) if init is not None else None
        self._enter()
        try:
            rc = self._lib.bps_server_init_key(self._h, key, nbytes,
                                               _DTYPES[dtype], ptr)
        finally:
            self._exit()
        if rc == -5:
            raise ServerClosed(f"init_key({key}): server shutting down")
        if rc != 0:
            raise RuntimeError(f"init_key({key}) failed rc={rc}")
        self._key_dtypes[key] = dtype

    def push(self, key: int, data: np.ndarray) -> None:
        # in-process transcode mirror of the transport server's wire
        # transcode (narrow async-delta pushes land in a full-precision
        # store); no bandwidth at stake here, just uniform semantics
        store = self._key_dtypes.get(key)
        if store is not None and str(data.dtype) != store:
            data = data.astype(store)
        data = np.ascontiguousarray(data)
        self._enter()
        try:
            rc = self._lib.bps_server_push(
                self._h, key, data.ctypes.data_as(ctypes.c_void_p),
                data.nbytes)
        finally:
            self._exit()
        if rc == -5:
            raise ServerClosed(f"push({key}): server shutting down")
        if rc != 0:
            raise RuntimeError(f"push({key}) failed rc={rc} "
                               f"(len mismatch or key not initialised)")

    def pull(self, key: int, out: np.ndarray, round: int = 0,
             timeout_ms: int = 30000) -> None:
        """Pull round ``round`` (1-based; 0 = latest published). Sync-mode
        callers should pass the round their push contributed to."""
        store = self._key_dtypes.get(key)
        if store is not None and str(out.dtype) != store:
            tmp = np.empty(out.size, dtype=store)
            self.pull(key, tmp, round=round, timeout_ms=timeout_ms)
            np.copyto(out, tmp.astype(out.dtype).reshape(out.shape))
            return
        self._enter()
        try:
            rc = self._lib.bps_server_pull(
                self._h, key, out.ctypes.data_as(ctypes.c_void_p),
                out.nbytes, round, timeout_ms)
        finally:
            self._exit()
        if rc == -2:
            raise TimeoutError(f"pull({key}) round={round} timed out "
                               f"after {timeout_ms}ms")
        if rc == -5:
            raise ServerClosed(f"pull({key}): server shutting down")
        if rc != 0:
            raise RuntimeError(f"pull({key}) failed rc={rc}")

    def push_onebit(self, key: int, payload) -> None:
        """Fused native decompress→enqueue of a onebit payload (fp32
        stores; reference: server.cc:86-113 decompress-before-SUM_RECV
        inside the C++ engine). The ctypes call releases the GIL, so
        concurrent workers' payloads decode in parallel."""
        buf = np.frombuffer(bytes(payload), np.uint8)
        self._enter()
        try:
            rc = self._lib.bps_server_push_onebit(
                self._h, key, buf.ctypes.data_as(ctypes.c_void_p),
                buf.nbytes)
        finally:
            self._exit()
        if rc == -5:
            raise ServerClosed(f"push_onebit({key}): server shutting down")
        if rc != 0:
            raise RuntimeError(f"push_onebit({key}) failed rc={rc} "
                               f"(bad payload length or non-fp32 key)")

    def pull_onebit(self, key: int, payload_nbytes: int, round: int = 0,
                    timeout_ms: int = 30000,
                    use_scale: bool = False) -> bytes:
        """Native merged-round pull + onebit recompress in one call."""
        out = np.empty(payload_nbytes, np.uint8)
        self._enter()
        try:
            rc = self._lib.bps_server_pull_onebit(
                self._h, key, out.ctypes.data_as(ctypes.c_void_p),
                out.nbytes, round, timeout_ms, int(use_scale))
        finally:
            self._exit()
        if rc == -2:
            raise TimeoutError(f"pull_onebit({key}) round={round} timed "
                               f"out after {timeout_ms}ms")
        if rc == -5:
            raise ServerClosed(f"pull_onebit({key}): server shutting down")
        if rc != 0:
            raise RuntimeError(f"pull_onebit({key}) failed rc={rc}")
        return out.tobytes()

    def push_topk(self, key: int, payload) -> None:
        """Fused native scatter→enqueue of a topk payload (k int32
        indices + k fp32 values; duplicate indices are LAST-WINS,
        matching the Python scatter ``out[idx] = vals``)."""
        buf = np.frombuffer(bytes(payload), np.uint8)
        self._enter()
        try:
            rc = self._lib.bps_server_push_topk(
                self._h, key, buf.ctypes.data_as(ctypes.c_void_p),
                buf.nbytes)
        finally:
            self._exit()
        if rc == -5:
            raise ServerClosed(f"push_topk({key}): server shutting down")
        if rc != 0:
            raise RuntimeError(f"push_topk({key}) failed rc={rc} "
                               f"(bad payload or non-fp32 key)")

    def pull_topk(self, key: int, payload_nbytes: int, round: int = 0,
                  timeout_ms: int = 30000) -> bytes:
        """Native merged-round pull + top-k reselection (largest |x|,
        ties to the lower index — matches HostTopk)."""
        out = np.empty(payload_nbytes, np.uint8)
        self._enter()
        try:
            rc = self._lib.bps_server_pull_topk(
                self._h, key, out.ctypes.data_as(ctypes.c_void_p),
                out.nbytes, round, timeout_ms)
        finally:
            self._exit()
        if rc == -2:
            raise TimeoutError(f"pull_topk({key}) round={round} timed "
                               f"out after {timeout_ms}ms")
        if rc == -5:
            raise ServerClosed(f"pull_topk({key}): server shutting down")
        if rc != 0:
            raise RuntimeError(f"pull_topk({key}) failed rc={rc}")
        return out.tobytes()

    def round(self, key: int) -> int:
        self._enter()
        try:
            return self._lib.bps_server_round(self._h, key)
        finally:
            self._exit()

    def engine_load(self, tid: int) -> int:
        self._enter()
        try:
            return self._lib.bps_server_engine_load(self._h, tid)
        finally:
            self._exit()

    def queue_depth(self) -> int:
        """Total enqueued-but-unsummed pushes across the engine's sticky
        per-key threads — the server-side backlog gauge."""
        return sum(self.engine_load(t) for t in range(self.engine_threads))

    def key_thread(self, key: int) -> int:
        self._enter()
        try:
            return self._lib.bps_server_key_thread(self._h, key)
        finally:
            self._exit()


class HostPSBackend:
    """Worker-side driver over sharded PSServer instances.

    Keys are placed on shards by hash (reference: global.cc:628-677) via
    ``place_key``. In-process shards model the colocated-server deployment
    (reference: BYTEPS_ENABLE_IPC best-practice); the data path and engine
    are identical for a networked deployment.
    """

    def __init__(self, num_servers: int = 1, num_workers: int = 1,
                 engine_threads: int = 4, enable_schedule: bool = False,
                 async_mode: bool = False, hash_fn: str = "djb2"):
        self.servers = [PSServer(num_workers, engine_threads, enable_schedule,
                                 async_mode)
                        for _ in range(num_servers)]
        self.num_workers = num_workers
        # homogeneous fused summation (server/homog.py): keys declared
        # ``fused=True`` at init have their ROUNDS owned by this store
        # — same-codec arrivals merge in one widen->add pass and pulls
        # are served as payload bytes, no dense decode through the
        # engine. Lazy: plain deployments never allocate it.
        self._homog = None
        # bounded-staleness round store (server/admission.StaleStore):
        # keys declared via declare_lag have their rounds versioned and
        # served under the K-lag contract instead of the native
        # complete-count engine. Lazy like _homog: K=1 deployments
        # never allocate it and stay bit-identical.
        self._stale = None
        self.hash_fn = hash_fn
        from ..common.naming import check_mixed_mode_enabled, placement_from_env
        check_mixed_mode_enabled(hash_fn)
        self._placement = placement_from_env()
        # hash_fn="ring": placement comes from the server plane's
        # byte-weighted consistent-hash service instead of the env hash
        # — balanced by construction (max−min assigned bytes bounded by
        # one key), deterministic across workers under the exchange's
        # declaration-order contract. The env hashes stay for
        # reference-parity deployments.
        self._ring = None
        if hash_fn == "ring" and num_servers > 1:
            from .plane.placement import DEFAULT_VNODES, PlacementService
            self._ring = PlacementService(
                num_servers,
                vnodes=int(self._placement.get("vnodes") or 0)
                or DEFAULT_VNODES)
        self.async_mode = async_mode
        self._rounds: Dict[int, int] = {}
        self._shard_bytes: Dict[int, int] = {}
        # key -> shard override from migrate_key (hash placements have
        # no routing table to rewrite, so moves live here); ring
        # placements rewrite the PlacementService table instead
        self._migrated: Dict[int, int] = {}
        self._key_meta: Dict[int, tuple] = {}    # key -> (nbytes, dtype)
        # plane round = shard-local round + base after a migration (the
        # new shard's store counts from 0)
        self._round_base: Dict[int, int] = {}
        self._placed: set = set()
        self._rs_cols: Dict[int, int] = {}   # row-sparse: pinned cols/key
        from .compressed import CompressedKeyStore
        self.compressed = CompressedKeyStore()
        # fused-plane pull cache (byteps_tpu.compress), created on first
        # fused pull so plain deployments never pay the import
        self._fused_cache = None
        # param mailbox (sharded weight update): one in-process store —
        # worker threads sharing this backend share it, mirroring the
        # transport server's param_store(); lazy, plain deployments
        # never allocate it
        import threading
        self._param_store = None
        self._param_lock = threading.Lock()
        from ..obs.metrics import get_registry
        self._m_pull_wait = get_registry().histogram("server/pull_wait_s")
        self._m_queue_depth = get_registry().gauge(
            "server/engine_queue_depth")
        # unmanaged fused pushes dense-decode per call: cache the
        # counter off the per-bucket hot path (homog.FusedSumStore does
        # the same for its own counters)
        self._m_dense_decodes = get_registry().counter(
            "server/fused_dense_decodes")
        self._qd_next_sample = 0.0
        import time as _time
        self._t0_mono = _time.monotonic()   # heartbeat base for stats()
        # causal span ring (obs/spans.py): per-(key, round) arrival +
        # serve records for the critical-path analyzer. In-process
        # callers carry no dedup token, so the worker id is 0; a
        # fronting PSTransportServer reuses THIS ring (and skips its
        # own recording) so colocated rigs never double-count.
        from ..obs.spans import ServerSpanRing
        self.spans = ServerSpanRing(num_workers=num_workers)

    def close(self) -> None:
        for s in self.servers:
            s.close()

    def _shard_index(self, key: int) -> int:
        s = self._migrated.get(key)
        if s is not None:
            return s
        if self._ring is not None:
            try:
                return self._ring.shard_of(key)
            except KeyError:
                # op before init_key (raw clients' round probes): route
                # to the ring primary WITHOUT recording an assignment —
                # place(key, 0) here would pin the key at weight zero
                # forever (place is idempotent), silently breaking the
                # byte-weighted balance and, worse, diverging this
                # worker's placement sequence from peers that never hit
                # this path. init_key does the real byte-weighted place.
                return self._ring.ring.lookup(key)
        from ..common.naming import place_key
        return place_key(key, len(self.servers), self.hash_fn,
                         **self._placement)

    def _shard(self, key: int) -> PSServer:
        return self.servers[self._shard_index(key)]

    def _homog_store(self):
        if self._homog is None:
            from .homog import FusedSumStore
            self._homog = FusedSumStore(self.num_workers)
        return self._homog

    def _homog_managed(self, key: int) -> bool:
        return self._homog is not None and self._homog.managed(key)

    def init_key(self, key: int, nbytes: int, dtype: str = "float32",
                 init: Optional[np.ndarray] = None,
                 compression: Optional[Dict[str, str]] = None,
                 fused: bool = False) -> None:
        """``compression`` kwargs register a server-side codec for the key
        (reference: server.cc:222-252); the dense store still holds
        ``nbytes`` — pushes arrive compressed, are decompressed into it.
        ``fused=True`` (the exchange's plan-time declaration for
        compression-plane-managed keys) hands the key's rounds to the
        homogeneous fused store — same-codec rounds merge decode-free
        and pulls are served as payload bytes (server/homog.py); a
        re-init resets the store (new tenancy), exactly like the fused
        pull cache."""
        if compression:
            size = nbytes // np.dtype(dtype).itemsize
            self.compressed.register(key, compression, size, dtype)
        from .homog import homog_enabled
        if fused and homog_enabled():
            self._homog_store().init_key(key, nbytes, dtype, init)
        elif self._homog_managed(key):
            self._homog.drop(key)     # re-declared non-fused
        # a (re-)init is a new tenancy: shard-local rounds restart, so
        # cached fused pulls from the previous tenancy would alias the
        # recurring round numbers (the transport server applies the
        # same rule to its own cache)
        if self._fused_cache is not None:
            self._fused_cache.drop(key)
        if self._ring is not None:
            self._ring.place(key, nbytes)    # byte-weighted, idempotent
        self._shard(key).init_key(key, nbytes, dtype, init)
        # init copy kept for migrate_key's round-0 replay (a fresh key
        # moved before any round completes must carry its init, not
        # zero-fill the destination)
        self._key_meta.setdefault(
            key, (int(nbytes), dtype,
                  None if init is None else np.array(init)))
        if key not in self._placed:      # re-inits are no-ops server-side;
            self._placed.add(key)        # don't double-count the load stats
            from ..common.naming import log_key_placement
            log_key_placement(key, nbytes, self._shard_index(key),
                              self._shard_bytes, self.hash_fn)
            # one shared publisher with the plane: the rebalancer and
            # the watchdog read the same plane/shard_bytes gauges
            # whichever backend is in play
            from .plane.placement import publish_shard_bytes
            publish_shard_bytes(dict(self._shard_bytes))

    def push(self, key: int, data: np.ndarray) -> None:
        import time
        if self._homog_managed(key):
            # dense round of a fused-managed key (level none, or a
            # divergent worker's dense arrival): the homog store owns
            # the round either way — splitting one key's rounds across
            # two stores would wedge the next pull
            self._homog.ingest_dense(key, data)
        else:
            self._shard(key).push(key, data)
        self.spans.note_arrival(key, 0, data.nbytes)
        # server-side backlog: how far the summation engine is behind
        # the pushes (the reference's engine_load). RATE-LIMITED — the
        # sample is engine_threads locked ctypes calls per shard, and a
        # per-push cadence measurably taxed small-step pipelines
        if metrics_enabled():
            now = time.time()
            if now >= self._qd_next_sample:
                self._qd_next_sample = now + 0.05
                try:
                    self._m_queue_depth.set(self.queue_depth())
                except Exception:   # noqa: BLE001 — the push LANDED; a
                    pass            # metrics read racing close() must
                    #                 not fail the data plane after it

    def queue_depth(self) -> int:
        """Enqueued-but-unsummed pushes across every shard's engine,
        plus the fused store's buffered arrivals — the backlog signal
        the compression controller reads must keep tracking managed
        keys after their rounds leave the engine."""
        n = sum(s.queue_depth() for s in self.servers)
        if self._homog is not None:
            n += self._homog.pending()
        return n

    def stats(self, timeout_ms: int = 0) -> Dict[str, dict]:
        """In-process form of the fleet stats surface (the shared
        ServerStats/v1 shape, obs/fleet.py — one entry per shard):
        here the "server registry" IS this process's registry, so the
        snapshot is shared across shards and only the per-shard engine
        backlog differs. Keeps FleetScraper / bench / exporter code
        backend-agnostic."""
        import time as _time

        from ..obs.fleet import server_stats_payload
        up = _time.monotonic() - self._t0_mono
        out: Dict[str, dict] = {}
        for i, s in enumerate(self.servers):
            def qd(s=s, i=i):
                n = s.queue_depth()
                if i == 0 and self._homog is not None:
                    n += self._homog.pending()   # fold buffered fused
                return n                         # arrivals once
            out[f"s{i}"] = server_stats_payload(
                up, len(self._key_meta), queue_depth_fn=qd)
        return out

    def trace(self, timeout_ms: int = 0) -> Dict[str, dict]:
        """In-process form of the causal trace scrape (one shared ring
        across shards — see ``spans``): the shape ``RemotePSBackend
        .trace()`` returns, with a zero-width roundtrip (same process,
        same clock — offset estimates to ~0 by construction)."""
        import time as _time
        now = _time.time()
        return {"s0": {"payload": self.spans.payload(now=now),
                       "t_send": now, "t_recv": now}}

    def pull(self, key: int, out: np.ndarray, round: int = 0,
             timeout_ms: int = 30000) -> None:
        import time
        if self._homog_managed(key):
            t0 = time.time()
            self._homog.pull_dense(key, out, round, timeout_ms)
            self._m_pull_wait.observe(time.time() - t0)
            self.spans.note_serve(key, round, t0, time.time() - t0)
            return
        t0 = time.time()
        base = self._round_base.get(key, 0)
        if round and round <= base:
            # the classic backend keeps no forward log (that is the
            # plane's job): a pre-migration round cannot be served —
            # round==base would silently alias to "latest published"
            # (shard round 0) and smaller rounds go negative
            raise ValueError(
                f"pull({key}) round={round}: rounds <= the migration "
                f"base ({base}) left with the old shard — only the "
                f"replicated plane retains them")
        self._shard(key).pull(key, out, (round - base) if round else 0,
                              timeout_ms)
        # how long the merge took to publish from this worker's view —
        # server sum time plus the wait for the other workers' pushes
        self._m_pull_wait.observe(time.time() - t0)
        self.spans.note_serve(key, round, t0, time.time() - t0)

    def round(self, key: int) -> int:
        """Latest COMPLETED sync round for ``key`` (0 = none yet) — lets
        a restarted worker of a live job resynchronize its round
        counters to the server's instead of stalling on round 1
        (the elastic-rejoin analog of the reference's is_recovery
        skip-barrier, global.cc:283-297). Migrated keys report
        ``base + shard round`` (the destination store counts from 0).
        Fused-managed keys answer from the homog store — its counter IS
        the key's round authority (in-process migration never moves it,
        so no base applies)."""
        if self._stale is not None and self._stale.managed(key):
            return self._stale.round(key)
        if self._homog_managed(key):
            return self._homog.round(key)
        return (self._round_base.get(key, 0)
                + int(self._shard(key).round(key)))

    # --------------------------------------- bounded staleness (K>1)

    def declare_lag(self, key: int, max_lag: int) -> None:
        """Hand ``key``'s rounds to the bounded-staleness store with
        bound ``max_lag`` (idempotent; conflicting K is a loud error).
        The key must be init_key'd first — the store snapshots its
        size/dtype from the declaration. The native engine keeps the
        key's dense store (async pulls, raw clients) but versioned
        rounds are served exclusively from the StaleStore."""
        meta = self._key_meta.get(key)
        if meta is None:
            raise KeyError(f"declare_lag({key}) before init_key")
        nbytes, dtype = meta[0], meta[1]
        if self._stale is None:
            from .admission import StaleStore
            self._stale = StaleStore(self.num_workers, spans=self.spans)
        self._stale.declare(key, nbytes // np.dtype(dtype).itemsize,
                            dtype, max_lag)

    def push_lag(self, key: int, worker: int, rnd: int,
                 data: np.ndarray) -> None:
        """Versioned-round push: fold ``worker``'s round-``rnd``
        gradient (or late-fold it into the open round — the arrival is
        recorded against the round it actually landed in, so the span
        ring's (key, round) joins stay truthful under sealing)."""
        tgt = self._stale.push(key, worker, rnd, data)
        self.spans.note_arrival(key, int(worker), data.nbytes, rnd=tgt)

    def pull_lag(self, key: int, worker: int, rnd: int,
                 out: np.ndarray, timeout_ms: int = 30000) -> int:
        """Versioned-round pull; returns the verdict flags
        (admission.LAG_COMPLETE / LAG_STALE / LAG_BARRIER)."""
        import time
        t0 = time.time()
        flags = self._stale.pull(key, worker, rnd, out, timeout_ms)
        dur = time.time() - t0
        self._m_pull_wait.observe(dur)
        self.spans.note_serve(key, rnd, t0, dur)
        return flags

    def migrate_key(self, key: int, dst: int) -> int:
        """Move ``key``'s store to shard ``dst`` at a round boundary:
        replay the latest merged state (or nothing, for a round-0 key)
        to the destination, re-base the round translation, and update
        the ``_shard_bytes`` accounting + ``plane/shard_bytes`` gauges
        so the rebalancer and the watchdog keep seeing truth. Callers
        must be at a round boundary for the key (no pushed-but-unpulled
        round — the plane backend's ``migrate_key`` enforces this; here
        the single-process trainer's step edges are the boundary).
        Returns the destination shard."""
        if not 0 <= dst < len(self.servers):
            raise ValueError(f"shard {dst} out of range "
                             f"0..{len(self.servers) - 1}")
        if self.compressed.has(key) or key in self._rs_cols:
            # the byte-path pulls (pull_bytes/onebit/topk) carry raw
            # plane rounds with no base translation — migrating such a
            # key would leave them waiting on rounds the destination
            # never published. Refuse until the byte paths learn the
            # re-basing the dense path does.
            raise ValueError(
                f"key {key} has a compressed/row-sparse codec — "
                f"migration is dense-path only")
        src = self._shard_index(key)
        if src == dst:
            return dst
        meta = self._key_meta.get(key)
        if meta is None:
            raise KeyError(f"key {key} was never init_key'd — nothing "
                           f"to migrate")
        nbytes, dtype, init = meta
        srv = self.servers[src]
        cr = int(srv.round(key))
        state = init                 # round-0 key: replay its init
        if cr > 0:
            state = np.empty(nbytes // np.dtype(dtype).itemsize,
                             dtype=dtype)
            srv.pull(key, state, round=cr, timeout_ms=5000)
        self.servers[dst].init_key(key, nbytes, dtype, state)
        self._round_base[key] = self._round_base.get(key, 0) + cr
        if self._ring is not None:
            self._ring.migrate(key, dst)     # epoch bump + its counter
        else:
            self._migrated[key] = dst
            from ..obs.metrics import get_registry
            get_registry().counter("plane/migrations").inc()
        self._shard_bytes[src] = self._shard_bytes.get(src, 0) - nbytes
        self._shard_bytes[dst] = self._shard_bytes.get(dst, 0) + nbytes
        from .plane.placement import publish_shard_bytes
        publish_shard_bytes(dict(self._shard_bytes))
        return dst

    def push_onebit(self, key: int, payload) -> None:
        """Native onebit push on the key's shard (see PSServer)."""
        self._shard(key).push_onebit(key, payload)
        # every codec path notes its arrival, or the ring's
        # count-derived rounds shear on keys that mix dense and
        # compressed rounds (the serve of round r would be joined
        # against an earlier round's arrivals)
        self.spans.note_arrival(key, 0, len(payload))

    def pull_onebit(self, key: int, payload_nbytes: int, round: int = 0,
                    timeout_ms: int = 30000,
                    use_scale: bool = False) -> bytes:
        return self._shard(key).pull_onebit(key, payload_nbytes, round,
                                            timeout_ms, use_scale)

    def push_topk(self, key: int, payload) -> None:
        """Native topk push on the key's shard (see PSServer)."""
        self._shard(key).push_topk(key, payload)
        self.spans.note_arrival(key, 0, len(payload))   # see push_onebit

    def pull_topk(self, key: int, payload_nbytes: int, round: int = 0,
                  timeout_ms: int = 30000) -> bytes:
        return self._shard(key).pull_topk(key, payload_nbytes, round,
                                          timeout_ms)

    def push_bytes(self, key: int, payload) -> None:
        """Compressed push: decompress server-side, dense-sum in the
        engine (reference: decompress before SUM_RECV, server.cc:86-113)."""
        from .compressed import compressed_push
        compressed_push(self.compressed, self._shard(key), key, payload)
        self.spans.note_arrival(key, 0, len(payload))   # see push_onebit

    def push_fused(self, key: int, payload) -> None:
        """Fused-plane push (byteps_tpu.compress): the payload is
        SELF-DESCRIBING (codec header). Managed keys buffer it in the
        homogeneous store — same-codec rounds merge in one widen->add
        pass, no dense decode through the engine; unmanaged keys keep
        the PR-7 decode-on-arrival dense sum (now counter-visible). A
        torn/mismatched payload raises CodecError loudly before any
        bytes reach either store."""
        from ..compress import wire
        if self._homog_managed(key):
            self._homog.ingest(key, payload)
            self.spans.note_arrival(key, 0, len(payload))
            return
        dense = wire.decode_for_store(payload, self._key_meta.get(key))
        if wire.lossy(wire.peek(payload)[0]):   # `none` frames are a
            self._m_dense_decodes.inc()         # frombuffer view, not
        self.push(key, dense)                   # a decode

    def pull_fused(self, key: int, nbytes: int, dtype: str, codec: int,
                   round: int = 0, timeout_ms: int = 30000,
                   div: Optional[int] = None) -> bytes:
        """Fused-plane pull: the merged round encoded at the codec the
        caller's decision trace pinned for it (deterministic codecs —
        every puller of (round, codec, div) gets byte-identical
        payloads; caches only skip repeat encodes). Managed keys serve
        straight from the homog store's merged round."""
        from ..compress import wire
        if self._homog_managed(key):
            return self._homog.pull_payload(
                key, codec, round, timeout_ms,
                div=div if div else wire.TOPK_DIV)
        if self._fused_cache is None:
            self._fused_cache = wire.FusedPullCache()
        return wire.pull_encoded(self, self._fused_cache, key, nbytes,
                                 dtype, codec, round,
                                 timeout_ms=timeout_ms,
                                 div=div if div else wire.TOPK_DIV)

    def param_store(self):
        if self._param_store is None:
            with self._param_lock:
                if self._param_store is None:
                    from ..sharded_update import ParamStore
                    self._param_store = ParamStore()
        return self._param_store

    def param_put(self, key: int, seq: int, payload) -> None:
        """Sharded-update param publish (in-process mailbox; last-wins
        per (key, seq) — see sharded_update.ParamStore)."""
        self.param_store().put(key, seq, payload)

    def param_get(self, key: int, seq: int,
                  timeout_ms: int = 30000) -> bytes:
        """Blocking non-destructive fetch of a (key, seq) param frame."""
        return self.param_store().get(key, seq, timeout_ms=timeout_ms)

    def param_latest(self, key: int) -> int:
        """Newest retained param seq for ``key`` (0 = empty) — the
        elastic-rejoin seq seed (sharded_update)."""
        return self.param_store().latest(key)

    def pull_bytes(self, key: int, round: int = 0,
                   timeout_ms: int = 30000) -> bytes:
        """Compressed pull: merged dense round recompressed once, served
        byte-identical to every worker."""
        import time as _time
        from .compressed import compressed_pull
        t0 = _time.time()
        out = compressed_pull(self.compressed, self._shard(key), key,
                              round, timeout_ms)
        self.spans.note_serve(key, round, t0, _time.time() - t0)
        return out

    def push_rowsparse(self, key: int, idx, rows, dense_nbytes: int,
                       dtype=None) -> None:
        """Row-sparse push: only touched rows cross into the store; the
        server scatters to dense before the engine sums (reference:
        reserved kRowSparsePushPull, common.h:267-271 — unimplemented
        there). dtype defaults to the rows array's own dtype."""
        from .rowsparse import rowsparse_push
        rowsparse_push(self._shard(key), key, idx, rows, dense_nbytes,
                       dtype, meta=self._rs_cols)
        self.spans.note_arrival(
            key, 0, int(getattr(rows, "nbytes", 0)))    # see push_onebit

    def push_pull(self, key: int, data: np.ndarray,
                  timeout_ms: int = 30000) -> np.ndarray:
        """One sync round from a single-worker's perspective: push, then
        pull the round this push completes (per-key local round counter)."""
        self.push(key, data)
        rnd = self._rounds.get(key, 0) + 1
        self._rounds[key] = rnd
        out = np.empty_like(data)
        self.pull(key, out, rnd if not self.async_mode else 0, timeout_ms)
        return out

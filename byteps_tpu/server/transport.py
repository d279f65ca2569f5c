"""TCP transport for the host reduction service — the reference's
ps-lite "van" equivalent (reference: ps-lite ZMQ/TCP van, SURVEY §2.6;
worker call sites ZPush/ZPull core_loops.cc:567-613).

Wire protocol: one persistent connection per worker, length-prefixed
binary frames:

    request  := op:u8 | key:u64 | round:u64 | nbytes:u64 | timeout_ms:u64
                | plen:u64 | dtype:u8[8] | payload[plen]
    response := status:u8 | nbytes:u64 | payload[nbytes]

ops: 1=INIT (``nbytes`` = store size, payload = optional initial value),
2=PUSH (payload = data; ``round`` carries a dedup token
``worker_incarnation<<32 | per-key seq`` so a push retried after a
dropped ACK is applied exactly once — see ``RemotePSBackend``),
3=PULL (``nbytes`` = expected size, no payload;
response carries the merged buffer), 4=CLOSE, 5=INIT_C (``nbytes`` =
DENSE store size, payload = serialized compression kwargs — the server
registers a codec for the key, reference server.cc:222-252), 6=PUSH_C
(payload = compressed bytes; server decompresses then dense-sums),
7=PULL_C (``nbytes`` unused/0 — the payload size is fixed by the key's
codec; server recompresses the merged round once and serves identical
bytes to every worker, reference server.cc:86-113), 8=PUSH_RS
(row-sparse push: ``nbytes`` = DENSE table byte size, payload =
``n|idx|rows`` per server/rowsparse.py; server scatters to dense then
engine-sums — the reference's reserved-but-unimplemented
kRowSparsePushPull). status: 0=OK, 1=error
(backend rejected the request; the error response carries a UTF-8
message as payload and the connection stays usable), 2=timeout.

``PSTransportServer`` fronts a ``PSServer``/``HostPSBackend`` (the
native C++ summation engine) with a threaded socket server: one thread
per worker connection; the engine's sticky key→thread queues do the
summation exactly as in-process. ``RemotePSBackend`` is the worker-side
client with the same interface as ``HostPSBackend`` (including
``push_pull``'s per-key round counter), so ``PSGradientExchange`` and
``AsyncPSWorker`` work unchanged across process/host boundaries.
"""

from __future__ import annotations

import os
import socket
import struct
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

try:                       # registers "bfloat16" with numpy for the
    import ml_dtypes       # noqa: F401 — bf16 wire transcode path
except ImportError:        # pragma: no cover — jax ships ml_dtypes
    pass

from ..common.naming import place_key

_HDR = struct.Struct("!BQQQQQ8s")   # op, key, round, nbytes, timeout, plen, dtype
_RSP = struct.Struct("!BQ")

OP_INIT, OP_PUSH, OP_PULL, OP_CLOSE = 1, 2, 3, 4
OP_INIT_C, OP_PUSH_C, OP_PULL_C = 5, 6, 7
OP_PUSH_RS = 8   # row-sparse push: nbytes = DENSE table size, payload =
                 # n|idx|rows (server/rowsparse.py wire format)
OP_ROUND = 9     # query the key's latest completed round (response
                 # payload = u64) — a restarted worker of a LIVE job
                 # resyncs its round counters from this instead of
                 # stalling on round 1 (elastic rejoin)
# Shared-memory data plane (reference: ps-lite's zero-copy ZPush/ZPull
# on shm for colocated worker↔server, core_loops.cc:567-613 /
# BYTEPS_ENABLE_IPC): the frame carries only the segment name and
# length; the payload lives at offset 0 of a worker-owned POSIX shm
# segment (one per connection channel) the server attaches to —
# gradient bytes never cross a socket. Field semantics are unchanged
# from the socket ops: ``round`` = dedup token (push) / sync round
# (pull), ``timeout`` = pull timeout ms.
OP_PUSH_SHM = 10   # payload = segment name, ``nbytes`` = data length
OP_PULL_SHM = 11   # same; the server PULLs INTO the segment
# Connection STRIPING for large tensors (VERDICT r4 #4 — the role of
# ps-lite's multi-lane RDMA/UCX vans): one logical push/pull split
# over several pooled connections in flight at once.
#   OP_PUSH_PART: nbytes = TOTAL length, rnd = dedup token shared by
#     all parts; payload = _PART prefix + the part's bytes. The server
#     stages parts per (key, token) and applies ONCE when complete.
#     The prefix's nonce is 0 (the token already identifies the op and
#     MUST be stable across retries for the staging dedup).
#   OP_PULL_PART: rnd = round; payload = _PART prefix (no data). The
#     server round-blocks once per (key, round, nonce), caches the
#     merged bytes while the op's parts drain, and each part response
#     carries its [offset, offset+len) slice — the client receives
#     straight into the caller's buffer (zero-copy scatter). The nonce
#     is fresh per LOGICAL pull attempt: without it, concurrent
#     striped pullers of the same async key share a (key, round=0)
#     stage, and the second fetch after the first op's parts drain it
#     can serve a NEWER store value to the first op's stragglers — a
#     torn tensor assembled from two different rounds (ADVICE.md).
OP_PUSH_PART = 12
OP_PULL_PART = 13
# Replica-log ops for the server plane's primary-backup replication
# (byteps_tpu.server.plane): the forward-log of a key's summed rounds
# lives in a ReplicaStore hosted by the BACKUP shard's transport
# server, so after the primary dies the promoted shard replays pulls
# from its local log bit-exact (docs/server-plane.md).
#   OP_REPL_PUT: ``round`` = plane round, payload = merged bytes
#     (idempotent last-wins; every worker logs the identical merge).
#   OP_REPL_GET: ``round`` = plane round; response payload = one
#     presence byte (0/1) + the logged bytes — a zero-length logged
#     round stays distinguishable from "never logged".
#   OP_REPL_BASE: response payload = u64 highest logged round.
OP_REPL_PUT, OP_REPL_GET, OP_REPL_BASE = 14, 15, 16
# Fused compression plane (byteps_tpu.compress): unlike INIT_C/PUSH_C/
# PULL_C (one immutable codec registered per key), the payload is
# SELF-DESCRIBING — a codec header rides every frame, so the adaptive
# controller can re-decide a layer's codec at any round boundary and
# the server decodes whatever arrives (or refuses LOUDLY on a codec-
# version mismatch / torn header, compress.wire.CodecError).
#   OP_PUSH_F: ``round`` = dedup token (like OP_PUSH); payload =
#     header + codec body. Server decodes → dense-sums in the engine.
#   OP_PULL_F: ``round`` = sync round, ``nbytes`` = DENSE size, dtype =
#     dense dtype; payload = codec:u8 | topk-div:u16le (the level the
#     worker's decision trace pinned for this round + its configured
#     keep fraction). Server pulls the merged round dense, encodes it
#     at that codec (cached per (key, round, codec, div) —
#     deterministic codecs, so the cache is throughput-only), responds
#     with the payload.
OP_PUSH_F, OP_PULL_F = 17, 18
# Point-to-point activation plane (byteps_tpu.pipeline, MPMD pipeline
# parallelism): activations / activation-grads hop stage→stage through
# the RECEIVER's mailbox, never through the server sum.
#   OP_ACT_PUSH: key = activation channel (pipeline.exchange.act_key),
#     ``round`` = absolute microbatch seq; payload = the boundary's
#     concatenated var bytes. Last-wins per (key, seq), so the
#     transport's resend path is idempotent for free.
#   OP_ACT_PULL: remote take — blocks server-side (sliced, like
#     OP_PULL) until the (key, seq) frame arrives; response = payload.
# ACT frames are the transport's LATENCY class: the client tags them
# ``sched.CLASS_ACT`` so they overtake queued gradient bursts in the
# send scheduler (BPS_SCHEDULING_CREDIT).
OP_ACT_PUSH, OP_ACT_PULL = 19, 20
# Sharded weight update (byteps_tpu.sharded_update): the group OWNER
# publishes post-apply parameter bytes, non-owners fetch them instead
# of gradients. A versioned last-wins mailbox like the act store, but
# NON-destructive reads (dp-1 replicas read each frame) with bounded
# retention (the two-round cross-step window + slack).
#   OP_PARAM_PUT: key = param-class key (bit 41 | decl<<16 | group),
#     ``round`` = the sharded step seq; payload = the group's
#     concatenated leaf bytes. Idempotent last-wins per (key, seq).
#     PUT frames ride the wire scheduler's LATENCY class with
#     next-step first-use priority — they gate the next forward like
#     activations do.
#   OP_PARAM_GET: ``round`` = seq; blocks server-side (sliced, like
#     OP_PULL) until the frame arrives; response = payload. A timeout
#     is the owner-death diagnostic's trigger, never a silent hang.
OP_PARAM_PUT, OP_PARAM_GET = 21, 22
# Fleet telemetry plane (byteps_tpu.obs.fleet): serve this SERVER
# process's registry snapshot + heartbeat (monotonic uptime, op
# counters) as one JSON response. Request carries no payload and the
# response is an ordinary reply, so the op is reuse-safe by
# construction and NEVER credit-gated — the send scheduler only gates
# payload-bearing frames, and the client scrapes on a DEDICATED
# channel outside the data-plane pools: telemetry must flow when the
# data plane is wedged (that is precisely when it is needed).
OP_STATS = 23
# Elastic rejoin (docs/elasticity.md): the newest retained seq in a
# key's param mailbox, so a rejoining sharded-update owner resumes its
# param-frame sequence from the server's retained frames instead of
# re-publishing from seq 0 (which would strand every non-owner blocked
# on the real next seq). Response payload = u64 seq (0 = empty).
OP_PARAM_SEQ = 24
# Causal trace plane (byteps_tpu.obs.spans): serve this server's
# per-(key, round) span ring — first arrival, per-worker arrival
# ts+bytes, merge-wait, per-pull serve spans — plus the server's wall
# clock ``now`` (the NTP-style clock-alignment sample). Same contract
# as OP_STATS: no payload, reuse-safe, NEVER credit-gated, scraped on
# the dedicated stats channel so a wedged data plane cannot starve it.
OP_TRACE = 25
# Bounded staleness (server/admission.StaleStore, docs/admission.md):
# OP_LAG_DECL declares a key's K bound (rnd = K); it is replayed on
# reconnect like inits — the failover contract — so a replacement
# server relearns every key's bound before the first versioned frame.
# OP_PUSH_LAG / OP_PULL_LAG carry ``rnd = worker_id << 48 | round``
# (48 bits of round, 16 of worker). The pull response prefixes one
# verdict byte (admission.LAG_* flags) to the dense payload.
OP_LAG_DECL, OP_PUSH_LAG, OP_PULL_LAG = 26, 27, 28
# Sharded embedding store (server/embed.py, docs/embedding.md): rows
# of a table hash-placed across shards, addressed by id in the PAYLOAD
# (one key per table — bit 43 of the key space), pulled conditionally
# against cached per-row versions and pushed as dedup'd row-sparse
# sums. Transport-owned like the act/param mailboxes so raw-PSServer
# fleet server roles speak it; REFUSED on a hierarchical-agg front
# (embed_store below — an aggregator has no row store to serve from).
#   OP_EMBED_INIT: payload = JSON table meta; idempotent first-wins.
#   OP_EMBED_PULL: payload = n:u32|ids:u64[n]|cached_vers:u64[n]
#     [|table_epoch:u64]; response = table_epoch:u64|flags:u8[n]|
#     vers:u64[n]|full rows for flag==1 only. A request epoch behind
#     the table's forces every row full (failover/restore coherence).
#   OP_EMBED_PUSH: payload = n:u32|ids:u64[n]|deltas:dtype[n·cols];
#     ``rnd`` = push dedup token — a reconnect retry applies once.
OP_EMBED_INIT, OP_EMBED_PULL, OP_EMBED_PUSH = 29, 30, 31
# Embed durability (ISSUE 20, server↔server + admin ops):
#   OP_EMBED_REPL: chain forward of applied rows — key = slice key
#     (table | origin shard), ``rnd`` = the originating push's dedup
#     token, payload = n:u32|ids:u64[n]|vers:u64[n]|rows (ABSOLUTE
#     post-apply state; last-wins by version on the replica).
#   OP_EMBED_FAILOVER: promote this server for a dead slice — key =
#     slice key, payload = JSON {"dead": [shards]}; response = JSON
#     stats {table, slice, rows, errors, epoch, already}. Idempotent.
#   OP_EMBED_SNAP / OP_EMBED_RESTORE: payload = JSON {"path"}; the
#     server dumps/loads its whole row store as one npz (atomic
#     tmp+rename on SNAP); response = JSON stats.
OP_EMBED_REPL, OP_EMBED_FAILOVER = 32, 33
OP_EMBED_SNAP, OP_EMBED_RESTORE = 34, 35
_PART = struct.Struct("!IIHHQ")  # offset, part_len, part_idx, nparts, nonce
_LAG_ROUND_MASK = (1 << 48) - 1
ST_OK, ST_ERR, ST_TIMEOUT, ST_GONE = 0, 1, 2, 3


class _ServerTimeout(TimeoutError):
    """An ST_TIMEOUT reply — an APPLICATION answer on a healthy
    connection. Distinct from the OS's TimeoutError (ETIMEDOUT, which
    also subclasses OSError and SHOULD take the reconnect path)."""

# applied seqs kept as an exact set above a contiguous floor — bounds
# memory while letting out-of-order same-key pushes through
_DEDUP_WINDOW = 256


class _PosixShm:
    """Minimal POSIX shared-memory segment (shm_open + mmap), used
    instead of multiprocessing.shared_memory to keep the resource
    tracker out of the picture: this Python's tracker mis-handles the
    create-in-one-process/attach-in-another lifecycle (spurious
    KeyErrors and exit warnings), and ownership here is explicit —
    workers create and unlink their segments, the server only attaches.
    A SIGKILLed worker can strand its current /dev/shm/bps-shm-*
    files (0600, one or two per connection channel) until reboot or a
    manual ``rm`` — the documented cost of skipping the tracker."""

    __slots__ = ("name", "size", "_mmap", "buf")

    def __init__(self, name: Optional[str] = None, create: bool = False,
                 size: int = 0) -> None:
        import mmap as _mmap
        import os as _os
        import secrets as _secrets
        from multiprocessing import shared_memory as _sm
        posixshmem = _sm._posixshmem
        if create:
            while True:
                name = f"/bps-shm-{_secrets.token_hex(6)}"
                try:
                    fd = posixshmem.shm_open(
                        name, _os.O_CREAT | _os.O_EXCL | _os.O_RDWR,
                        mode=0o600)
                    break
                except FileExistsError:
                    continue
            _os.ftruncate(fd, size)
        else:
            fd = posixshmem.shm_open(name, _os.O_RDWR, mode=0o600)
            size = _os.fstat(fd).st_size
        try:
            self._mmap = _mmap.mmap(fd, size)
        finally:
            _os.close(fd)
        self.name = name
        self.size = size
        self.buf = memoryview(self._mmap)

    def close(self) -> None:
        try:
            self.buf.release()
            self._mmap.close()
        except (BufferError, ValueError):
            pass

    def unlink(self) -> None:
        from multiprocessing import shared_memory as _sm
        try:
            _sm._posixshmem.shm_unlink(self.name)
        except OSError:
            pass


class _ShmCache:
    """Server-side LRU of attached worker shm segments, bounded by
    count AND bytes (a worker's segment growth abandons old names —
    already unlinked, but mapped here until evicted; the byte bound
    keeps dead generations from pinning multi-GB of shm). Slices are
    taken under the lock so a concurrent eviction can't release a
    buffer between lookup and use; an evicted-while-exported buffer
    stays alive because _PosixShm.close backs off on BufferError."""

    def __init__(self, cap: int = 64, cap_bytes: int = 1 << 30) -> None:
        self._segs: Dict[str, _PosixShm] = {}   # insertion order = LRU
        self._lock = threading.Lock()
        self._cap = cap
        self._cap_bytes = cap_bytes

    def view(self, name: str, nbytes: int) -> memoryview:
        with self._lock:
            seg = self._segs.pop(name, None)
            if seg is None:
                seg = _PosixShm(name=name)
            self._segs[name] = seg              # (re)insert most-recent
            while len(self._segs) > self._cap or (
                    len(self._segs) > 1 and
                    sum(s.size for s in self._segs.values())
                    > self._cap_bytes):
                old = next(iter(self._segs))
                if old == name:
                    break
                try:
                    self._segs.pop(old).close()
                except Exception:
                    pass
            if nbytes > seg.size:
                raise ValueError(f"shm window {nbytes}B exceeds segment "
                                 f"{name} ({seg.size}B)")
            return seg.buf[:nbytes]

    def close(self) -> None:
        with self._lock:
            for seg in self._segs.values():
                try:
                    seg.close()
                except Exception:
                    pass
            self._segs.clear()


class _DedupState:
    """Per-(key, worker-incarnation) push-dedup record."""

    __slots__ = ("floor", "applied", "claims", "ts")

    def __init__(self) -> None:
        self.floor = 0          # every seq <= floor is applied
        self.applied: set = set()   # applied seqs above floor
        self.claims: set = set()    # seqs whose apply is in flight
        self.ts = 0.0

    def is_applied(self, seq: int) -> bool:
        return seq <= self.floor or seq in self.applied

    def record(self, seq: int) -> None:
        self.applied.add(seq)
        # advance the contiguous floor, then cap the exact window
        while (self.floor + 1) in self.applied:
            self.floor += 1
            self.applied.discard(self.floor)
        while len(self.applied) > _DEDUP_WINDOW:
            low = min(self.applied)
            self.applied.discard(low)
            self.floor = max(self.floor, low)


def _as_bytes(arr) -> memoryview:
    """Byte view of any numpy array — dtypes outside the buffer protocol
    (bfloat16) go through a uint8 reinterpret."""
    a = np.ascontiguousarray(arr)
    try:
        return memoryview(a).cast("B")
    except (ValueError, TypeError):
        return memoryview(a.view(np.uint8))


def _recv_exact(sock: socket.socket, n: int) -> memoryview:
    buf = bytearray(n)
    _recv_exact_into(sock, memoryview(buf))
    return memoryview(buf)


def _recv_exact_into(sock: socket.socket, view: memoryview) -> None:
    """Fill ``view`` from the socket — the zero-copy receive: dense
    pulls land straight in the caller's preallocated array instead of
    paying an allocate + copy per pull (VERDICT r4 #4)."""
    n = len(view)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError("peer closed")
        got += r


def _byteview(p) -> memoryview:
    """Flat byte view of any buffer — multi-byte-item views (a numpy
    float array passed raw) are recast so vector lengths are BYTE
    lengths, the unit sendmsg's return value and the partial-send
    bookkeeping below are denominated in."""
    v = p if isinstance(p, memoryview) else memoryview(p)
    if v.itemsize != 1 or v.ndim != 1:
        v = v.cast("B")
    return v


def _send_frame(sock, hdr, parts) -> None:
    """Vectored zero-copy frame send: header + payload parts ride ONE
    ``sendmsg`` scatter-gather array of memoryviews, so no frame size
    pays a join/copy (the old path materialized ``hdr + b"".join(...)``
    for every frame up to 16 KB) and no part count pays per-part
    syscalls. A short vectored write resumes from the first unsent
    byte — fully-sent vectors are dropped, the split one is resliced
    (slicing a memoryview is a view, not a copy).

    Sockets without a vectored primitive (test doubles) degrade to
    sequential ``sendall`` per part — still no join, single-part
    frames still one write for the payload. ThrottledSocket implements
    its OWN metered ``sendmsg`` (throttle.py): its ``__getattr__``
    would otherwise proxy this call to the raw socket and every
    vectored byte would silently bypass the emulated NIC's pacing AND
    the wire-byte accounting the scaling-curve rig asserts against."""
    sendmsg = getattr(sock, "sendmsg", None)
    if sendmsg is None:
        sock.sendall(hdr)
        for p in parts:
            sock.sendall(p)
        return
    bufs = [_byteview(hdr)]
    for p in parts:
        bufs.append(_byteview(p))
    while bufs:
        # cap the iovec count: sendmsg raises EMSGSIZE past IOV_MAX
        # (1024 on Linux) and a large row-gather can exceed it; the
        # resume loop below already handles the unsent tail
        n = sendmsg(bufs[:1024])
        while bufs and n >= len(bufs[0]):
            n -= len(bufs[0])
            bufs.pop(0)
        if n:
            bufs[0] = bufs[0][n:]


def _send_req(sock: socket.socket, op: int, key: int, rnd: int, nbytes: int,
              timeout_ms: int, dtype: str, payload) -> None:
    """``payload``: None, one buffer, or a SEQUENCE of buffers sent
    back to back as one wire payload (scatter-gather — striped parts
    prepend their _PART prefix without copying the data slice)."""
    parts = ([] if payload is None
             else list(payload) if isinstance(payload, (tuple, list))
             else [payload])
    # normalize to byte views up front: plen must be a BYTE count even
    # if a caller hands a multi-byte-item buffer (len() of a float32
    # memoryview counts elements)
    parts = [_byteview(p) for p in parts]
    plen = sum(len(p) for p in parts)
    hdr = _HDR.pack(op, key, rnd, nbytes, timeout_ms, plen,
                    dtype.encode()[:8].ljust(8, b"\0"))
    if not parts:
        sock.sendall(hdr)
        return
    _send_frame(sock, hdr, parts)


# The reused-recv-buffer invariant: an op's handler must CONSUME its
# payload before the connection reads the next frame, because the next
# frame overwrites the shared buffer. This allowlist names the ops whose
# handlers are known to copy synchronously (the engine/stage copies the
# bytes before the handler returns); any op NOT listed gets a fresh
# buffer — a new op that stashes a payload view past its handler return
# degrades to an allocation instead of silently corrupting frames.
_REUSE_SAFE_OPS = frozenset(
    {OP_INIT, OP_PUSH, OP_PUSH_C, OP_PUSH_RS, OP_PUSH_PART,
     OP_REPL_PUT,    # ReplicaStore.put copies via bytes() synchronously
     OP_PUSH_F,      # wire.decode materializes (or the engine copies
                     # the dense view) before the handler returns
     OP_ACT_PUSH,    # ActStore.put copies via bytes() synchronously
     OP_PARAM_PUT,   # ParamStore.put copies via bytes() synchronously
     OP_PUSH_LAG,    # StaleStore.push folds (+=) before returning
     OP_EMBED_PUSH,  # EmbedRowStore.apply folds row-wise (new arrays)
                     # before returning
     OP_EMBED_PULL,  # ids/vers views are consumed inside .pull()
                     # (the row buffer is a fresh concatenation)
     OP_EMBED_REPL})  # handler materializes via bytes() before
#                       repl_apply stores per-row copies


def _recv_req(sock: socket.socket, rholder: Optional[list] = None):
    op, key, rnd, nbytes, timeout, plen, dt = _HDR.unpack(
        _recv_exact(sock, _HDR.size))
    if not plen:
        payload = None
    elif (rholder is not None and plen > (64 << 10)
            and op in _REUSE_SAFE_OPS):
        # large payloads land in the connection's REUSED buffer: a fresh
        # bytearray(n) zero-fills n bytes before the recv overwrites
        # them — at 8 MB pushes that zeroing alone was a measurable
        # slice of the wire path. Safe because the allowlisted handlers
        # consume their payload synchronously (the engine copies before
        # returning). Grown by REPLACEMENT, never resize: the caller's
        # loop still holds the previous frame's memoryview, and resizing
        # an exported bytearray raises BufferError and kills the
        # connection
        if len(rholder[0]) < plen:
            rholder[0] = bytearray(plen)
        payload = memoryview(rholder[0])[:plen]
        _recv_exact_into(sock, payload)
    else:
        payload = _recv_exact(sock, plen)
    return op, key, rnd, nbytes, timeout, dt.rstrip(b"\0").decode(), payload


# ------------------------------------------------------------------ server

def _ipc_path(port: int) -> str:
    """Deterministic UDS path for a server's IPC listener — colocated
    workers derive it from the TCP port they were given, so no extra
    address plumbing is needed (reference: BYTEPS_ENABLE_IPC switches
    colocated worker↔server traffic off the network stack,
    docs/best-practice.md). Sockets live in a 0700 per-uid directory —
    a world-writable shared path would let another local user squat the
    name (denying startup) or bind an impostor listener that workers
    auto-upgrade their gradients to."""
    import os as _os
    import stat as _stat
    import tempfile as _tempfile
    base = _os.environ.get("BPS_IPC_DIR")
    if not base:
        base = _os.path.join(_tempfile.gettempdir(),
                             f"bps-ipc-{_os.getuid()}")
    _os.makedirs(base, mode=0o700, exist_ok=True)
    st = _os.stat(base)
    if st.st_uid != _os.getuid() or (st.st_mode & 0o077):
        raise RuntimeError(
            f"IPC dir {base} must be owned by uid {_os.getuid()} with "
            f"mode 0700 (found uid {st.st_uid}, mode "
            f"{_stat.S_IMODE(st.st_mode):o}) — refusing to exchange "
            f"gradients over a tamperable socket path")
    return _os.path.join(base, f"bps-ipc-{port}.sock")


def _bump_bufs(s: socket.socket, nbytes: int = 4 << 20) -> None:
    """Grow a UDS's kernel buffers: the AF_UNIX default (~208KB) makes
    multi-MB gradient frames ping-pong between the peers with a context
    switch per buffer-full, which measured SLOWER than loopback TCP
    (whose autotuned windows absorb bulk writes)."""
    for opt in (socket.SO_SNDBUF, socket.SO_RCVBUF):
        try:
            s.setsockopt(socket.SOL_SOCKET, opt, nbytes)
        except OSError:
            pass


def _ipc_enabled() -> bool:
    import os as _os
    return _os.environ.get(
        "BPS_ENABLE_IPC", _os.environ.get("BYTEPS_ENABLE_IPC", "0")) \
        not in ("0", "", "false")


class PSTransportServer:
    """Threaded TCP front for a local summation backend.

    With BPS_ENABLE_IPC=1 the server ALSO listens on a Unix-domain
    socket (path derived from the TCP port) and colocated workers
    auto-upgrade their connections to it — loopback TCP's
    checksum/segmentation overhead gone, same frames, same handler
    (the reference's colocated-IPC deployment knob)."""

    def __init__(self, backend, host: str = "0.0.0.0", port: int = 0,
                 key_meta=None, nic=None):
        self.backend = backend
        # fused/homogeneous front (server/homog.py): backends with a
        # fused surface of their own (HostPSBackend) handle managed
        # keys internally; a RAW engine (PSServer) gets wrapped so the
        # homogeneous decode-free sum exists on every deployment. Ops
        # that can touch managed keys route through ``_fb``.
        if hasattr(backend, "push_fused"):
            self._fb = backend
        else:
            from .homog import FusedFront
            self._fb = FusedFront(backend,
                                  getattr(backend, "num_workers", 1))
        # optional emulated-NIC throttle (throttle.Nic): every accepted
        # connection's bytes are charged to this server endpoint's
        # bandwidth — see throttle.py / the PS-vs-allreduce bench
        self._nic = nic
        from .compressed import CompressedKeyStore
        self.compressed = CompressedKeyStore()
        # per-key traffic log (reference: PS_KEY_LOG on the server,
        # server.cc:408-409)
        import os as _os
        self._key_log = _os.environ.get(
            "BPS_KEY_LOG", _os.environ.get("PS_KEY_LOG", "")) in ("1", "true")
        self._rs_cols: Dict[int, int] = {}   # row-sparse: pinned cols/key
        # key -> (nbytes, dtype), recorded at INIT/INIT_C so the store can
        # be snapshotted (the reference has NO PS-state checkpoint —
        # docs/rationale.md leaves server recovery as future work);
        # seeded with restore_snapshot's meta when recovering
        self._key_meta: Dict[int, Tuple[int, str]] = dict(key_meta or {})
        # (key, worker_incarnation) -> _DedupState. A push retried after
        # a lost ACK carries the same token and is acknowledged without
        # re-applying — without this, a sync-mode reconnect could
        # double-count one worker's gradient in the round's sum (the
        # per-round push counter would fill early with another worker
        # missing). Applied seqs are EXACT-membership (recent set +
        # contiguous floor), not a high-water mark, so concurrent
        # same-key pushes whose frames land out of order are both
        # applied. ``claims`` marks seqs whose apply is IN FLIGHT, so a
        # retry racing the original apply (conn reset mid-sum, instant
        # redial) blocks on its outcome instead of re-applying
        # concurrently. Applied seqs are recorded only after a
        # successful apply: a dedup hit always means the payload reached
        # the store. Entries for dead incarnations are swept after
        # ``BPS_PUSH_DEDUP_TTL_SECS`` (default 600 — far beyond any
        # retry window) of inactivity so elastic worker churn can't grow
        # the table without bound.
        self._push_seen: Dict[Tuple[int, int], _DedupState] = {}
        # replica log hosted FOR other shards' keys (server plane
        # primary-backup replication, OP_REPL_*) — created on first use
        # so plain deployments never pay the import
        self._replica = None
        self._replica_lock = threading.Lock()
        # activation mailbox (pipeline stage→stage plane, OP_ACT_*) —
        # likewise lazy; plain PS deployments never allocate it
        self._acts = None
        self._acts_lock = threading.Lock()
        # param mailbox (sharded weight update, OP_PARAM_*) — lazy too
        self._params = None
        # sharded embedding row store (server/embed.py, OP_EMBED_*) —
        # lazy; deployments without tables never allocate it
        self._embed = None
        self._embed_lock = threading.Lock()
        self._shm = _ShmCache()
        # fused-pull caching lives behind self._fb (the backend's own
        # FusedPullCache, or FusedFront's, or the homog store's merged
        # payload dict) — the transport layer holds no codec state
        # striping reassembly/scatter state (OP_PUSH_PART/OP_PULL_PART):
        # parts of one logical op arrive on DIFFERENT connection
        # threads. Stages carry a last-activity stamp and are swept
        # after _STRIPE_TTL_SECS — a client dying mid-striped-op (or a
        # retry racing a completed stage) must not strand full-tensor
        # staging buffers for the server's lifetime
        self._stripe_lock = threading.Lock()
        self._push_stage: Dict[Tuple[int, int], Dict] = {}
        self._pull_stage: Dict[Tuple[int, int], Dict] = {}
        self._stripe_sweep_at = 0.0
        self._push_lock = threading.Lock()
        self._push_cv = threading.Condition(self._push_lock)
        # bounded-staleness store for RAW backends (see the lag-op
        # helpers below) — lazy, K=1 deployments never allocate it
        self._stale = None
        self._stale_lock = threading.Lock()
        self._dedup_ttl = float(_os.environ.get(
            "BPS_PUSH_DEDUP_TTL_SECS", "600"))
        self._dedup_sweep_at = 0.0
        # cached metric handles — _handle runs per request; a registry
        # name lookup there is avoidable data-plane overhead
        from ..obs.metrics import get_registry
        self._m_requests = get_registry().counter("transport/requests")
        self._m_merge_wait = get_registry().histogram(
            "server/merge_wait_s")
        # heartbeat state for OP_STATS (obs/fleet.py): MONOTONIC birth
        # time (a scraper seeing uptime go backwards has watched this
        # process restart — wall clocks can step, this cannot) and a
        # plain per-server request count (the registry counter above is
        # process-wide and shared by colocated servers)
        self._t0_mono = time.monotonic()
        self._t0_wall = time.time()
        self._n_requests = 0
        # causal span ring (obs/spans.py, OP_TRACE): per-(key, round)
        # arrival/serve records. A backend with its OWN ring
        # (HostPSBackend) records internally — this layer then only
        # serves it, never double-notes the same push into two rings.
        from ..obs.spans import ServerSpanRing
        ring = getattr(backend, "spans", None)
        self._own_spans = ring is None
        self.spans = ring if ring is not None else ServerSpanRing(
            num_workers=getattr(backend, "num_workers", 1))
        # the clock-alignment sample source — an attribute so skew
        # tests (and one day a chaos rig) can inject a stepped clock
        self._trace_now = time.time
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self.port = self._sock.getsockname()[1]
        self._sock.listen(64)
        self._stop = threading.Event()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, args=(self._sock, True),
            daemon=True, name="bps-ps-accept")
        self._accept_thread.start()
        self._ipc_sock = None
        self.ipc_path = None
        if _ipc_enabled():
            import os as _os
            path = _ipc_path(self.port)
            try:
                _os.unlink(path)
            except OSError:
                pass
            self._ipc_sock = socket.socket(socket.AF_UNIX,
                                           socket.SOCK_STREAM)
            _bump_bufs(self._ipc_sock)
            self._ipc_sock.bind(path)
            self._ipc_sock.listen(64)
            self.ipc_path = path
            threading.Thread(target=self._accept_loop,
                             args=(self._ipc_sock, False),
                             daemon=True, name="bps-ps-ipc-accept").start()

    def _accept_loop(self, sock: socket.socket, is_tcp: bool) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = sock.accept()
            except OSError:
                return
            if is_tcp:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            if self._nic is not None:
                from .throttle import ThrottledSocket
                conn = ThrottledSocket(conn, self._nic)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True, name="bps-ps-conn").start()

    def _handle(self, conn, op, key, rnd, nbytes, timeout, dtype, payload):
        """One request; backend errors become ST_ERR/ST_TIMEOUT responses
        (the connection survives — one bad request must not take down the
        worker's whole data plane)."""
        self._m_requests.inc()
        self._n_requests += 1    # heartbeat op counter; GIL-atomic int
        #                          add is plenty for a liveness signal
        try:
            if self._key_log and op in (OP_PUSH, OP_PULL, OP_PUSH_C,
                                        OP_PUSH_RS):
                # OP_PULL_C logs in its branch — its size is the codec
                # payload, known only after the pull
                from ..common.logging import get_logger
                get_logger().info("PS_KEY_LOG op=%d key=%d bytes=%d rnd=%d",
                                  op, key,
                                  len(payload) if payload else nbytes, rnd)
            if op == OP_INIT:
                init = (np.frombuffer(payload, dtype=dtype)
                        if payload is not None else None)
                # rnd bit 0 = the worker's plan-time fused-managed
                # declaration (compression-plane keys): hands the key's
                # rounds to the homogeneous fused store
                self._fb.init_key(key, nbytes, dtype, init=init,
                                  fused=bool(int(rnd) & 1))
                self._key_meta[key] = (int(nbytes), dtype)
                # a (re-)init marks a new tenancy of the key on this
                # shard (migration replay): shard-local rounds restart,
                # so cached fused pulls from a previous tenancy would
                # alias the recurring round numbers. HostPSBackend
                # drops its own cache inside init_key; FusedFront
                # exposes the drop explicitly.
                if hasattr(self._fb, "drop_cached"):
                    self._fb.drop_cached(key)
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PUSH:
                # wire transcode: a frame dtype narrower than the store
                # (bf16 async deltas, BPS_ASYNC_WIRE_DTYPE) halves wire
                # bytes; the store keeps full precision (the reference's
                # inter-node fp16 compression, applied the TPU way)
                arr = np.frombuffer(payload, dtype=dtype)
                meta = self._key_meta.get(key)
                if meta is not None and meta[1] != dtype:
                    arr = arr.astype(meta[1])
                self._note_push(self._apply_push_once(
                    key, rnd, lambda: self._fb.push(key, arr)),
                    key, rnd, len(payload))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PULL:
                out = self._pull_dense(key, rnd, nbytes, dtype, timeout)
                # vectored: status + dense sum in one gather write
                _send_frame(conn, _RSP.pack(ST_OK, out.nbytes),
                            [_as_bytes(out)])
            elif op == OP_INIT_C:
                from ..ops.compression.host import deserialize_kwargs
                kwargs = deserialize_kwargs(bytes(payload or b""))
                size = nbytes // np.dtype(dtype).itemsize
                self.compressed.register(key, kwargs, size, dtype)
                self.backend.init_key(key, nbytes, dtype)
                self._key_meta[key] = (int(nbytes), dtype)
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PUSH_C:
                from .compressed import compressed_push
                plen_c = len(payload)
                self._note_push(self._apply_push_once(
                    key, rnd,
                    lambda: compressed_push(self.compressed, self.backend,
                                            key, payload)),
                    key, rnd, plen_c)
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PUSH_F:
                # payload stays ENCODED through the front: managed keys
                # buffer it for the homogeneous merge (no dense decode
                # on this path), unmanaged keys decode into the engine
                pay = bytes(payload)
                self._note_push(self._apply_push_once(
                    key, rnd, lambda: self._fb.push_fused(key, pay)),
                    key, rnd, len(pay))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PULL_F:
                from ..compress import wire as cwire
                pb = bytes(payload or b"\0")
                cid = int(pb[0])
                div = (struct.unpack("<H", pb[1:3])[0]
                       if len(pb) >= 3 else cwire.TOPK_DIV)
                t0 = time.time()
                buf = self._fb.pull_fused(
                    key, int(nbytes), dtype, cid, round=int(rnd),
                    timeout_ms=int(timeout) or 30000,
                    div=div or cwire.TOPK_DIV)
                # same bottleneck signal OP_PULL feeds (_pull_dense):
                # merge wait + the slowest worker's push lag; cache
                # hits observe ~0 and don't skew the histogram
                self._m_merge_wait.observe(time.time() - t0)
                if self._own_spans:
                    self.spans.note_serve(key, int(rnd), t0,
                                          time.time() - t0)
                if self._key_log:
                    from ..common.logging import get_logger
                    get_logger().info(
                        "PS_KEY_LOG op=%d key=%d bytes=%d rnd=%d",
                        op, key, len(buf), rnd)
                conn.sendall(_RSP.pack(ST_OK, len(buf)))
                conn.sendall(buf)
            elif op == OP_PUSH_RS:
                from .rowsparse import rowsparse_push, unpack_rows
                idx, rows = unpack_rows(payload, dtype)
                plen_rs = len(payload)
                self._note_push(self._apply_push_once(
                    key, rnd,
                    lambda: rowsparse_push(self.backend, key, idx, rows,
                                           int(nbytes), dtype,
                                           meta=self._rs_cols)),
                    key, rnd, plen_rs)
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_ROUND:
                # a transport-owned StaleStore (raw-engine fallback)
                # versions the key's rounds itself — the elastic-rejoin
                # resync must see ITS counter, not the engine's zeros
                if self._stale is not None and self._stale.managed(key):
                    rv = struct.pack("!Q", int(self._stale.round(key)))
                else:
                    rv = struct.pack("!Q", int(self._fb.round(key)))
                conn.sendall(_RSP.pack(ST_OK, len(rv)) + rv)
            elif op == OP_PUSH_SHM:
                view = self._shm.view(bytes(payload).decode(), int(nbytes))
                data = np.frombuffer(view, dtype=dtype)
                self._note_push(self._apply_push_once(
                    key, rnd, lambda: self._fb.push(key, data)),
                    key, rnd, int(nbytes))
                del data, view   # release the buffer before reuse/unlink
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PULL_SHM:
                view = self._shm.view(bytes(payload).decode(), int(nbytes))
                out = np.frombuffer(view, dtype=dtype)
                t0 = time.time()
                try:
                    self._fb.pull(key, out, round=int(rnd),
                                  timeout_ms=int(timeout) or 30000)
                finally:
                    del out, view
                if self._own_spans:
                    self.spans.note_serve(key, int(rnd), t0,
                                          time.time() - t0)
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PUSH_PART:
                off, plen_, idx, nparts, _ = _PART.unpack(
                    payload[:_PART.size])
                stage_key = (key, int(rnd))
                now = time.time()
                with self._stripe_lock:
                    self._sweep_stages(now)
                    st = self._push_stage.get(stage_key)
                    if st is None:
                        st = {"buf": bytearray(int(nbytes)), "got": 0,
                              "seen": set(), "t": now}
                        self._push_stage[stage_key] = st
                    st["t"] = now
                # the multi-MB copy runs OUTSIDE the lock — part ranges
                # are disjoint, and copying under a server-wide lock
                # would serialize exactly the parallel staging striping
                # exists for. A retried part overwrites its own range
                # (idempotent) but only counts once toward completion
                memoryview(st["buf"])[off:off + plen_] = \
                    payload[_PART.size:_PART.size + plen_]
                with self._stripe_lock:
                    if idx not in st["seen"]:
                        st["seen"].add(idx)
                        st["got"] += plen_
                    complete = st["got"] >= int(nbytes)
                    if complete:
                        self._push_stage.pop(stage_key, None)
                if complete:
                    arr = np.frombuffer(st["buf"], dtype=dtype)
                    meta = self._key_meta.get(key)
                    if meta is not None and meta[1] != dtype:
                        arr = arr.astype(meta[1])
                    self._note_push(self._apply_push_once(
                        key, rnd, lambda: self.backend.push(key, arr)),
                        key, rnd, int(nbytes))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PULL_PART:
                off, plen_, idx, nparts, nonce = _PART.unpack(
                    payload[:_PART.size])
                # nonce in the stage key: concurrent striped pulls of
                # one async key (round=0) must each get their OWN
                # fetch, or a late part can be served a newer value
                stage_key = (key, int(rnd), int(nonce))
                now = time.time()
                with self._stripe_lock:
                    self._sweep_stages(now)
                    st = self._pull_stage.get(stage_key)
                    if st is None:
                        st = {"ev": threading.Event(), "data": None,
                              "err": None, "served": 0,
                              "nparts": int(nparts), "t": now}
                        self._pull_stage[stage_key] = st
                        fetch = True
                    else:
                        st["t"] = now
                        fetch = False
                if fetch:
                    # ONE round-blocked engine pull feeds every part
                    try:
                        st["data"] = _as_bytes(
                            self._pull_dense(key, rnd, nbytes, dtype,
                                             timeout))
                    except Exception as e:  # noqa: BLE001 — relayed below
                        st["err"] = e
                    finally:
                        st["ev"].set()
                if not st["ev"].wait(
                        timeout=(int(timeout) or 30000) / 1e3 + 5):
                    # fetch still in flight: surface a retryable timeout
                    # WITHOUT counting ourselves served — a premature
                    # served count could pop the stage under the fetch
                    raise TimeoutError(
                        f"pull({key}) round={rnd}: striped fetch did "
                        f"not resolve in time")
                with self._stripe_lock:
                    st["served"] += 1
                    if st["served"] >= st["nparts"]:
                        self._pull_stage.pop(stage_key, None)
                if st["err"] is not None:
                    raise st["err"]
                part = st["data"][off:off + plen_]
                _send_frame(conn, _RSP.pack(ST_OK, len(part)), [part])
            elif op == OP_PARAM_PUT:
                self.param_store().put(key, int(rnd),
                                       bytes(payload or b""))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PARAM_GET:
                data = self.param_store().get(
                    key, int(rnd), timeout_ms=int(timeout) or 30000)
                conn.sendall(_RSP.pack(ST_OK, len(data)))
                if data:
                    conn.sendall(data)
            elif op == OP_PARAM_SEQ:
                rv = struct.pack("!Q",
                                 int(self.param_store().latest(key)))
                conn.sendall(_RSP.pack(ST_OK, len(rv)) + rv)
            elif op == OP_ACT_PUSH:
                self.act_store().put(key, int(rnd),
                                     bytes(payload or b""))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_ACT_PULL:
                data = self.act_store().take(
                    key, int(rnd), timeout_ms=int(timeout) or 30000)
                conn.sendall(_RSP.pack(ST_OK, len(data)))
                if data:
                    conn.sendall(data)
            elif op == OP_REPL_PUT:
                self._replica_store().put(key, int(rnd),
                                          bytes(payload or b""))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_REPL_GET:
                data = self._replica_store().get(key, int(rnd))
                if data is None:
                    conn.sendall(_RSP.pack(ST_OK, 1) + b"\x00")
                else:
                    conn.sendall(_RSP.pack(ST_OK, 1 + len(data)) + b"\x01")
                    conn.sendall(data)
            elif op == OP_REPL_BASE:
                rv = struct.pack("!Q",
                                 int(self._replica_store().base(key)))
                conn.sendall(_RSP.pack(ST_OK, len(rv)) + rv)
            elif op == OP_STATS:
                import json as _json
                body = _json.dumps(self.stats_payload()).encode()
                conn.sendall(_RSP.pack(ST_OK, len(body)))
                conn.sendall(body)
            elif op == OP_TRACE:
                import json as _json
                body = _json.dumps(self.trace_payload()).encode()
                conn.sendall(_RSP.pack(ST_OK, len(body)))
                conn.sendall(body)
            elif op == OP_EMBED_INIT:
                import json as _json
                self.embed_store().init_table(
                    key, _json.loads(bytes(payload or b"{}")))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_EMBED_PULL:
                ep, flags, vers, rowbuf = self.embed_store().pull(
                    key, payload)
                # vectored: status + epoch + flags + versions + the row
                # gather in ONE sendmsg — the zero-copy path the sparse
                # pull rides (rows are copied once under the table
                # lock, never joined again)
                _send_frame(conn,
                            _RSP.pack(ST_OK, len(ep) + len(flags)
                                      + len(vers) + len(rowbuf)),
                            [ep, flags, vers, rowbuf])
            elif op == OP_EMBED_PUSH:
                pay = payload   # consumed synchronously by apply()
                plen_e = len(pay)
                tok = int(rnd)
                self._note_push(self._apply_push_once(
                    key, rnd,
                    lambda: self.embed_store().apply(key, pay,
                                                     token=tok)),
                    key, rnd, plen_e)
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_EMBED_REPL:
                self.embed_store().repl_apply(key, int(rnd),
                                              bytes(payload or b""))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_EMBED_FAILOVER:
                import json as _json
                req = _json.loads(bytes(payload or b"{}"))
                st = self.embed_store().failover(
                    key, req.get("dead") or (),
                    observe=bool(req.get("observe")))
                body = _json.dumps(st).encode()
                conn.sendall(_RSP.pack(ST_OK, len(body)))
                conn.sendall(body)
            elif op == OP_EMBED_SNAP:
                import json as _json
                req = _json.loads(bytes(payload or b"{}"))
                st = self.embed_store().save_shard(str(req["path"]))
                body = _json.dumps(st).encode()
                conn.sendall(_RSP.pack(ST_OK, len(body)))
                conn.sendall(body)
            elif op == OP_EMBED_RESTORE:
                import json as _json
                req = _json.loads(bytes(payload or b"{}"))
                st = self.embed_store().restore_shard(str(req["path"]))
                body = _json.dumps(st).encode()
                conn.sendall(_RSP.pack(ST_OK, len(body)))
                conn.sendall(body)
            elif op == OP_LAG_DECL:
                self._lag_declare(key, int(rnd))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PUSH_LAG:
                w, r = int(rnd) >> 48, int(rnd) & _LAG_ROUND_MASK
                arr = np.frombuffer(payload, dtype=dtype)
                meta = self._key_meta.get(key)
                if meta is not None and meta[1] != dtype:
                    arr = arr.astype(meta[1])
                # the packed rnd doubles as the dedup token: ident
                # becomes (key, worker<<16), seq the round — exactly
                # one fold per (worker, round) across reconnect retries
                self._apply_push_once(
                    key, rnd, lambda: self._lag_push(key, w, r, arr,
                                                     len(payload)))
                conn.sendall(_RSP.pack(ST_OK, 0))
            elif op == OP_PULL_LAG:
                w, r = int(rnd) >> 48, int(rnd) & _LAG_ROUND_MASK
                out = np.empty(int(nbytes) // np.dtype(dtype).itemsize,
                               dtype=dtype)
                flags = self._lag_pull(key, w, r, out,
                                       int(timeout) or 30000)
                _send_frame(conn,
                            _RSP.pack(ST_OK, 1 + out.nbytes)
                            + bytes([flags & 0xFF]),
                            [_as_bytes(out)])
            elif op == OP_PULL_C:
                from .compressed import compressed_pull
                buf = compressed_pull(self.compressed, self.backend, key,
                                      int(rnd), int(timeout) or 30000)
                if self._key_log:
                    from ..common.logging import get_logger
                    get_logger().info(
                        "PS_KEY_LOG op=%d key=%d bytes=%d rnd=%d",
                        op, key, len(buf), rnd)
                conn.sendall(_RSP.pack(ST_OK, len(buf)))
                conn.sendall(buf)
            else:
                conn.sendall(_RSP.pack(ST_ERR, 0))
        except TimeoutError as e:
            msg = str(e).encode()
            conn.sendall(_RSP.pack(ST_TIMEOUT, len(msg)) + msg)
        except Exception as e:
            from .engine import ServerClosed
            if isinstance(e, ServerClosed):
                # shutting down: tell the worker to reconnect (a
                # supervisor restart + snapshot restore is transparent)
                msg = str(e).encode()
                conn.sendall(_RSP.pack(ST_GONE, len(msg)) + msg)
            else:   # backend rejections (bad length, key, …)
                msg = f"{type(e).__name__}: {e}".encode()[:4096]
                conn.sendall(_RSP.pack(ST_ERR, len(msg)) + msg)

    def _note_push(self, applied: bool, key: int, rnd: int,
                   nbytes: int) -> None:
        """One data-plane push reached the store: record the arrival in
        the span ring (dedup duplicates — ``applied=False`` — are NOT
        arrivals; counting them would shear the count-derived round
        attribution). The worker id is the push dedup token's
        incarnation (``rnd >> 32``; 0 for tokenless/legacy frames).
        Skipped when the backend runs its own ring (it noted already)."""
        if applied and self._own_spans:
            self.spans.note_arrival(key, rnd >> 32, nbytes)

    def trace_payload(self) -> dict:
        """The OP_TRACE response body: the span ring + this server's
        wall clock (``now`` — the clock-alignment sample the client
        midpoints against its own send/recv stamps). Reads only
        already-published state, like ``stats_payload``."""
        return self.spans.payload(now=self._trace_now())

    # ------------------------------------------ bounded staleness ops
    #
    # A backend with its own lag surface (HostPSBackend) serves the
    # versioned rounds itself; a RAW engine (PSServer) gets a
    # transport-owned StaleStore — the FusedFront pattern, applied to
    # the K-lag contract so every deployment speaks it.

    def _lag_local(self):
        if self._stale is None:
            with self._stale_lock:
                if self._stale is None:
                    from .admission import StaleStore
                    self._stale = StaleStore(
                        getattr(self.backend, "num_workers", 1),
                        spans=self.spans)
        return self._stale

    def _lag_declare(self, key: int, max_lag: int) -> None:
        if hasattr(self.backend, "declare_lag"):
            self.backend.declare_lag(key, max_lag)
            return
        meta = self._key_meta.get(key)
        if meta is None:
            raise KeyError(f"declare_lag({key}) before init")
        nbytes, dtype = meta
        self._lag_local().declare(
            key, nbytes // np.dtype(dtype).itemsize, dtype, max_lag)

    def _lag_push(self, key: int, worker: int, rnd: int,
                  arr: np.ndarray, wire_bytes: int) -> None:
        if hasattr(self.backend, "push_lag"):
            self.backend.push_lag(key, worker, rnd, arr)
            return
        tgt = self._lag_local().push(key, worker, rnd, arr)
        if self._own_spans:
            self.spans.note_arrival(key, worker, wire_bytes, rnd=tgt)

    def _lag_pull(self, key: int, worker: int, rnd: int,
                  out: np.ndarray, timeout_ms: int) -> int:
        import time
        if hasattr(self.backend, "pull_lag"):
            return int(self.backend.pull_lag(key, worker, rnd, out,
                                             timeout_ms))
        t0 = time.time()
        flags = self._lag_local().pull(key, worker, rnd, out, timeout_ms)
        self._m_merge_wait.observe(time.time() - t0)
        if self._own_spans:
            self.spans.note_serve(key, rnd, t0, time.time() - t0)
        return int(flags)

    def _replica_store(self):
        if self._replica is None:
            with self._replica_lock:
                if self._replica is None:
                    from .plane.replica import ReplicaStore
                    self._replica = ReplicaStore()
        return self._replica

    def act_store(self):
        """This server's activation mailbox (pipeline plane) — also the
        LOCAL take endpoint for a colocated stage driver, so a received
        activation never makes a second hop."""
        if self._acts is None:
            with self._acts_lock:
                if self._acts is None:
                    from ..pipeline.exchange import ActStore
                    self._acts = ActStore()
        return self._acts

    def stats_payload(self) -> dict:
        """The OP_STATS response body: this process's registry snapshot
        plus this server's heartbeat (the shared ServerStats/v1 shape,
        obs/fleet.py). Every field is a read of already-published state
        — no round-blocking, no engine waits — so the scrape answers
        even while the data plane is wedged on a lost pull (the whole
        point of a liveness signal)."""
        from ..obs.fleet import server_stats_payload
        return server_stats_payload(
            time.monotonic() - self._t0_mono, len(self._key_meta),
            requests=self._n_requests,
            queue_depth_fn=(self.backend.queue_depth
                            if hasattr(self.backend, "queue_depth")
                            else None),
            start_ts=self._t0_wall)

    def embed_store(self):
        """This server's sharded embedding row store (OP_EMBED_*,
        server/embed.py) — lazy like the act/param mailboxes. REFUSED
        on a hierarchical-aggregation front (server/hier.py): an
        aggregator's local fold has no row store, and silently passing
        embed ops through would split one table's rows across the
        agg's own upstream sharding — serving rows from the WRONG
        shard's lazy-init values. Point EmbedClient at the plane
        shards directly (docs/embedding.md failure matrix)."""
        if self._embed is None:
            with self._embed_lock:
                if self._embed is None:
                    if getattr(self.backend, "is_local_agg", False):
                        raise RuntimeError(
                            "embed tables cannot ride a hierarchical "
                            "aggregator front (BPS_HIER_AGG): the agg "
                            "tier folds dense gradients and has no row "
                            "store — connect EmbedClient to the plane "
                            "shards (BPS_SERVER_ADDRS), not the agg")
                    from .embed import EmbedRowStore
                    # the dedup-seed hook lets a failover promotion
                    # install the replicated log's push tokens into
                    # THIS server's dedup table — a worker retrying an
                    # acked-at-the-dead-primary push lands here and is
                    # acknowledged without re-applying (exactly-once
                    # across failover, ISSUE 20)
                    self._embed = EmbedRowStore(
                        dedup_seed=self._seed_push_token)
        return self._embed

    def _seed_push_token(self, key: int, token: int) -> None:
        """Mark a push-dedup token as already applied for ``key`` —
        the failover-replay half of ``_apply_push_once``'s contract
        (tokens arrive via the replicated embed log, not the wire)."""
        tok = int(token)
        if not tok:
            return
        ident = (int(key), tok >> 32)
        seq = tok & 0xFFFFFFFF
        with self._push_lock:
            st = self._push_seen.get(ident)
            if st is None:
                st = self._push_seen[ident] = _DedupState()
            if not st.is_applied(seq):
                st.record(seq)
            st.ts = time.time()

    def param_store(self):
        """This server's param mailbox (sharded weight update,
        OP_PARAM_*) — lazy like the act store, so plain deployments
        never allocate it."""
        if self._params is None:
            with self._acts_lock:
                if self._params is None:
                    from ..sharded_update import ParamStore
                    self._params = ParamStore()
        return self._params

    def _pull_dense(self, key, rnd, nbytes, dtype, timeout) -> np.ndarray:
        """Round-blocked engine pull in WIRE dtype — the one transcode
        rule shared by OP_PULL and the striped fetch: a frame dtype
        narrower than the store downcasts on the way out."""
        import time
        t0 = time.time()
        elems = int(nbytes) // np.dtype(dtype).itemsize
        meta = self._key_meta.get(key)
        if meta is not None and meta[1] != dtype:
            store = np.empty(elems, dtype=meta[1])
            self._fb.pull(key, store, round=int(rnd),
                          timeout_ms=int(timeout) or 30000)
            out = store.astype(dtype)
        else:
            out = np.empty(elems, dtype=dtype)
            self._fb.pull(key, out, round=int(rnd),
                          timeout_ms=int(timeout) or 30000)
        # server-side merge wait: sum time + the lag of the slowest
        # worker's push — the transport server's bottleneck signal
        self._m_merge_wait.observe(time.time() - t0)
        if self._own_spans:
            self.spans.note_serve(key, int(rnd), t0, time.time() - t0)
        return out

    _STRIPE_TTL_SECS = 120.0

    def _sweep_stages(self, now: float) -> None:
        """Drop abandoned striping stages (caller holds _stripe_lock).
        A pull stage is only swept once its fetch resolved — sweeping a
        stage whose engine pull is in flight would strand late parts
        waiting on an event nobody will set."""
        if now < self._stripe_sweep_at:
            return
        self._stripe_sweep_at = now + 30.0
        cutoff = now - self._STRIPE_TTL_SECS
        for d in (self._push_stage, self._pull_stage):
            for k in [k for k, st in d.items()
                      if st["t"] < cutoff
                      and ("ev" not in st or st["ev"].is_set())]:
                del d[k]

    def _apply_push_once(self, key: int, rnd: int, apply_fn) -> bool:
        """Run ``apply_fn`` exactly once per dedup token; returns True
        when THIS call applied the payload (False = dedup hit — the
        span ring must not count a retried frame as a second arrival).
        Tokenless pushes (rnd=0: legacy frames, raw clients) apply
        unconditionally. A
        duplicate of an APPLIED seq is acknowledged without re-applying; a
        duplicate racing the original's in-flight apply (conn reset
        mid-sum + instant redial) WAITS for that apply's outcome — ack if
        it succeeded, apply itself if it failed. Applied seqs are exact
        membership (not a high-water mark), so two threads pushing the
        same key through one backend both count even when their frames
        land out of order. The applied mark is recorded only after the
        backend accepted the payload, so a dedup hit can never mask a
        push lost mid-apply (that stalls the round loudly instead)."""
        if not rnd:
            apply_fn()
            return True
        ident = (key, rnd >> 32)
        seq = rnd & 0xFFFFFFFF
        now = time.time()
        with self._push_lock:
            if now >= self._dedup_sweep_at:
                self._dedup_sweep_at = now + self._dedup_ttl / 4
                dead = [k for k, st in self._push_seen.items()
                        if now - st.ts > self._dedup_ttl and not st.claims]
                for k in dead:
                    del self._push_seen[k]
            st = self._push_seen.get(ident)
            if st is None:
                st = self._push_seen[ident] = _DedupState()
            while True:
                if st.is_applied(seq):
                    st.ts = now
                    return False                  # duplicate, already applied
                if seq not in st.claims:
                    st.claims.add(seq)            # we own the apply
                    break
                self._push_cv.wait(1.0)   # original in flight: await outcome
        try:
            apply_fn()
        except BaseException:
            with self._push_lock:
                # retract the claim so the waiting retry (or a later
                # resend) applies it instead
                st.claims.discard(seq)
                self._push_cv.notify_all()
            raise
        with self._push_lock:
            st.record(seq)
            st.ts = time.time()
            st.claims.discard(seq)
            self._push_cv.notify_all()
        return True

    def _serve_conn(self, conn: socket.socket) -> None:
        rholder = [bytearray()]  # reused across this connection's frames
        try:
            while True:
                op, key, rnd, nbytes, timeout, dtype, payload = \
                    _recv_req(conn, rholder)
                if op == OP_CLOSE:
                    conn.sendall(_RSP.pack(ST_OK, 0))
                    return
                self._handle(conn, op, key, rnd, nbytes, timeout, dtype,
                             payload)
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()

    def snapshot(self, path: str, timeout_ms: int = 250) -> int:
        """Best-effort dump of every known key's latest merged value to an
        .npz (the reference has no PS-state checkpoint — server death
        loses the async-mode weights; this closes that gap). Returns the
        number of keys saved. Keys whose pull fails or times out (e.g. a
        sync-mode key with no completed round yet — async pulls return
        immediately) are skipped with a warning; the short per-key
        timeout bounds the stall a sync-mode snapshot can cause.

        Embed tables ride the same file: live rows + versions + metas
        as ``e<key>|…`` entries next to the dense ``k<key>|<dtype>``
        ones (only when the embed store was ever touched — plain
        deployments pay nothing)."""
        embed = (self._embed.snapshot_state()
                 if self._embed is not None else None)
        return snapshot_store(self.backend, list(self._key_meta.items()),
                              path, timeout_ms, embed=embed)

    def restore(self, path: str) -> int:
        """Re-seed the store from a snapshot. NOTE: this server accepts
        connections from construction — to guarantee a reconnecting
        worker's INIT can't land first and pin its own values, restore
        the BACKEND before constructing the transport
        (``restore_snapshot`` + the ``key_meta`` ctor arg, as
        bpslaunch-tpu --server does). Embed ``e<key>|…`` entries (if
        present) repopulate the row store and bump each table's epoch
        past the saved one."""
        meta = restore_snapshot(self.backend, path)
        self._key_meta.update(meta)
        data = np.load(path)
        embed = {n: data[n] for n in data.files if n.startswith("e")}
        if embed:
            self.embed_store().restore_state(embed)
        return len(meta)

    def close(self) -> None:
        self._stop.set()
        if self._embed is not None:
            try:
                self._embed.close()
            except Exception:
                pass
        self._shm.close()
        try:
            self._sock.close()
        except OSError:
            pass
        if self._ipc_sock is not None:
            import os as _os
            try:
                self._ipc_sock.close()
            except OSError:
                pass
            try:
                _os.unlink(self.ipc_path)
            except OSError:
                pass


# ------------------------------------------------------- state snapshots

def snapshot_store(backend, key_meta, path: str,
                   timeout_ms: int = 250, embed=None) -> int:
    """Dump ``key_meta`` (iterable of (key, (nbytes, dtype))) from
    ``backend`` to ``path`` atomically. Entries are named
    ``k<key>|<dtype>`` with raw-byte payloads, so dtypes numpy can't
    round-trip through npz (bfloat16) survive. ``embed`` (optional) is
    an already-rendered ``EmbedRowStore.snapshot_state()`` dict whose
    ``e<key>|…`` entries ride the same npz."""
    import os as _os

    from ..common.logging import get_logger
    arrays = {}
    for key, (nbytes, dtype) in sorted(key_meta):
        buf = np.empty(nbytes // np.dtype(dtype).itemsize, dtype)
        try:
            # round 0 = latest published value
            backend.pull(key, buf, round=0, timeout_ms=timeout_ms)
        except Exception as e:
            get_logger().warning("snapshot: skipping key %d: %s", key, e)
            continue
        arrays[f"k{key}|{dtype}"] = buf.view(np.uint8)
    if embed:
        arrays.update(embed)
    tmp = f"{path}.tmp.npz"
    np.savez(tmp, **arrays)
    _os.replace(tmp, path)         # atomic: readers never see a torn file
    get_logger().info("snapshot: %d keys -> %s", len(arrays), path)
    return len(arrays)


def restore_snapshot(backend, path: str):
    """Re-seed ``backend`` from a snapshot; returns the key→(nbytes,
    dtype) meta restored. Run this BEFORE the transport server starts
    accepting, or a fast-reconnecting worker's INIT can allocate the key
    first and the restored value is silently dropped (server-side init
    is first-wins). Non-dense entries (embed ``e<key>|…``) are left to
    ``PSTransportServer.restore``."""
    from ..common.logging import get_logger
    data = np.load(path)
    meta = {}
    for name in data.files:
        if not name.startswith("k"):
            continue               # embed entries, handled by the caller
        keypart, dtype = name[1:].split("|", 1)
        key = int(keypart)
        arr = np.frombuffer(data[name].tobytes(), np.dtype(dtype))
        backend.init_key(key, arr.nbytes, dtype, init=arr)
        meta[key] = (arr.nbytes, dtype)
    get_logger().info("restore: %d keys <- %s", len(meta), path)
    return meta


# ------------------------------------------------------------------ client

class _Channel:
    """One pooled connection; ``sock`` is None until first use. ``shm``
    is the channel's worker-owned segment for the shared-memory data
    plane (created on demand, grown by replacement)."""

    __slots__ = ("sock", "shm")

    def __init__(self, sock: Optional[socket.socket]) -> None:
        self.sock = sock
        self.shm = None

    @staticmethod
    def _unlink(seg) -> None:
        try:
            seg.unlink()   # name gone; the server's attachment survives
            seg.close()
        except Exception:
            pass

    def ensure_shm(self, nbytes: int):
        if self.shm is None or self.shm.size < nbytes:
            if self.shm is not None:
                self._unlink(self.shm)
            self.shm = _PosixShm(create=True, size=max(nbytes, 1 << 20))
        return self.shm

    def drop_shm(self) -> None:
        if self.shm is not None:
            self._unlink(self.shm)
            self.shm = None


class RemotePSBackend:
    """Worker-side client; same interface as HostPSBackend, keys sharded
    over N transport servers with the same placement hash (reference:
    key→server placement global.cc:628-677).

    Fault tolerance (ours — ps-lite aborts on van failure): a dropped
    connection triggers reconnect-with-backoff for up to
    ``reconnect_secs`` (BPS_RECONNECT_SECS, default 30; 0 disables).
    Recorded ``init_key`` calls are REPLAYED on the fresh connection so a
    restarted server re-learns the key table (values come from its
    snapshot, see BPS_SERVER_SNAPSHOT — without one, async training
    restarts from the replayed init values). Clean recovery is an
    async-PS property: sync rounds reset with the server while the
    worker's round counters don't, so a sync-mode reconnect can stall
    on pulls (documented limitation). Retried pushes carry a
    ``worker_incarnation<<32 | per-key seq`` dedup token: a push whose
    ACK was lost is re-sent but applied exactly once by a surviving
    server, so a sync-mode connection blip cannot double-count this
    worker's gradient in the round. The incarnation id is fresh per
    RemotePSBackend instance, so a RESTARTED worker's pushes are never
    mistaken for its predecessor's. Only a server that itself restarted
    (losing the dedup table) can re-apply a retried push — and that
    path already resets rounds, which async mode absorbs as one
    duplicated delta and sync mode surfaces as the documented stall."""

    def __init__(self, addrs: Sequence[str], hash_fn: str = "djb2",
                 async_mode: bool = False,
                 reconnect_secs: Optional[float] = None,
                 conns_per_shard: Optional[int] = None,
                 nic=None, lazy_dial: bool = False):
        import os as _os
        import queue as _queue
        self._addrs = [a.rsplit(":", 1) for a in addrs]
        # optional emulated-NIC throttle (throttle.Nic) charged for this
        # worker endpoint's traffic across ALL its channels
        self._nic = nic
        self.hash_fn = hash_fn
        from ..common.naming import check_mixed_mode_enabled, placement_from_env
        check_mixed_mode_enabled(hash_fn)
        self._placement = placement_from_env()
        # hash_fn="ring": byte-weighted consistent-hash placement from
        # the server plane (balanced by construction under the
        # exchange's declaration-order contract) instead of the env
        # hash — see HostPSBackend for the full rationale
        self._ring = None
        if hash_fn == "ring" and len(addrs) > 1:
            from .plane.placement import DEFAULT_VNODES, PlacementService
            self._ring = PlacementService(
                len(addrs),
                vnodes=int(self._placement.get("vnodes") or 0)
                or DEFAULT_VNODES)
        self.async_mode = async_mode
        self._dead = False      # set by close(); aborts redial loops
        self.reconnect_secs = (
            float(_os.environ.get("BPS_RECONNECT_SECS", "30"))
            if reconnect_secs is None else reconnect_secs)
        # connection POOL per shard: the transport server handles one
        # request per connection at a time, so a round-blocked PULL would
        # stall every later request on its socket — extra channels let
        # the pipelined exchange push bucket k+1 while bucket k's pull
        # waits on the server's merge (the reference's free-running
        # push/pull loops, core_loops.cc:538-618)
        self._nconns = (int(_os.environ.get("BPS_PS_CONNS", "4"))
                        if conns_per_shard is None else conns_per_shard)
        self._nconns = max(1, self._nconns)
        # connection striping threshold: a logical push/pull at least
        # this large is split over the pool's connections in flight at
        # once (0 = off, the default). Striping targets multi-core
        # hosts where parallel streams buy parallel recv+apply; on a
        # single-core box it measured NEGATIVE (0.99 -> 0.66 GB/s push
        # at 10 Gbps — thread switching with no extra cycles to win),
        # so it is opt-in: BPS_STRIPE_MIN=4194304 is a sane setting for
        # real deployments (docs/performance.md "transport wire speed")
        self._stripe_min = int(_os.environ.get("BPS_STRIPE_MIN", "0"))
        self._stripe_exec = None
        self._stripe_exec_lock = threading.Lock()
        # placement-aware striping (ring mode): one large bucket's
        # stripes live as independent sub-keys on DISTINCT ring
        # successors (PlacementService.place_stripes), so a hot key's
        # traffic spreads across servers instead of saturating its
        # primary's NIC. key -> [(byte off, byte len, subkey)];
        # subkey -> shard index (consulted by _shard before any hash)
        self._stripe_plans: Dict[int, list] = {}
        self._stripe_shards: Dict[int, int] = {}
        # per-key send priority for the two-class wire scheduler
        # (sched.SendScheduler): the exchange assigns reverse-first-use
        # priorities at plan time via set_send_priority
        self._send_prio: Dict[int, int] = {}
        self._rounds: Dict[int, int] = {}
        # push dedup: fresh nonzero 32-bit incarnation id + per-key seq
        # (seq lives in the frame's ``round`` field, unused by pushes)
        self._wid = int.from_bytes(_os.urandom(4), "big") or 1
        self._push_seq: Dict[int, int] = {}
        self._push_seq_lock = threading.Lock()
        self._shard_bytes: Dict[int, int] = {}
        self._placed: set = set()
        # init_key replay log per shard index: key -> args
        self._inits: List[Dict[int, tuple]] = [dict() for _ in addrs]
        # bounded-staleness contract replay log (docs/admission.md):
        # key -> K per shard. A restarted server has an empty StaleStore
        # — without the re-declaration its first post-reconnect push
        # would be rejected and the worker's lag budget silently lost
        self._lag_decls: List[Dict[int, int]] = [dict() for _ in addrs]
        # embed-table declaration replay log (OP_EMBED_INIT is
        # idempotent first-wins, so replaying into a restarted server
        # re-declares the table; its ROWS come from lazy re-init +
        # whatever pushes land after — the same async-recovery
        # semantics as the dense store without a snapshot)
        self._embed_inits: List[Dict[int, bytes]] = [dict() for _ in addrs]
        # DEDICATED telemetry channel per shard (OP_STATS, obs/fleet):
        # scrapes must not draw from the data-plane pools — when every
        # pooled channel is parked on a round-blocked pull (the wedged
        # state the fleet plane exists to observe), a pool-queued
        # scrape would block behind exactly the stall it should report
        self._stats_chans: List[Optional[_Channel]] = [None] * len(addrs)
        self._stats_locks = [threading.Lock() for _ in addrs]
        self._pools: List[_queue.Queue] = []
        for i in range(len(addrs)):
            pool = _queue.Queue()
            if lazy_dial:
                # plane-managed shard clients (docs/elasticity.md): an
                # elastic REPLACEMENT joins a fleet that may already
                # have a dead shard — construction must succeed and the
                # first op's connection error drive the plane's
                # failover, not a constructor crash. Plain deployments
                # keep the eager dial (a typo'd addr fails at startup).
                pool.put(_Channel(None))
            else:
                pool.put(_Channel(self._dial(i)))  # eager: validate addr
            for _ in range(self._nconns - 1):
                pool.put(_Channel(None))        # dialed on first use
            self._pools.append(pool)
        # shared-memory data plane: colocated shards only (the reference
        # gates its shm path the same way — BYTEPS_ENABLE_IPC colocated
        # deployments)
        shm_on = _os.environ.get("BPS_ENABLE_SHM", "0") not in ("0", "",
                                                                "false")
        self._shm_shards = [
            shm_on and host in ("unix", "127.0.0.1", "localhost")
            for host, _ in self._addrs]

    def _dial(self, i: int) -> socket.socket:
        s = self._dial_raw(i)
        if self._nic is not None:
            from .throttle import ThrottledSocket
            s = ThrottledSocket(s, self._nic)
        return s

    def _dial_raw(self, i: int) -> socket.socket:
        host, port = self._addrs[i]
        if host == "unix":                 # explicit "unix:/path.sock"
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            _bump_bufs(s)
            s.connect(port)
            return s
        if _ipc_enabled() and host in ("127.0.0.1", "localhost"):
            # colocated server: auto-upgrade to its Unix-domain listener
            # (path derived from the TCP port; fall back to TCP when the
            # server predates the knob or runs elsewhere)
            import os as _os
            path = _ipc_path(int(port))
            if _os.path.exists(path):
                s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                _bump_bufs(s)
                try:
                    s.connect(path)
                    return s
                except OSError:
                    s.close()
        s = socket.create_connection((host, int(port)))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    def _shard(self, key: int) -> int:
        s = self._stripe_shards.get(key)
        if s is not None:            # striping sub-key: pinned at init
            return s
        if self._ring is not None:
            try:
                return self._ring.shard_of(key)
            except KeyError:
                # pre-init op: ring-primary routing only — recording a
                # zero-weight assignment here would poison the byte-
                # weighted balance and diverge placement across workers
                # (see HostPSBackend._shard_index)
                return self._ring.ring.lookup(key)
        return place_key(key, len(self._pools), self.hash_fn,
                         **self._placement)

    def _reconnect(self, i: int, ch: "_Channel", deadline: float) -> None:
        """Redial ``ch`` on shard ``i`` with backoff until ``deadline``,
        then replay the shard's init_key log (a restarted server has an
        empty key table; its values come from the snapshot, which restore
        seeds BEFORE accepting — so replayed inits are no-ops there;
        several channels replaying is harmless for the same reason).
        Raises ConnectionError when the budget runs out."""
        import time as _time

        from ..common.logging import get_logger
        from ..obs.metrics import get_registry
        get_registry().counter("transport/reconnects").inc()
        delay = 0.1
        while True:
            if self._dead:
                raise ConnectionError(
                    f"PS backend closed while reconnecting to "
                    f"{':'.join(self._addrs[i])}")
            try:
                old_sock = ch.sock
                ch.sock = self._dial(i)
                if old_sock is not None:    # don't leak one fd per retry
                    try:
                        old_sock.close()
                    except OSError:
                        pass
                break
            except OSError as e:
                if _time.time() + delay > deadline:
                    raise ConnectionError(
                        f"PS server {':'.join(self._addrs[i])} unreachable "
                        f"for {self.reconnect_secs:.0f}s: {e}") from e
                _time.sleep(delay)
                delay = min(delay * 2, 2.0)
        get_logger().warning("reconnected to PS server %s; replaying %d "
                             "key inits", ":".join(self._addrs[i]),
                             len(self._inits[i]))
        for args in self._inits[i].values():
            self._send_init(ch.sock, *args)
        # replay the K-lag contract after the inits (declare_lag needs
        # the key's meta present server-side)
        for k, lag in self._lag_decls[i].items():
            self._roundtrip(ch.sock, OP_LAG_DECL, k, int(lag), 0, 0,
                            "uint8", None)
        # replay embed-table declarations (idempotent first-wins)
        for k, body in self._embed_inits[i].items():
            self._roundtrip(ch.sock, OP_EMBED_INIT, k, 0, 0, 0,
                            "uint8", memoryview(body))

    def _send_init(self, sock, key, nbytes, dtype, init, compression,
                   fused=False):
        if compression:
            from ..ops.compression.host import serialize_kwargs
            self._roundtrip(sock, OP_INIT_C, key, 0, nbytes, 0, dtype,
                            memoryview(serialize_kwargs(compression)))
        else:
            payload = None if init is None else _as_bytes(init)
            self._roundtrip(sock, OP_INIT, key, 1 if fused else 0,
                            nbytes, 0, dtype, payload)

    @staticmethod
    def _roundtrip(sock, op, key, rnd, nbytes, timeout_ms, dtype, payload,
                   recv_into=None):
        _send_req(sock, op, key, rnd, nbytes, timeout_ms, dtype, payload)
        status, rbytes = _RSP.unpack(_recv_exact(sock, _RSP.size))
        if (recv_into is not None and status == ST_OK
                and rbytes == len(recv_into)):
            # zero-copy dense pull: the payload lands straight in the
            # caller's preallocated buffer
            _recv_exact_into(sock, recv_into)
            return memoryview(b"")
        data = _recv_exact(sock, rbytes) if rbytes else memoryview(b"")
        if status == ST_TIMEOUT:
            raise _ServerTimeout(bytes(data).decode() or
                                 f"pull({key}) timed out")
        if status == ST_GONE:
            # server announced shutdown mid-request — treat like a dropped
            # connection so _rpc's reconnect path takes over
            raise ConnectionError(bytes(data).decode() or "server gone")
        if status != ST_OK:
            raise RuntimeError(f"PS server rejected key={key} op={op}: "
                               f"{bytes(data).decode()!r}")
        return data

    def _roundtrip_with_retry(self, i: int, ch: "_Channel", op, key, rnd,
                              nbytes, timeout_ms, dtype, payload,
                              recv_into=None):
        """One roundtrip on ``ch``, with the reconnect policy: redials
        draw on ONE shared budget because the retry itself can land on
        a still-dying server (GONE frames)."""
        import time as _time
        try:
            if ch.sock is None:          # lazily-dialed pool channel
                ch.sock = self._dial(i)
            return self._roundtrip(ch.sock, op, key, rnd, nbytes,
                                   timeout_ms, dtype, payload,
                                   recv_into=recv_into)
        except _ServerTimeout:
            # an APPLICATION reply on a healthy connection — and
            # TimeoutError subclasses OSError, so without this explicit
            # re-raise the reconnect path below would swallow every
            # server-side pull timeout into a redial-and-resend loop for
            # the whole reconnect budget. The OS's ETIMEDOUT (a real
            # link failure) deliberately still takes the reconnect path.
            raise
        except (ConnectionError, OSError):
            if self.reconnect_secs <= 0:
                raise
            from ..obs.metrics import get_registry
            deadline = _time.time() + self.reconnect_secs
            while True:
                try:
                    self._reconnect(i, ch, deadline)
                    # the request is re-sent whole on the fresh channel
                    # (push dedup keeps it exactly-once server-side)
                    get_registry().counter("transport/resends").inc()
                    return self._roundtrip(ch.sock, op, key, rnd, nbytes,
                                           timeout_ms, dtype, payload,
                                           recv_into=recv_into)
                except _ServerTimeout:
                    raise
                except (ConnectionError, OSError):
                    if _time.time() >= deadline:
                        raise
                    _time.sleep(0.2)

    # payload-bearing ops the wire scheduler gates (the bandwidth
    # class; OP_ACT_PUSH is the latency class — see server/admission.py).
    # OP_REPL_PUT is included: a replication forward-log upload is a
    # merged-round-sized payload — unscheduled it would saturate the
    # NIC outside the credit and nothing could overtake it
    _SCHED_GRAD_OPS = frozenset({OP_PUSH, OP_PUSH_C, OP_PUSH_RS,
                                 OP_PUSH_PART, OP_PUSH_F, OP_REPL_PUT,
                                 OP_PUSH_LAG, OP_EMBED_PUSH,
                                 OP_EMBED_REPL})

    def _rpc(self, op: int, key: int, rnd: int, nbytes: int,
             timeout_ms: int, dtype: str, payload: Optional[memoryview],
             pull_into: Optional[np.ndarray] = None) -> bytes:
        # two-class wire admission (BPS_SCHEDULING_CREDIT): payload
        # frames queue in (priority desc, key asc) order behind the
        # byte credit, so a small CLASS_ACT frame overtakes a queued
        # gradient burst. Credit is held across the frame's roundtrip
        # (send + ack) — the host-side analogue of the reference's
        # ack-released scheduling credit. Disabled (credit 0) this is
        # two dict lookups.
        ticket = scheduler = None
        if payload is not None:
            from . import admission as _sched
            scheduler = _sched.send_scheduler()
            if scheduler is not None:
                plen = (sum(len(p) for p in payload)
                        if isinstance(payload, (tuple, list))
                        else len(payload))
                if op == OP_ACT_PUSH:
                    ticket = scheduler.acquire(_sched.CLASS_ACT, 0, key,
                                               plen)
                elif op == OP_PARAM_PUT:
                    # sharded-update param frames are the latency class
                    # too — they gate the next step's forward — with
                    # next-step first-use priority among themselves
                    # (set_send_priority at sharded-plan time)
                    ticket = scheduler.acquire(
                        _sched.CLASS_ACT, self._send_prio.get(key, 0),
                        key, plen)
                elif op in self._SCHED_GRAD_OPS:
                    ticket = scheduler.acquire(
                        _sched.CLASS_GRAD, self._send_prio.get(key, 0),
                        key, plen)
        try:
            return self._rpc_unscheduled(op, key, rnd, nbytes,
                                         timeout_ms, dtype, payload,
                                         pull_into=pull_into)
        finally:
            if ticket is not None:
                scheduler.release(ticket)

    def _rpc_unscheduled(self, op, key, rnd, nbytes, timeout_ms, dtype,
                         payload, pull_into=None) -> bytes:
        i = self._shard(key)
        ch = self._pools[i].get()        # blocks while all channels busy
        try:
            recv_into = None
            if (pull_into is not None
                    and pull_into.flags["C_CONTIGUOUS"]):
                try:                     # writable byte view of the
                    recv_into = memoryview(pull_into).cast("B")
                except (ValueError, TypeError):   # bfloat16 etc.
                    recv_into = memoryview(pull_into.view(np.uint8))
            data = self._roundtrip_with_retry(i, ch, op, key, rnd, nbytes,
                                              timeout_ms, dtype, payload,
                                              recv_into=recv_into)
            if pull_into is not None:
                if len(data):            # non-zero-copy fallback path
                    np.copyto(pull_into,
                              np.frombuffer(data, dtype=pull_into.dtype)
                              .reshape(pull_into.shape))
                return b""          # dense pulls land in pull_into; don't
                                    # re-copy megabytes for a discarded value
            return bytes(data)
        finally:
            self._pools[i].put(ch)   # even with a dead sock: keep the pool
                                     # size invariant; next user redials

    def init_key(self, key: int, nbytes: int, dtype: str = "float32",
                 init: Optional[np.ndarray] = None,
                 compression: Optional[Dict[str, str]] = None,
                 fused: bool = False) -> None:
        if self._ring is not None:
            self._ring.place(key, nbytes)    # byte-weighted, idempotent
        if compression:
            from ..ops.compression.host import serialize_kwargs
            self._rpc(OP_INIT_C, key, 0, nbytes, 0, dtype,
                      memoryview(serialize_kwargs(compression)))
        else:
            payload = None if init is None else _as_bytes(init)
            # OP_INIT rnd bit 0 = fused-managed declaration (the
            # compression plane's plan-time eligibility): the server
            # hands the key's rounds to its homogeneous fused store
            self._rpc(OP_INIT, key, 1 if fused else 0, nbytes, 0, dtype,
                      payload)
        # record for replay after a reconnect (restarted server has an
        # empty key table) — only once ACCEPTED, or a rejected conflicting
        # re-declaration would poison the replay log; keep a copy of init
        # (the caller may mutate it). The fused flag replays too — a
        # restarted server must re-manage the key, not silently fall
        # back to dense decodes.
        i = self._shard(key)
        self._inits[i][key] = (key, nbytes, dtype,
                               None if init is None else np.array(init),
                               dict(compression) if compression else None,
                               bool(fused))
        # count only after the server accepted, once per key (re-inits are
        # no-ops server-side — don't skew the load stats)
        if key not in self._placed:
            self._placed.add(key)
            from ..common.naming import log_key_placement
            log_key_placement(key, nbytes, i, self._shard_bytes,
                              self.hash_fn)
        self._plan_stripes(key, nbytes, dtype, init, compression)

    # striping sub-keys ride bits 48+ of the u64 wire key — disjoint
    # from gradient keys (decl<<16|bucket) and the activation channel
    # space (bit 40)
    @staticmethod
    def _stripe_subkey(key: int, part: int) -> int:
        return key | ((part + 1) << 48)

    def _plan_stripes(self, key: int, nbytes: int, dtype: str,
                      init, compression) -> None:
        """Placement-aware striping (ring mode): init each stripe of a
        large key as its own sub-key on a DISTINCT ring successor
        (``PlacementService.place_stripes``), so later push/pull of the
        key fans its bytes over several servers' NICs instead of one
        shard's connection pool. Dense ops of the key (round queries,
        fused/compressed frames — whose payloads are not
        range-separable) keep routing to the primary, so the plan only
        engages for plain dense keys."""
        if (self._ring is None or compression or key in self._stripe_plans
                or key >= (1 << 40)):    # never re-stripe sub/act keys
            return
        # the fused compression plane is level-per-ROUND: level-0 rounds
        # take the plain push/pull path, level>0 rounds push_fused to
        # the key's primary — striping only the dense rounds would
        # split one key's round counters across two stores and wedge
        # the next pull. A compress-managed deployment keeps
        # single-shard routing (codec payloads are not range-separable).
        import os as _os

        from ..common.global_state import GlobalState
        comp = (GlobalState.get().config.compress
                if GlobalState.initialized()
                else (_os.environ.get("BPS_COMPRESS", "none")
                      or "none").lower())
        if comp not in ("", "none"):
            return
        ranges = self._stripe_ranges(int(nbytes))
        if not ranges:
            return
        shards = self._ring.place_stripes(key, len(ranges))
        item = np.dtype(dtype).itemsize
        flat = (None if init is None
                else np.ascontiguousarray(init).reshape(-1))
        plan = []
        for j, (off, ln) in enumerate(ranges):
            skey = self._stripe_subkey(key, j)
            self._stripe_shards[skey] = shards[j]
            part_init = (None if flat is None
                         else flat[off // item:(off + ln) // item])
            payload = None if part_init is None else _as_bytes(part_init)
            self._rpc(OP_INIT, skey, 0, ln, 0, dtype, payload)
            self._inits[shards[j]][skey] = (
                skey, ln, dtype,
                None if part_init is None else np.array(part_init), None)
            plan.append((off, ln, skey))
        self._stripe_plans[key] = plan

    def set_send_priority(self, key: int, prio: int) -> None:
        """Send-scheduler priority for ``key``'s frames (higher = sent
        earlier under BPS_SCHEDULING_CREDIT). The exchange assigns
        reverse-first-use bucket priorities here at plan time; stripes
        of the key inherit it."""
        self._send_prio[key] = int(prio)
        for _, _, skey in self._stripe_plans.get(key, ()):
            self._send_prio[skey] = int(prio)

    @property
    def incarnation(self) -> int:
        """This client's push-dedup incarnation id — the worker id the
        server's span ring records per arrival, and therefore the id a
        watchtower incident blames. Surfaced so a driver (the ps_watch
        bench) can map a blamed id back to a fleet role."""
        return self._wid

    def _push_token(self, key: int) -> int:
        with self._push_seq_lock:
            seq = self._push_seq.get(key, 0) + 1
            if seq > 0xFFFFFFFF:
                # seq field exhausted: roll to a fresh incarnation (the
                # server tracks (incarnation, seq) pairs, so this resets
                # dedup cleanly instead of wrapping into "already seen"
                # territory where every push would be dropped as a retry)
                import os as _os
                self._wid = int.from_bytes(_os.urandom(4), "big") or 1
                self._push_seq.clear()
                seq = 1
            self._push_seq[key] = seq
        return (self._wid << 32) | seq

    def _shm_rpc(self, op: int, key: int, rnd: int,
                 arr: Optional[np.ndarray] = None,
                 out: Optional[np.ndarray] = None,
                 timeout_ms: int = 30000) -> None:
        """Data-plane op through the channel's shared segment: only the
        (name, length) addressing crosses the socket. Reconnect uses
        the same single budget as ``_rpc``; the segment survives
        redials (it is addressed by name per frame)."""
        i = self._shard(key)
        ch = self._pools[i].get()
        try:
            nbytes = arr.nbytes if arr is not None else out.nbytes
            try:
                seg = ch.ensure_shm(nbytes)
                if arr is not None:
                    seg.buf[:nbytes] = _as_bytes(arr)
            except OSError as e:
                # client-side shm_open/ftruncate failure (small or full
                # /dev/shm): same degradation as a server-side attach
                # rejection, not a hard op failure
                raise RuntimeError(f"client-side shm unavailable: {e}") from e
            dtype = str(arr.dtype if arr is not None else out.dtype)
            self._roundtrip_with_retry(i, ch, op, key, rnd, nbytes,
                                       timeout_ms, dtype,
                                       memoryview(seg.name.encode()))
            if out is not None:
                flat = np.frombuffer(seg.buf[:nbytes], dtype=out.dtype)
                np.copyto(out, flat.reshape(out.shape))
        finally:
            self._pools[i].put(ch)

    def _shm_disable(self, i: int, err: Exception) -> None:
        """No shared /dev/shm with the server (SSH-tunneled loopback,
        separate containers): degrade this shard to the socket path
        like the UDS auto-upgrade does, instead of hard-failing every
        op on a mis-set env var."""
        from ..common.logging import get_logger
        self._shm_shards[i] = False
        get_logger().warning(
            "BPS_ENABLE_SHM: server %s cannot attach this worker's shm "
            "segment (%s) — no shared /dev/shm? falling back to the "
            "socket data plane for this shard",
            ":".join(self._addrs[i]), err)

    def _stripe_ranges(self, nbytes: int):
        """[(offset, length)] for a striped op, or None when striping is
        off / not worth it. Parts are element-aligned 16-byte multiples
        so a part boundary can never split a wire element."""
        if self._stripe_min <= 0 or self._nconns < 2:
            return None
        if nbytes < max(self._stripe_min, 2 * (256 << 10)):
            return None
        if nbytes >= (1 << 32):
            return None     # _PART offsets are u32; huge ops go dense
        nparts = min(self._nconns, (nbytes + self._stripe_min - 1)
                     // self._stripe_min)
        if nparts < 2:
            return None
        step = ((nbytes + nparts - 1) // nparts + 15) & ~15
        return [(off, min(step, nbytes - off))
                for off in range(0, nbytes, step)]

    def _stripe_pool_get(self):
        with self._stripe_exec_lock:     # two racing creators would
            if self._stripe_exec is None:  # leak the loser's threads
                from concurrent.futures import ThreadPoolExecutor
                self._stripe_exec = ThreadPoolExecutor(
                    max_workers=self._nconns,
                    thread_name_prefix="bps-stripe")
            return self._stripe_exec

    def _stripe_run(self, fn, items) -> None:
        """Run one striped op's parts concurrently and wait for ALL of
        them before surfacing the first error — an early raise would
        let a retry attempt race its own stragglers on the server's
        shared (key, round) stage."""
        futs = [self._stripe_pool_get().submit(fn, it) for it in items]
        first = None
        for f in futs:
            try:
                f.result()
            except Exception as e:  # noqa: BLE001 — re-raised below
                if first is None:
                    first = e
        if first is not None:
            raise first

    def push(self, key: int, data: np.ndarray) -> None:
        plan = self._stripe_plans.get(key)
        if plan is not None:
            # placement-aware stripes: each part is an ordinary dense
            # push of its own sub-key on its own shard — full framing,
            # dedup, and reconnect per part, flying concurrently
            view = _as_bytes(data)
            dtype = str(data.dtype)

            def push_part(args):
                off, ln, skey = args
                self._rpc(OP_PUSH, skey, self._push_token(skey), 0, 0,
                          dtype, view[off:off + ln])

            self._stripe_run(push_part, plan)
            return
        tok = self._push_token(key)
        i = self._shard(key)
        if self._shm_shards[i]:
            try:
                self._shm_rpc(OP_PUSH_SHM, key, tok, arr=data)
                return
            except RuntimeError as e:     # server rejected: can't attach
                self._shm_disable(i, e)   # same token: exactly-once holds
        view = _as_bytes(data)
        ranges = self._stripe_ranges(len(view))
        if ranges is None:
            self._rpc(OP_PUSH, key, tok, 0, 0, str(data.dtype), view)
            return
        # striped push: the parts fly on separate pooled connections
        # concurrently; the server reassembles per (key, token) and
        # applies exactly once (dedup rides the shared token)
        dtype = str(data.dtype)
        nparts = len(ranges)

        def send_part(args):
            pi, (off, ln) = args
            self._rpc(OP_PUSH_PART, key, tok, len(view), 0, dtype,
                      (_PART.pack(off, ln, pi, nparts, 0),
                       view[off:off + ln]))

        self._stripe_run(send_part, list(enumerate(ranges)))

    # Round-blocked pulls wait on the server in SHORT slices and the
    # client loops to its own deadline: a severed connection then costs
    # at most one slice instead of silently re-arming the full wait on
    # every reconnect — without this, steady connection churn could
    # extend a "30 s" pull indefinitely (observed as livelock under
    # fault injection, tests/test_fault_injection.py).
    _PULL_SLICE_MS = 2000

    def _sliced_pull(self, attempt, timeout_ms: int, descr: str):
        """Run ``attempt(slice_ms)`` until it succeeds or ONE global
        deadline expires; server-side waits are per-slice."""
        import time as _time
        deadline = _time.time() + timeout_ms / 1e3
        while True:
            left_ms = max(1, int((deadline - _time.time()) * 1e3))
            try:
                return attempt(min(self._PULL_SLICE_MS, left_ms))
            except TimeoutError:
                if _time.time() >= deadline:
                    raise TimeoutError(
                        f"{descr} timed out after {timeout_ms}ms "
                        f"(sliced waits)") from None

    def pull(self, key: int, out: np.ndarray, round: int = 0,
             timeout_ms: Optional[int] = None) -> None:
        if timeout_ms is None:
            # the default is a liveness diagnostic, not a correctness
            # bound — BPS_PULL_TIMEOUT_MS lets contended CI boxes (where
            # a peer's first round can sit behind interpreter startup
            # for tens of seconds) widen it without touching prod
            timeout_ms = int(os.environ.get(
                "BPS_PULL_TIMEOUT_MS", "30000") or 30000)
        plan = self._stripe_plans.get(key)
        if plan is not None and not out.flags["C_CONTIGUOUS"]:
            # a striped key's data lives ONLY in the sub-keys — falling
            # through to the dense base key (which never sees a push)
            # would round-block forever. Stage through a contiguous
            # buffer instead; the extra copy is the price of a strided
            # caller, not a wrong answer.
            staged = np.empty(out.shape, out.dtype)
            self.pull(key, staged, round=round, timeout_ms=timeout_ms)
            np.copyto(out, staged)
            return
        if plan is not None:
            # placement-aware stripes: one dense pull per sub-key on
            # its own shard, each landing straight in out's byte range
            # (zero-copy scatter). Every worker pushes every stripe
            # every round, so the sub-keys' server rounds advance in
            # lockstep with the logical key's round
            flat = out.view(np.uint8).reshape(-1)
            dtype = str(out.dtype)

            def pull_part(args):
                def one(slice_ms):
                    off, ln, skey = args
                    self._rpc(OP_PULL, skey, round, ln, slice_ms, dtype,
                              None, pull_into=flat[off:off + ln])
                self._sliced_pull(one, timeout_ms,
                                  f"pull({key}) stripe round={round}")

            self._stripe_run(pull_part, plan)
            return

        def attempt(slice_ms: int) -> None:
            i = self._shard(key)
            if self._shm_shards[i]:
                try:
                    self._shm_rpc(OP_PULL_SHM, key, round, out=out,
                                  timeout_ms=slice_ms)
                    return
                except RuntimeError as e:   # server cannot attach our shm
                    self._shm_disable(i, e)
            ranges = (self._stripe_ranges(out.nbytes)
                      if out.flags["C_CONTIGUOUS"] else None)
            if ranges is None:
                self._rpc(OP_PULL, key, round, out.nbytes, slice_ms,
                          str(out.dtype), None, pull_into=out)
                return
            # striped pull: each part round-blocks on the SAME (key,
            # round, nonce) server stage (one engine pull feeds all of
            # THIS op's parts) and its slice lands straight in `out`
            # (zero-copy scatter). The nonce is fresh per attempt so a
            # retry can never race its own (or a concurrent puller's)
            # stragglers on a shared stage — the abandoned stage is
            # TTL-swept server-side
            flat = out.view(np.uint8).reshape(-1)
            nparts = len(ranges)
            dtype = str(out.dtype)
            import os as _os
            nonce = int.from_bytes(_os.urandom(8), "big")

            def pull_part(args):
                pi, (off, ln) = args
                self._rpc(OP_PULL_PART, key, round, out.nbytes, slice_ms,
                          dtype, (_PART.pack(off, ln, pi, nparts, nonce),),
                          pull_into=flat[off:off + ln])

            self._stripe_run(pull_part, list(enumerate(ranges)))

        self._sliced_pull(attempt, timeout_ms,
                          f"pull({key}) round={round}")

    # Fleet telemetry client (byteps_tpu.obs.fleet): scrape EVERY
    # shard's registry snapshot + heartbeat over OP_STATS — placement-
    # independent (the scrape is about the servers, not any key),
    # never credit-gated (no payload = nothing for the send scheduler
    # to gate), and on a dedicated per-shard channel so a wedged data
    # plane cannot starve telemetry.

    def _stats_rpc(self, i: int, op: int,
                   timeout_ms: int) -> Tuple[dict, float, float]:
        """One telemetry roundtrip (OP_STATS/OP_TRACE) on shard ``i``'s
        dedicated channel; returns (payload, t_send, t_recv) — the
        send/recv wall stamps bracket the roundtrip for the NTP-style
        clock-offset midpoint (obs.spans.ClockEstimator)."""
        import json as _json

        # client-side SOCKET timeout, not just the frame field: a
        # black-holed host (power loss, partition without an RST) —
        # exactly the silent death the fleet plane detects — would
        # otherwise block this recv forever and starve every shard's
        # scrape behind it. socket.timeout is an OSError: it takes the
        # same one-redial-then-fail path as a severed connection.
        sock_to = timeout_ms / 1e3 + 1.0
        with self._stats_locks[i]:
            ch = self._stats_chans[i]
            if ch is None:
                ch = self._stats_chans[i] = _Channel(None)
            try:
                if ch.sock is None:
                    ch.sock = self._dial(i)
                ch.sock.settimeout(sock_to)
                t_send = time.time()
                data = self._roundtrip(ch.sock, op, 0, 0, 0,
                                       timeout_ms, "uint8", None)
                t_recv = time.time()
            except (ConnectionError, OSError):
                # ONE redial, then fail loudly: a scrape is cheap and
                # periodic — burning the full reconnect budget here
                # would hold the scrape thread through exactly the
                # outage it should be reporting as staleness
                old, ch.sock = ch.sock, None
                if old is not None:
                    try:
                        old.close()
                    except OSError:
                        pass
                ch.sock = self._dial(i)
                ch.sock.settimeout(sock_to)
                t_send = time.time()
                data = self._roundtrip(ch.sock, op, 0, 0, 0,
                                       timeout_ms, "uint8", None)
                t_recv = time.time()
            return _json.loads(bytes(data).decode()), t_send, t_recv

    def stats_shard(self, i: int, timeout_ms: int = 5000) -> dict:
        """One shard's OP_STATS scrape; raises on an unreachable shard
        (the aggregate ``stats()`` folds that into an error entry —
        the scraper's staleness machinery owns the retry cadence)."""
        return self._stats_rpc(i, OP_STATS, timeout_ms)[0]

    def stats(self, timeout_ms: int = 5000) -> Dict[str, dict]:
        """{shard label: OP_STATS payload} for EVERY shard. Unreachable
        shards become ``{"error": …}`` entries instead of raising — the
        fleet scraper turns those into stale scrape-age + ``up=0``,
        never an exception on its control thread."""
        out: Dict[str, dict] = {}
        for i in range(len(self._addrs)):
            try:
                out[f"s{i}"] = self.stats_shard(i, timeout_ms)
            except Exception as e:   # noqa: BLE001 — per-shard isolation
                out[f"s{i}"] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def trace_shard(self, i: int,
                    timeout_ms: int = 5000) -> Tuple[dict, float, float]:
        """One shard's OP_TRACE scrape on the dedicated stats channel:
        (ServerSpans payload, t_send, t_recv). The wall stamps bracket
        the roundtrip — the clock-offset probe's raw material."""
        return self._stats_rpc(i, OP_TRACE, timeout_ms)

    def trace(self, timeout_ms: int = 5000) -> Dict[str, dict]:
        """{shard label: {"payload", "t_send", "t_recv"}} for every
        shard (``{"error": …}`` for unreachable ones) — the causal
        span + clock-alignment scrape the fleet scraper drives."""
        out: Dict[str, dict] = {}
        for i in range(len(self._addrs)):
            try:
                p, t0, t1 = self.trace_shard(i, timeout_ms)
                out[f"s{i}"] = {"payload": p, "t_send": t0, "t_recv": t1}
            except Exception as e:   # noqa: BLE001 — per-shard isolation
                out[f"s{i}"] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def round(self, key: int) -> int:
        """The server's latest completed round for ``key`` (see
        HostPSBackend.round — the elastic-rejoin resync point). A
        striped key reports the slowest stripe's round — the only
        round every stripe is guaranteed to have completed."""
        plan = self._stripe_plans.get(key)
        if plan is not None:
            return min(
                struct.unpack("!Q", self._rpc(OP_ROUND, skey, 0, 0, 0,
                                              "uint8", None))[0]
                for _, _, skey in plan)
        data = self._rpc(OP_ROUND, key, 0, 0, 0, "uint8", None)
        return struct.unpack("!Q", data)[0]

    # Bounded-staleness client (server/admission.py StaleStore,
    # docs/admission.md): the K-lag contract is declared once per key,
    # pushes/pulls carry (worker, round) packed in the frame's round
    # field, and a pull's reply leads with one verdict byte
    # (LAG_COMPLETE / LAG_STALE / LAG_BARRIER) before the dense sum.

    def declare_lag(self, key: int, max_lag: int) -> None:
        """Declare ``key``'s staleness bound K; recorded for replay so
        a restarted server relearns the contract on reconnect."""
        self._rpc(OP_LAG_DECL, key, int(max_lag), 0, 0, "uint8", None)
        self._lag_decls[self._shard(key)][key] = int(max_lag)

    def push_lag(self, key: int, worker: int, rnd: int,
                 data: np.ndarray) -> None:
        """Versioned push into ``key``'s round ``rnd``. The packed
        round field doubles as the server's dedup token — ident
        (key, worker<<16), seq rnd — so a reconnect retry of the same
        (worker, round) folds exactly once."""
        packed = (int(worker) << 48) | (int(rnd) & _LAG_ROUND_MASK)
        self._rpc(OP_PUSH_LAG, key, packed, 0, 0, str(data.dtype),
                  _as_bytes(data))

    def pull_lag(self, key: int, worker: int, rnd: int, out: np.ndarray,
                 timeout_ms: int = 30000) -> int:
        """Pull round ``rnd``'s published sum; returns the verdict
        flags. Barrier waits are sliced like dense pulls so connection
        churn cannot silently re-arm the full server-side wait."""
        packed = (int(worker) << 48) | (int(rnd) & _LAG_ROUND_MASK)

        def attempt(slice_ms: int) -> int:
            data = self._rpc(OP_PULL_LAG, key, packed, out.nbytes,
                             slice_ms, str(out.dtype), None)
            np.copyto(out, np.frombuffer(data[1:], dtype=out.dtype)
                      .reshape(out.shape))
            return data[0]

        return self._sliced_pull(attempt, timeout_ms,
                                 f"pull_lag({key}) round={rnd}")

    # Replica-log client (server plane primary-backup replication,
    # docs/server-plane.md): the plane backend wraps SINGLE-address
    # RemotePSBackend clients as shard handles, so these ops always
    # target this client's one server — the shard the plane chose as
    # the key's backup.

    def repl_put(self, key: int, round: int, payload) -> None:
        """Forward-log a completed round's merged bytes (idempotent
        last-wins: every worker logs the identical published merge)."""
        self._rpc(OP_REPL_PUT, key, int(round), 0, 0, "uint8",
                  memoryview(bytes(payload)))

    def repl_get(self, key: int, round: int) -> Optional[bytes]:
        """The logged bytes for ``round``, or None when never logged /
        aged out of the retention window."""
        data = self._rpc(OP_REPL_GET, key, int(round), 0, 0, "uint8",
                         None)
        if not data or data[:1] == b"\x00":
            return None
        return data[1:]

    def repl_base(self, key: int) -> int:
        """Highest logged round (0 = nothing logged) — the round base a
        promoted shard re-counts from after failover."""
        data = self._rpc(OP_REPL_BASE, key, 0, 0, 0, "uint8", None)
        return struct.unpack("!Q", data)[0]

    def push_bytes(self, key: int, payload) -> None:
        """Compressed push: ship the codec payload as-is; the server
        decompresses and dense-sums (wire bytes stay compressed — the
        bandwidth win the reference's inter-node compression is for)."""
        self._rpc(OP_PUSH_C, key, self._push_token(key), 0, 0, "uint8",
                  memoryview(payload))

    def push_fused(self, key: int, payload) -> None:
        """Fused-plane push (byteps_tpu.compress): self-describing codec
        payload, decoded on arrival by the server; dedup-tokenized like
        any push so a retried frame is applied exactly once."""
        self._rpc(OP_PUSH_F, key, self._push_token(key), 0, 0, "uint8",
                  memoryview(payload))

    def pull_fused(self, key: int, nbytes: int, dtype: str, codec: int,
                   round: int = 0, timeout_ms: int = 30000,
                   div: Optional[int] = None) -> bytes:
        """Fused-plane pull: the merged round encoded server-side at
        ``codec`` (the level this worker's decision trace pinned for
        the round) — wire bytes stay compressed in BOTH directions.
        The frame's payload carries (codec:u8 | topk div:u16le) so the
        server's re-encode honors this worker's keep fraction."""
        from ..compress.wire import TOPK_DIV
        payload = bytes((int(codec),)) + struct.pack(
            "<H", int(div) if div else TOPK_DIV)
        return self._sliced_pull(
            lambda slice_ms: self._rpc(
                OP_PULL_F, key, round, int(nbytes), slice_ms, dtype,
                payload),
            timeout_ms, f"pull_fused({key}) round={round}")

    # Activation-plane client (byteps_tpu.pipeline): point-to-point
    # stage→stage frames into the PEER's mailbox. CLASS_ACT in the send
    # scheduler — the latency class that overtakes gradient bursts.

    def act_push(self, key: int, seq: int, payload) -> None:
        """Deliver one boundary frame (activations or activation-grads)
        into the receiving stage's mailbox; last-wins per (key, seq) so
        the transport's resend path is idempotent."""
        self._rpc(OP_ACT_PUSH, key, int(seq), 0, 0, "uint8",
                  _as_bytes(np.asarray(payload).view(np.uint8)))

    def act_pull(self, key: int, seq: int,
                 timeout_ms: int = 30000) -> bytes:
        """Remote take: block until the (key, seq) frame arrives in the
        peer's mailbox, then fetch it — the pull-model form (the local
        take via ``PSTransportServer.act_store`` is the fast path)."""
        return self._sliced_pull(
            lambda slice_ms: self._rpc(OP_ACT_PULL, key, int(seq), 0,
                                       slice_ms, "uint8", None),
            timeout_ms, f"act_pull({key:#x}) seq={seq}")

    # Sharded-update param plane (byteps_tpu.sharded_update): the group
    # owner's post-apply param bytes into the server's param mailbox;
    # non-owners block-fetch them instead of pulling gradients.

    def param_put(self, key: int, seq: int, payload) -> None:
        """Publish one param frame; idempotent last-wins per (key, seq)
        so the transport's resend path re-stores identical bytes."""
        self._rpc(OP_PARAM_PUT, key, int(seq), 0, 0, "uint8",
                  memoryview(bytes(payload)))

    def param_get(self, key: int, seq: int,
                  timeout_ms: int = 30000) -> bytes:
        """Blocking NON-destructive fetch of the (key, seq) param frame
        (dp-1 replicas read each frame). A timeout here is the
        owner-death signal the sharded tail turns into its loud per-key
        diagnostic."""
        return self._sliced_pull(
            lambda slice_ms: self._rpc(OP_PARAM_GET, key, int(seq), 0,
                                       slice_ms, "uint8", None),
            timeout_ms, f"param_get({key:#x}) seq={seq}")

    def param_latest(self, key: int) -> int:
        """Newest retained seq in the server's param mailbox for
        ``key`` (0 = empty) — the elastic-rejoin seq seed
        (OP_PARAM_SEQ; docs/elasticity.md)."""
        data = self._rpc(OP_PARAM_SEQ, key, 0, 0, 0, "uint8", None)
        return struct.unpack("!Q", data)[0]

    def push_rowsparse(self, key: int, idx, rows, dense_nbytes: int,
                      dtype=None) -> None:
        """Row-sparse push: only the touched rows cross the wire. dtype
        defaults to the rows array's own dtype (mis-declaring it would
        reinterpret the bytes server-side)."""
        from .rowsparse import pack_rows
        if dtype is None:
            dtype = str(np.asarray(rows).dtype)
        self._rpc(OP_PUSH_RS, key, self._push_token(key), dense_nbytes, 0,
                  dtype, memoryview(pack_rows(idx, rows)))

    # Sharded-embedding client (server/embed.py EmbedClient rides
    # these; docs/embedding.md): one key per TABLE, rows addressed by
    # id inside the payload — EmbedClient wraps single-address
    # backends per shard (the plane-backend idiom), so these ops
    # always target this client's one server.

    def embed_init(self, key: int, meta: dict) -> None:
        """Declare a table (idempotent first-wins server-side;
        conflicting shape/dtype/seed refused loudly). Recorded for
        replay so a restarted server relearns the declaration."""
        import json as _json
        body = _json.dumps(meta).encode()
        self._rpc(OP_EMBED_INIT, key, 0, 0, 0, "uint8",
                  memoryview(body))
        self._embed_inits[self._shard(key)][key] = body

    def embed_pull(self, key: int, payload,
                   timeout_ms: int = 30000) -> bytes:
        """Conditional sparse row pull: ship ids + cached versions,
        receive flags + versions + only the rows whose version moved.
        Never round-blocked — embedding rows live under the async
        weight-delta contract, not the sync round gate."""
        return self._rpc(OP_EMBED_PULL, key, 0, 0, timeout_ms,
                         "uint8", memoryview(payload))

    def embed_push(self, key: int, payload,
                   token: Optional[int] = None) -> None:
        """Row-sparse delta push (ids + folded rows); dedup-tokenized
        like any push so a reconnect retry applies exactly once, and
        CLASS_GRAD in the wire scheduler like any gradient burst.
        ``token`` lets the caller pin the dedup token across a
        FAILOVER retry (EmbedClient allocates one per slice batch and
        resends it verbatim to the promoted replica — the replicated
        log already carries it iff the dead primary applied)."""
        self._rpc(OP_EMBED_PUSH, key,
                  self._push_token(key) if token is None else int(token),
                  0, 0, "uint8", memoryview(payload))

    def embed_repl(self, key: int, token: int, payload,
                   timeout_ms: int = 30000) -> None:
        """Chain forward of applied rows to a slice successor (server→
        server): absolute post-apply state + versions, dedup token in
        ``rnd`` so the replica can seed exactly-once across failover."""
        self._rpc(OP_EMBED_REPL, key, int(token), 0, timeout_ms,
                  "uint8", memoryview(payload))

    def embed_failover(self, key: int, payload,
                       timeout_ms: int = 30000) -> bytes:
        """Promote this client's server for a dead slice (``key`` = the
        slice key); returns the server's JSON stats body."""
        return self._rpc(OP_EMBED_FAILOVER, key, 0, 0, timeout_ms,
                         "uint8", memoryview(payload))

    def embed_snap(self, key: int, payload,
                   timeout_ms: int = 60000) -> bytes:
        """Ask this client's server to dump its embed row store to the
        JSON-named path (atomic tmp+rename); returns JSON stats."""
        return self._rpc(OP_EMBED_SNAP, key, 0, 0, timeout_ms,
                         "uint8", memoryview(payload))

    def embed_restore(self, key: int, payload,
                      timeout_ms: int = 60000) -> bytes:
        """Ask this client's server to load its embed row store from
        the JSON-named path; returns JSON stats."""
        return self._rpc(OP_EMBED_RESTORE, key, 0, 0, timeout_ms,
                         "uint8", memoryview(payload))

    def pull_bytes(self, key: int, round: int = 0,
                   timeout_ms: int = 30000) -> bytes:
        return self._sliced_pull(
            lambda slice_ms: self._rpc(OP_PULL_C, key, round, 0,
                                       slice_ms, "uint8", None),
            timeout_ms, f"pull_bytes({key}) round={round}")

    def push_pull(self, key: int, data: np.ndarray,
                  timeout_ms: int = 30000) -> np.ndarray:
        """One sync round from this worker's perspective: push, then pull
        the round this push completes (per-key local round counter —
        mirrors HostPSBackend.push_pull; round 0 would be a stale read)."""
        self.push(key, data)
        rnd = self._rounds.get(key, 0) + 1
        self._rounds[key] = rnd
        out = np.empty_like(data)
        self.pull(key, out, rnd if not self.async_mode else 0, timeout_ms)
        return out

    def close(self) -> None:
        import queue as _queue
        # flag FIRST: an op thread sitting in _reconnect's redial loop
        # holds its channel outside the pool, so the drain below never
        # reaches it — without the flag it would keep dialing the dead
        # address for up to reconnect_secs AFTER close. A zombie dialer
        # is not just waste: the kernel recycles the dead server's port
        # (sequential ephemeral allocation), and a successful redial
        # sprays init-replay frames at whatever now owns it — observed
        # aborting an unrelated process's gloo listener mid-handshake.
        self._dead = True
        if self._stripe_exec is not None:
            self._stripe_exec.shutdown(wait=True)
            self._stripe_exec = None
        for i, ch in enumerate(self._stats_chans):
            if ch is not None and ch.sock is not None:
                with self._stats_locks[i]:
                    try:
                        ch.sock.close()
                    except OSError:
                        pass
                    ch.sock = None
        for pool in self._pools:
            while True:
                try:
                    ch = pool.get_nowait()
                except _queue.Empty:
                    break
                ch.drop_shm()
                if ch.sock is None:
                    continue
                try:
                    _send_req(ch.sock, OP_CLOSE, 0, 0, 0, 0, "", None)
                    _recv_exact(ch.sock, _RSP.size)
                except (ConnectionError, OSError):
                    pass
                ch.sock.close()

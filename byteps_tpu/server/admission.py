"""Unified admission plane: every "may this byte / this apply proceed"
decision in one place.

Four independently-grown scheduling components used to share this
responsibility (ROADMAP item 1 called collapsing them "the refactor
everything else wants"):

  1. the exchange's per-key push admission gate (two rounds in flight
     under cross-step; ``PSGradientExchange._admit_key``),
  2. the exchange's landed-bucket pull priority heap
     (``_enqueue_pull`` / ``_pull_next``),
  3. the staged-segment launcher's cross-step epoch gate
     (``cross_step``'s ``wait_epoch(e - 1)``),
  4. the two-class wire send scheduler (``SendScheduler`` below).

They now live here as one plane with one contract. ``KeyGate`` is the
per-key apply-order gate, ``PullQueue`` is the pull scheduler,
``SendScheduler`` is the wire gate, and ``AdmissionPlane`` is the
facade an exchange owns. The external surfaces are unchanged at the
default configuration: same metrics (``ps/admission_*``, ``sched/*``),
same key-less ``send_admit`` flight events, same scheduler trace shape
the critical-path analyzer carves credit waits from.

On top of the unification sits **K-round bounded staleness**
(``StaleStore``): the server versions each key's rounds, workers
declare ``BPS_MAX_LAG=K``, and the plane decides per (key, round)
whether to

  - **serve** a complete sum (every worker contributed — the only
    verdict that exists at K=1, bitwise-identical to the classic path),
  - **stale-serve**: seal the round without the stragglers' gradients
    when every missing worker still has slack under its bound (a worker
    may miss at most K-1 CONSECUTIVE rounds), or
  - **barrier**: some missing worker has exhausted its slack — block
    until its push arrives, draining the in-flight round before any
    further progress.

A gradient is never dropped: a push that arrives for an already-sealed
round folds into the CURRENT open round's accumulator and counts as
that worker's contribution to it (resetting its miss streak), so a
permanently slow worker contributes one gradient per push at its own
pace and costs the fleet *lag, not wall-clock*. Sealed sums are
published as immutable snapshots — every puller of a round sees the
same bytes, so replicated workers stay bit-identical. Every
stale-serve and barrier decision is recorded as a key-less flight
event (like codec and ``send_admit`` decisions) and counted under the
``lag/*`` metric families.
"""

from __future__ import annotations

import heapq
import itertools
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..obs.metrics import get_registry

CLASS_GRAD = 0
CLASS_ACT = 1

# CLASS_ACT priority base: any activation outranks any gradient bucket
# (grad priorities are leaf-count-bounded, far below this)
ACT_PRIO_BASE = 1 << 20

# frames at or below this ride free (request headers, acks, control
# ops) — same reasoning as throttle.Nic.SMALL_FRAME: scheduling tiny
# frames buys nothing and a queued ack would stall the very pipeline
# the scheduler exists to keep busy
MIN_SCHED_BYTES = 4096

# pull_lag verdict flags (bit 0 and 1 of the response status byte)
LAG_COMPLETE = 0       # every worker contributed — the K=1 verdict
LAG_STALE = 1          # sealed under the bound without some workers
LAG_BARRIER = 2        # a bound was exhausted; the pull waited it out


def resolve_max_lag(explicit: Optional[int] = None) -> int:
    """The declared staleness bound K. 1 (the default) is today's sync
    path: a round publishes only when every worker contributed."""
    if explicit is not None:
        return max(1, int(explicit))
    try:
        return max(1, int(os.environ.get("BPS_MAX_LAG", "1") or 1))
    except ValueError:
        return 1


def lag_grace_s() -> float:
    """``BPS_LAG_GRACE_MS``: how long a seal-eligible pull waits for
    natural completion before sealing (0 = seal immediately)."""
    try:
        return max(0.0, float(
            os.environ.get("BPS_LAG_GRACE_MS", "0") or 0)) / 1e3
    except ValueError:
        return 0.0


# ===================================================================
# per-key push admission (component 1)
# ===================================================================


class KeyGate:
    """Per-key push admission: at most ``depth`` rounds of one key may
    be pushed-but-unpulled at once; excess pushes queue FIFO per key so
    rounds stay ordered on the wire. Depth 1 is the classic cross-step
    contract (round k+1's push waits for round k's pull — the server
    publishes one round per key at a time); under bounded staleness the
    depth is K, because the versioned store holds K rounds per key.
    Deferred admissions are counted and their wait timed — the gate is
    where a lost pull turns into a silent wedge, so its depth/latency
    are first-class signals."""

    def __init__(self, depth: int = 1) -> None:
        self.depth = max(1, int(depth))
        self._lock = threading.Lock()
        self._held: Dict[int, int] = {}
        self._waiters: Dict[int, deque] = {}
        reg = get_registry()
        self._m_wait = reg.histogram("ps/admission_wait_s")
        self._m_defer = reg.counter("ps/admission_deferred")

    def admit(self, pskey: int, submit) -> None:
        """Run ``submit`` now if ``pskey`` has an admission slot free,
        else defer it until a slot releases (FIFO per key)."""
        from ..obs import flight
        with self._lock:
            if self._held.get(pskey, 0) >= self.depth:
                self._m_defer.inc()
                t0 = time.time()

                def deferred(submit=submit, t0=t0):
                    wait = time.time() - t0
                    self._m_wait.observe(wait)
                    flight.record("admit", key=pskey,
                                  detail=f"deferred {wait:.3f}s")
                    submit()

                self._waiters.setdefault(pskey, deque()).append(deferred)
                return
            self._held[pskey] = self._held.get(pskey, 0) + 1
        flight.record("admit", key=pskey)
        submit()

    def release(self, pskey: int) -> None:
        with self._lock:
            waiters = self._waiters.get(pskey)
            if waiters:
                submit = waiters.popleft()
                if not waiters:
                    del self._waiters[pskey]
            else:
                n = self._held.get(pskey, 0) - 1
                if n <= 0:
                    self._held.pop(pskey, None)
                else:
                    self._held[pskey] = n
                return
        submit()                     # slot passes to the successor

    def state(self) -> dict:
        """Holders and queued waiters — the watchdog's dump shape."""
        with self._lock:
            return {"busy": sorted(self._held),
                    "waiters": {k: len(v)
                                for k, v in self._waiters.items()}}


# ===================================================================
# landed-bucket pull scheduling (component 2)
# ===================================================================


class PullQueue:
    """Pull scheduler for landed buckets: a min-heap ordered by (round
    age, next-step first-use priority, FIFO). Pushes keep
    backward-completion order, but pulls drain input-side-first because
    those params gate fwd(k+1)'s first gated segment — without this the
    reverse-packed plan applies the input layers LAST and the
    cross-step overlap window collapses to zero. Also owns the
    monotonically increasing round sequence the age ordering keys on."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._heap: List[tuple] = []
        self._seq = 0
        self._round_seq = 0

    def next_round_seq(self) -> int:
        with self._lock:
            self._round_seq += 1
            return self._round_seq

    def put(self, round_seq: int, prio: int, payload) -> None:
        with self._lock:
            heapq.heappush(self._heap,
                           (round_seq, prio, self._seq, payload))
            self._seq += 1

    def pop(self):
        """The highest-priority landed bucket (oldest round first, then
        first-use priority, then FIFO)."""
        with self._lock:
            return heapq.heappop(self._heap)[3]

    def __len__(self) -> int:
        with self._lock:
            return len(self._heap)


# ===================================================================
# two-class wire send scheduling (component 4 — was server/sched.py)
# ===================================================================


class _Ticket:
    __slots__ = ("klass", "prio", "key", "nbytes", "seq", "t_enq")

    def __init__(self, klass: int, prio: int, key: int, nbytes: int,
                 seq: int) -> None:
        self.klass = klass
        self.prio = prio
        self.key = key
        self.nbytes = int(nbytes)
        self.seq = seq
        self.t_enq = time.monotonic()

    def order(self):
        """Heap key: priority DESC, key ASC, then FIFO — the BytePS
        ``scheduled_queue`` comparator."""
        eff = self.prio + (ACT_PRIO_BASE if self.klass == CLASS_ACT else 0)
        return (-eff, self.key, self.seq)


class SendScheduler:
    """Wire-admission gate (BytePS ``scheduled_queue.cc:82-146`` +
    ``BYTEPS_SCHEDULING_CREDIT``): ``acquire`` blocks until the frame
    is the highest-priority queued entry AND byte credit is available;
    ``release`` returns the credit once the bytes left this host.
    ``CLASS_ACT`` frames (activations — latency-critical, a stage
    blocks on them) carry a large priority base so they always outrank
    ``CLASS_GRAD``; within grads the exchange assigns reverse-FIRST-USE
    priorities, the same order the pull queue drains, so the send and
    pull sides agree on who is urgent. One frame is always admitted
    even if larger than the whole credit, so a giant bucket cannot
    deadlock. With the credit at 0 (default) the gate is inert.

    Every admission is recorded in a bounded trace (class, key,
    priority, enqueue/admit sequence numbers, wait) — the "scheduler
    trace" the tests, ``bench.py pp``, and the critical-path analyzer's
    credit carve consume — plus registry metrics (``sched/*``)."""

    def __init__(self, credit_bytes: int, trace_cap: int = 4096) -> None:
        self.credit = int(credit_bytes)
        self._cv = threading.Condition()
        self._heap: List[tuple] = []          # (order, ticket)
        self._seq = itertools.count(1)
        self._inflight = 0
        self._trace: deque = deque(maxlen=trace_cap)
        self._admit_seq = 0
        reg = get_registry()
        self._m_act = reg.counter("sched/admitted_act")
        self._m_grad = reg.counter("sched/admitted_grad")
        self._m_overtakes = reg.counter("sched/overtakes")
        self._m_wait = reg.histogram("sched/credit_wait_s")
        self._g_inflight = reg.gauge("sched/inflight_bytes")

    # ------------------------------------------------------------ gate

    def acquire(self, klass: int, prio: int, key: int,
                nbytes: int) -> Optional[_Ticket]:
        """Block until this frame may be written. Returns the ticket to
        pass to ``release`` (None for frames below the scheduling
        floor — nothing to release)."""
        if nbytes <= MIN_SCHED_BYTES:
            return None
        t = _Ticket(klass, prio, key, nbytes, next(self._seq))
        entry = (t.order(), t)
        with self._cv:
            heapq.heappush(self._heap, entry)
            while not (self._heap[0] is entry
                       and (self._inflight == 0
                            or self._inflight + t.nbytes <= self.credit)):
                self._cv.wait(1.0)
            heapq.heappop(self._heap)
            self._inflight += t.nbytes
            self._g_inflight.set(self._inflight)
            self._admit_seq += 1
            # an overtake: some entry enqueued BEFORE us is still
            # queued — we jumped the line on priority
            overtook = any(e[1].seq < t.seq for e in self._heap)
            waited = time.monotonic() - t.t_enq
            self._trace.append({
                "class": "act" if klass == CLASS_ACT else "grad",
                "key": key, "prio": prio, "nbytes": t.nbytes,
                "enq_seq": t.seq, "admit_seq": self._admit_seq,
                "wait_s": waited, "overtook": overtook,
                # wall-clock ADMIT stamp: the credit wait occupied
                # [t - wait_s, t] — the interval the critical-path
                # analyzer subtracts out of PS_PUSH spans as "credit"
                "t": time.time(),
            })
        (self._m_act if klass == CLASS_ACT else self._m_grad).inc()
        if overtook:
            self._m_overtakes.inc()
        self._m_wait.observe(waited)
        # flight-recorder send-admission event, KEY-LESS like the codec
        # decisions (obs/flight.py): the admission ordering is context
        # for EVERY key's postmortem — a frame that waited did so
        # because of some OTHER key's burst, so filtering it out of
        # that key's dump would hide exactly the why. The enabled check
        # comes FIRST: with the recorder off the per-frame cost must
        # stay one attribute read, not an f-string build.
        from ..obs import flight
        if flight.get_recorder().enabled:
            flight.record(
                "send_admit", nbytes=t.nbytes,
                detail=f"class={'act' if klass == CLASS_ACT else 'grad'} "
                       f"key={key} prio={prio} wait_ms={waited * 1e3:.1f} "
                       f"overtook={overtook}")
        return t

    def release(self, ticket: Optional[_Ticket]) -> None:
        if ticket is None:
            return
        with self._cv:
            self._inflight -= ticket.nbytes
            self._g_inflight.set(self._inflight)
            self._cv.notify_all()

    # ------------------------------------------------------------ views

    def trace(self) -> List[dict]:
        """Admission records, oldest first (bounded window)."""
        with self._cv:
            return list(self._trace)

    def queued(self) -> int:
        with self._cv:
            return len(self._heap)

    def inflight(self) -> int:
        return self._inflight


_send_lock = threading.Lock()
_send_current: Optional[SendScheduler] = None
_send_configured = False


def configure_send(
        credit_bytes: Optional[int] = None) -> Optional[SendScheduler]:
    """(Re)build the process-global wire scheduler. ``None`` re-reads
    ``BPS_SCHEDULING_CREDIT`` (``BYTEPS_SCHEDULING_CREDIT`` accepted);
    credit <= 0 disables. Called by ``bps.init`` so the env contract
    matches every other knob; tests call it directly between arms."""
    global _send_current, _send_configured
    if credit_bytes is None:
        credit_bytes = int(
            os.environ.get("BPS_SCHEDULING_CREDIT",
                           os.environ.get("BYTEPS_SCHEDULING_CREDIT", "0"))
            or 0)
    with _send_lock:
        _send_current = (SendScheduler(credit_bytes)
                         if credit_bytes > 0 else None)
        _send_configured = True
        return _send_current


def send_scheduler() -> Optional[SendScheduler]:
    """The process-global wire scheduler, or None when disabled. First
    call resolves from the env so directly-constructed transports
    (tests, scripts without ``bps.init``) honor the credit knob."""
    if not _send_configured:
        configure_send()
    return _send_current


# ===================================================================
# K-round bounded staleness (server side)
# ===================================================================


class _LagKey:
    __slots__ = ("size", "dtype", "max_lag", "cv", "acc", "contrib",
                 "published", "published_upto", "streak", "late_folds")

    def __init__(self, size: int, dtype: str, max_lag: int,
                 num_workers: int) -> None:
        self.size = int(size)
        self.dtype = np.dtype(dtype)
        self.max_lag = int(max_lag)
        self.cv = threading.Condition()
        self.acc: Dict[int, np.ndarray] = {}       # open rounds' sums
        self.contrib: Dict[int, set] = {}          # round -> worker ids
        self.published: Dict[int, tuple] = {}      # round -> (sum, flags)
        self.published_upto = 0
        # consecutive published rounds each worker missed; the bound is
        # streak <= max_lag - 1, enforced at seal time
        self.streak = [0] * num_workers
        self.late_folds = 0


class StaleStore:
    """Server-side versioned round store for lag-managed keys.

    The decision table, evaluated by the pull of the oldest unpublished
    round (earlier pulls are served from published snapshots):

      every worker contributed          -> publish COMPLETE (flags 0)
      missing workers all have slack
        (streak + 1 <= K - 1)           -> wait ``BPS_LAG_GRACE_MS``,
                                           then SEAL (stale-serve)
      some missing worker is at bound   -> BARRIER: block until its
                                           push arrives (draining the
                                           in-flight round), then
                                           publish

    K=1 makes the seal condition unsatisfiable (a miss would need
    streak <= -1), so the store degenerates to complete-round-only —
    the classic sync semantics. A push for an already-published round
    folds into the current open round and counts as that worker's
    contribution to it (see module docstring): sums are conserved,
    every gradient is applied exactly once, and a permanently slow
    worker alternates miss/contribute instead of drifting to a
    permanent barrier.

    A fresh store that sees its first push at round r > 1 adopts
    r - 1 as its published head — the elastic rejoin / server-failover
    resync (the exchange seeds per-key rounds from the server, so a
    replacement server must meet workers at the fleet's live round,
    not at 1)."""

    def __init__(self, num_workers: int, spans=None) -> None:
        self.num_workers = max(1, int(num_workers))
        self.spans = spans
        self._lock = threading.Lock()
        self._keys: Dict[int, _LagKey] = {}
        reg = get_registry()
        self._m_stale = reg.counter("lag/stale_serves")
        self._m_barrier = reg.counter("lag/barrier_falls")
        self._m_late = reg.counter("lag/late_folds")
        self._m_evicted = reg.counter("lag/evicted_serves")
        self._g_streak = reg.gauge("lag/max_streak")

    # ------------------------------------------------------- contract

    def declare(self, key: int, size: int, dtype: str,
                max_lag: int) -> None:
        """Route ``key``'s rounds through this store with bound
        ``max_lag``. Idempotent; a conflicting re-declaration (workers
        disagreeing on K) is a loud config error."""
        key, max_lag = int(key), int(max_lag)
        with self._lock:
            st = self._keys.get(key)
            if st is not None:
                if st.max_lag != max_lag:
                    raise ValueError(
                        f"key {key} lag bound re-declared {max_lag} != "
                        f"{st.max_lag} — workers disagree on BPS_MAX_LAG")
                return
            self._keys[key] = _LagKey(size, dtype, max_lag,
                                      self.num_workers)

    def managed(self, key: int) -> bool:
        with self._lock:
            return int(key) in self._keys

    def declared(self, key: int) -> Optional[int]:
        with self._lock:
            st = self._keys.get(int(key))
            return None if st is None else st.max_lag

    def streaks(self, key: int) -> List[int]:
        st = self._st(key)
        with st.cv:
            return list(st.streak)

    def round(self, key: int) -> int:
        """Last published round — what a rejoining worker seeds from."""
        st = self._st(key)
        with st.cv:
            return st.published_upto

    def _st(self, key: int) -> _LagKey:
        with self._lock:
            st = self._keys.get(int(key))
        if st is None:
            raise KeyError(f"key {key} is not lag-managed "
                           f"(declare_lag never reached this server)")
        return st

    # ------------------------------------------------------ data path

    def push(self, key: int, worker: int, rnd: int,
             data: np.ndarray) -> int:
        """Fold one worker's gradient. Returns the round it landed in:
        ``rnd`` itself, or the current open round when ``rnd`` was
        already sealed (late fold)."""
        st = self._st(key)
        worker, rnd = int(worker), int(rnd)
        data = np.asarray(data).reshape(-1)
        with st.cv:
            if st.published_upto == 0 and not st.acc and rnd > 1:
                st.published_upto = rnd - 1      # failover/rejoin adopt
            if rnd <= st.published_upto:
                tgt = st.published_upto + 1      # late fold (see class)
                st.late_folds += 1
                self._m_late.inc()
            else:
                tgt = rnd
            acc = st.acc.get(tgt)
            if acc is None:
                acc = st.acc[tgt] = np.zeros(st.size, st.dtype)
                st.contrib[tgt] = set()
            if data.dtype != st.dtype:
                data = data.astype(st.dtype)
            acc += data
            st.contrib[tgt].add(worker)
            st.cv.notify_all()
        return tgt

    def pull(self, key: int, worker: int, rnd: int, out: np.ndarray,
             timeout_ms: int = 30000) -> int:
        """Block until every round <= ``rnd`` is published (publishing
        them per the decision table), then copy round ``rnd``'s
        snapshot into ``out``. Returns the verdict flags
        (LAG_COMPLETE / LAG_STALE, plus LAG_BARRIER when this pull had
        to wait out an exhausted bound)."""
        st = self._st(key)
        rnd = int(rnd)
        grace = lag_grace_s()
        deadline = time.monotonic() + int(timeout_ms) / 1e3
        flags = 0
        barrier_logged: set = set()
        with st.cv:
            t_wait0 = time.monotonic()
            while st.published_upto < rnd:
                nxt = st.published_upto + 1
                contrib = st.contrib.get(nxt, ())
                missing = [w for w in range(self.num_workers)
                           if w not in contrib]
                if not missing:
                    self._publish(st, key, nxt, sealed=False)
                    continue
                can_seal = all(st.streak[m] + 1 <= st.max_lag - 1
                               for m in missing)
                now = time.monotonic()
                if can_seal and now - t_wait0 >= grace:
                    self._publish(st, key, nxt, sealed=True,
                                  missing=missing)
                    continue
                if not can_seal and nxt not in barrier_logged:
                    barrier_logged.add(nxt)
                    flags |= LAG_BARRIER
                    self._m_barrier.inc()
                    self._decision("barrier", key, nxt, missing, st)
                if now >= deadline:
                    raise TimeoutError(
                        f"pull_lag key={key} round={rnd} blocked "
                        f"{int(timeout_ms)}ms at round {nxt} "
                        f"(missing workers {missing}, "
                        f"streaks {list(st.streak)})")
                # seal-eligible: sleep only to the end of the grace
                # window (tiny floor against spin — NOT 10ms+, or any
                # grace shorter than the floor would silently stretch
                # to it and lose the seal race to the late push)
                st.cv.wait(min(
                    deadline - now,
                    max(grace - (now - t_wait0), 0.0005)
                    if can_seal else 0.25))
            ent = st.published.get(rnd)
            if ent is None:
                # the worker fell beyond the retention window: its own
                # round's snapshot is gone. Serve the newest published
                # sum instead — under bounded staleness a hopelessly
                # behind worker reads the freshest state (its pushes
                # late-fold, so its gradients still land exactly once);
                # erroring here would wedge the one worker the lag
                # contract exists to keep off the critical path.
                ent = st.published[st.published_upto]
                flags |= LAG_STALE
                self._m_evicted.inc()
                self._decision("evicted", key, rnd, (), st)
            arr, f = ent
            flags |= f
            view = out.reshape(-1)
            if view.dtype == arr.dtype:
                np.copyto(view, arr)
            else:
                view[:] = arr.astype(view.dtype)
        return flags

    # ------------------------------------------------------- internals

    def _publish(self, st: _LagKey, key: int, rnd: int, sealed: bool,
                 missing=()) -> None:
        """Publish round ``rnd``'s accumulator as an immutable snapshot
        and advance the streak bookkeeping. Caller holds ``st.cv``."""
        acc = st.acc.pop(rnd, None)
        contrib = st.contrib.pop(rnd, set())
        if acc is None:             # nobody pushed (drained rejoin gap)
            acc = np.zeros(st.size, st.dtype)
        st.published[rnd] = (acc, LAG_STALE if sealed else LAG_COMPLETE)
        st.published_upto = rnd
        for w in range(self.num_workers):
            st.streak[w] = 0 if w in contrib else st.streak[w] + 1
        cut = rnd - (2 * st.max_lag + 4)
        for old in [r for r in st.published if r <= cut]:
            del st.published[old]
        if sealed:
            self._m_stale.inc()
            self._g_streak.set(max(st.streak))
            get_registry().gauge(f"lag/streak/{key}").set(max(st.streak))
            self._decision("stale", key, rnd, missing, st)
            if self.spans is not None:
                self.spans.note_seal(key, rnd, missing)
        st.cv.notify_all()

    def _decision(self, verdict: str, key: int, rnd: int, missing,
                  st: _LagKey) -> None:
        # KEY-LESS like send_admit: a sealed round is context for every
        # key's postmortem (the enabled check first — see SendScheduler)
        from ..obs import flight
        if flight.get_recorder().enabled:
            flight.record(
                "lag_admit",
                detail=f"verdict={verdict} key={key} round={rnd} "
                       f"missing={sorted(missing)} "
                       f"streaks={list(st.streak)} K={st.max_lag}")


# ===================================================================
# the facade an exchange owns
# ===================================================================


class AdmissionPlane:
    """One object owning every admission decision for an exchange: the
    per-key push gate (depth = K), the landed-bucket pull queue, the
    cross-step epoch bound, and (via the process-global) the wire send
    scheduler. The server-side ``StaleStore`` is its peer on the other
    end of the wire — ``HostPSBackend`` instantiates one lazily when
    the first ``declare_lag`` arrives."""

    def __init__(self, max_lag: Optional[int] = None,
                 worker_id: Optional[int] = None) -> None:
        self.max_lag = resolve_max_lag(max_lag)
        self.worker_id = (int(os.environ.get("BPS_WORKER_ID", "0") or 0)
                          if worker_id is None else int(worker_id))
        self.gate = KeyGate(depth=self.max_lag)
        self.pulls = PullQueue()

    def send(self) -> Optional[SendScheduler]:
        """The wire send gate (process-global; None when inert)."""
        return send_scheduler()

    def gate_round(self, e: int) -> int:
        """The newest epoch whose params must be APPLIED before step
        ``e`` may launch — the cross-step driver's wait target. K=1 is
        the classic two-rounds-in-flight window (wait on e-1)."""
        return e - self.max_lag

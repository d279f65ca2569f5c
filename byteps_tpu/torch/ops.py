"""torch-tensor push_pull ops (reference: torch/ops.py:48-236 +
handle_manager.{cc,h} — int handles over in-flight reductions).

Handles wrap futures on a priority-scheduled multi-channel pool
(``_Dispatcher``): dispatch returns immediately (backward keeps
running), exchanges drain lowest-priority-first across
``BPS_TORCH_CHANNELS`` push workers, and pulls resolve on separate
pull workers so a blocked pull never keeps pushes off the wire.
Exchange START order is therefore NOT per-process FIFO — anything
order-sensitive (name→key declaration) happens on the dispatching
thread in ``_dispatch``. Cross-worker matching is per KEY on the PS
server, so workers may run exchanges in different orders (the
reference relies on the same ps-lite property)."""

from __future__ import annotations

import threading
from concurrent.futures import Future
from typing import Dict, Optional, Tuple

import numpy as np
import torch

from ..common.global_state import GlobalState


def init(config=None, **kwargs) -> None:
    """bps.init() for torch scripts.

    Defaults to HOST-ONLY mode (no device mesh, no JAX backend
    discovery): the torch plugin's wire is numpy-over-TCP end to end,
    so touching accelerator discovery at init would only claim a chip
    this process never uses. Set ``BPS_HOST_ONLY=0`` to get
    the full collective engine in the same process (mixed torch+JAX
    scripts)."""
    import byteps_tpu as bps
    if config is None and not GlobalState.initialized():
        from ..common.config import Config, _env_bool
        config = Config.from_env(
            host_only=_env_bool("BPS_HOST_ONLY", None, default=True))
    bps.init(config=config, **kwargs)


def shutdown() -> None:
    import byteps_tpu as bps
    _Dispatcher.reset()
    _async_inited.clear()
    bps.shutdown()


def size() -> int:
    """World size = PS worker-process count (torch processes are the
    replicas; the jax mesh inside each is an implementation detail)."""
    return GlobalState.get().config.num_worker


def rank() -> int:
    return GlobalState.get().config.worker_id


def local_rank() -> int:
    return GlobalState.get().config.local_rank


def local_size() -> int:
    return GlobalState.get().config.local_size


def declare(name: str, **kwargs) -> None:
    """Pre-declare a tensor (priority / compression kwargs — reference:
    byteps_declare_tensor)."""
    GlobalState.get().registry.declare(name, **kwargs)


def declare_model_keys(names) -> None:
    """Declare Gradient.* then Parameter.* keys for a model's parameter
    names — two sorted loops for key-range load balancing, the
    reference's exact pattern (torch/__init__.py:95-100); shared by
    DistributedOptimizer and DistributedDataParallel so both map params
    onto identical PS key ranges."""
    reg = GlobalState.get().registry
    for name in sorted(names):
        reg.declare("Gradient." + name)
    for name in sorted(names):
        reg.declare("Parameter." + name)


class _Dispatcher:
    """Process-wide handle table + PRIORITY-scheduled channel pool.

    Multi-channel (``BPS_TORCH_CHANNELS``, default 4): a slow tensor
    must not head-of-line-block every later exchange — the reference
    runs free multi-channel push/pull loops. Pending exchanges drain in
    PRIORITY order (lower value first; ties FIFO): backward produces
    the LAST layer's gradient first, but the next forward needs the
    FIRST layer's parameters first, so the optimizer submits each
    parameter with its forward position as priority and queued
    exchanges jump ahead of later layers' (the reference's
    BYTEPS_SCHEDULING priority / the ByteScheduler result its
    cross_barrier.py cites). Safe: PS keys/rounds are independent per
    tensor name, so cross-worker dispatch order may differ."""

    _lock = threading.Lock()
    _handles: Dict[int, Tuple[Future, torch.Tensor, bool]] = {}
    _next = 0
    _noname = 0
    _pq: Optional[list] = None      # heap of (priority, seq, start, fut)
    _cv: Optional[threading.Condition] = None
    _pullq = None                   # queue of (resolver, fut)
    _threads: list = []
    _stop_evt: Optional[threading.Event] = None   # per pool GENERATION

    @classmethod
    def _ensure_pool(cls) -> None:
        with cls._lock:
            if cls._pq is not None:
                return
            import os
            import queue as _queue
            cls._pq = []
            cls._cv = threading.Condition()
            cls._pullq = _queue.Queue()
            cls._stop_evt = threading.Event()
            width = max(1, int(os.environ.get("BPS_TORCH_CHANNELS", "4")))
            cls._threads = [
                threading.Thread(target=cls._push_worker, daemon=True,
                                 args=(cls._pq, cls._cv, cls._pullq,
                                       cls._stop_evt),
                                 name=f"bps-torch-push-{i}")
                for i in range(width)]
            cls._threads += [
                threading.Thread(target=cls._pull_worker, daemon=True,
                                 args=(cls._pullq,),
                                 name=f"bps-torch-pull-{i}")
                for i in range(width)]
            for t in cls._threads:
                t.start()

    @classmethod
    def _push_worker(cls, pq: list, cv: threading.Condition,
                     pullq, stop: threading.Event) -> None:
        # pq/cv/stop captured at spawn: reset() swaps the class attrs
        # for a fresh pool while old workers drain against their OWN
        # generation's objects (a shared class-level stop flag could
        # kill a freshly created pool racing the reset).
        # A push worker only STARTS an exchange (its pushes are in
        # flight when start() returns); the blocking pull drain happens
        # on the pull workers — pushes never queue behind pulls, so two
        # workers' channel pools cannot wedge on disjoint key sets
        # (reference: free-running separate push/pull loops,
        # core_loops.cc:538-618)
        import heapq
        while True:
            with cv:
                while not pq and not stop.is_set():
                    cv.wait()
                if stop.is_set():
                    return
                _, _, start, fut = heapq.heappop(pq)
            try:
                resolver = start()
            except BaseException as e:   # noqa: BLE001 — via future
                fut.set_exception(e)
                continue
            pullq.put((resolver, fut))

    @classmethod
    def _pull_worker(cls, pullq) -> None:
        while True:
            item = pullq.get()
            if item is None:
                return
            resolver, fut = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(resolver())
            except BaseException as e:   # noqa: BLE001 — via future
                fut.set_exception(e)

    @classmethod
    def submit(cls, start, out: torch.Tensor, inplace: bool,
               priority: int = 0) -> int:
        """``start`` runs on a push worker and must return a resolver
        whose call (on a pull worker) yields the reduced array."""
        import heapq
        fut: Future = Future()
        while True:
            cls._ensure_pool()
            with cls._lock:
                if cls._pq is None:
                    continue   # reset() raced _ensure_pool; rebuild
                # enqueue while STILL holding cls._lock: reset() swaps
                # the generation under the same lock, so capture-then-
                # push-outside would let it retire this generation (and
                # clear _handles) between the two — the exchange would
                # land on a dead queue and its future never resolve
                h = cls._next
                cls._next += 1
                cls._handles[h] = (fut, out, inplace)
                with cls._cv:
                    heapq.heappush(cls._pq, (priority, h, start, fut))
                    cls._cv.notify()
                return h

    @classmethod
    def take(cls, handle: int):
        with cls._lock:
            try:
                return cls._handles.pop(handle)
            except KeyError:
                raise RuntimeError(
                    f"unknown push_pull handle {handle} — already "
                    "synchronized, or the dispatcher was reset/"
                    "shut down") from None

    @classmethod
    def peek(cls, handle: int):
        with cls._lock:
            try:
                return cls._handles[handle]
            except KeyError:
                raise RuntimeError(
                    f"unknown push_pull handle {handle} — already "
                    "synchronized, or the dispatcher was reset/"
                    "shut down") from None

    @classmethod
    def auto_name(cls) -> str:
        with cls._lock:
            n = cls._noname
            cls._noname += 1
        return f"push_pull.noname.{n}"

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            threads, cls._threads = cls._threads, []
            cv, cls._cv = cls._cv, None
            pullq, cls._pullq = cls._pullq, None
            stop, cls._stop_evt = cls._stop_evt, None
            pq, cls._pq = cls._pq, None
            handles = dict(cls._handles)
            cls._handles.clear()
        if cv is not None:
            with cv:
                stop.set()            # this generation's flag only
                cv.notify_all()
            for _ in threads:
                pullq.put(None)       # wake & stop pull workers
            for t in threads:
                t.join(timeout=5)
            # push workers exit on stop without draining: fail any
            # leftover queued exchanges so their waiters get an error,
            # not a silent hang (shutdown with undrained handles is
            # already warned about upstream)
            with cv:
                leftovers, pq[:] = list(pq), []
            for _, h, _, f in leftovers:
                if not f.done():
                    f.set_exception(RuntimeError(
                        "push_pull dispatcher was shut down before this "
                        "exchange started"))
                    # re-expose the handle so the waiter's synchronize()
                    # surfaces THIS error rather than an unknown-handle
                    # one (the wholesale clear above removed it)
                    if h in handles:
                        with cls._lock:
                            cls._handles.setdefault(h, handles[h])


def _exchange_np(arr: np.ndarray, average: bool, name: str) -> np.ndarray:
    """One cross-worker sum (host path). World 1: identity."""
    gs = GlobalState.get()
    ex = gs.engine.ps_exchange
    if ex is None:
        return arr                    # single worker, nothing to reduce
    out = ex.exchange({"t": arr}, name=name)["t"]
    if average and gs.engine.ps_world > 1:
        out = out / gs.engine.ps_world
    return out


def _exchange_start(arr: np.ndarray, average: bool, name: str):
    """Split form for the dispatcher: pushes are IN FLIGHT when this
    returns; the returned resolver (run on a pull worker) blocks for
    the merged result. See _Dispatcher._push_worker for why."""
    gs = GlobalState.get()
    ex = gs.engine.ps_exchange
    if ex is None:
        # no wire: defer to _exchange_np on the pull side (also the
        # tests' monkeypatch point)
        return lambda: _exchange_np(arr, average, name)
    pend = ex.exchange_async({"t": arr}, name=name)
    world = gs.engine.ps_world

    def resolve():
        out = pend.result()["t"]
        if average and world > 1:
            out = out / world
        return out

    return resolve


_async_inited: set = set()


def async_param_exchange(name: str, delta: np.ndarray,
                         init: np.ndarray) -> np.ndarray:
    """Async-PS protocol for one parameter: seed the store with the
    initial weights (first-wins, idempotent — every worker broadcasts
    the same values first), push the weight DELTA, pull the latest
    global weights (reference: async server folds raw deltas,
    server.cc:310-314; our AsyncPSWorker protocol in server/ps_mode.py)."""
    gs = GlobalState.get()
    be = gs.ps_backend
    key = gs.registry.declare(name).key_for_partition(0)
    if key not in _async_inited:
        be.init_key(key, init.nbytes, str(init.dtype),
                    init=np.ascontiguousarray(init))
        _async_inited.add(key)
    be.push(key, np.ascontiguousarray(delta))
    out = np.empty(init.size, init.dtype)
    be.pull(key, out)                 # async mode: latest, never blocks
    return out.reshape(init.shape)


def _dispatch(tensor: torch.Tensor, average: bool, name: Optional[str],
              inplace: bool, priority: int = 0) -> int:
    if name is None:
        name = _Dispatcher.auto_name()
    # declare on the DISPATCHING thread: name→key assignment is
    # declaration-order (naming.py), and every worker dispatches in the
    # same order (same model, same hooks) — on the racing push workers
    # the order would be nondeterministic and the same name could get
    # different PS keys on different workers (silent mis-summation)
    GlobalState.get().registry.declare(name)
    arr = tensor.detach().cpu().numpy().copy()

    def start():
        return _exchange_start(arr, average, name)

    return _Dispatcher.submit(start, tensor, inplace, priority=priority)


def push_pull_async(tensor: torch.Tensor, average: bool = True,
                    name: Optional[str] = None, priority: int = 0) -> int:
    """Dispatch a reduction of ``tensor``; returns an int handle. The
    input is snapshotted — later in-place mutation doesn't affect the
    exchange; ``synchronize`` returns a NEW tensor. Lower ``priority``
    drains first when channels are busy (the reference's
    BYTEPS_SCHEDULING priority knob)."""
    return _dispatch(tensor, average, name, inplace=False,
                     priority=priority)


def push_pull_async_inplace(tensor: torch.Tensor, average: bool = True,
                            name: Optional[str] = None,
                            priority: int = 0) -> int:
    """Like ``push_pull_async`` but ``synchronize`` writes the result
    back INTO ``tensor`` (reference: the default grad path)."""
    return _dispatch(tensor, average, name, inplace=True,
                     priority=priority)


def poll(handle: int) -> bool:
    fut, _, _ = _Dispatcher.peek(handle)
    return fut.done()


def synchronize(handle: int) -> torch.Tensor:
    fut, tensor, inplace = _Dispatcher.take(handle)
    out = fut.result()
    result = torch.from_numpy(np.ascontiguousarray(out)).reshape(
        tensor.shape).to(tensor.dtype)
    if inplace:
        with torch.no_grad():
            tensor.copy_(result)
        return tensor
    return result


def push_pull(tensor: torch.Tensor, average: bool = True,
              name: Optional[str] = None) -> torch.Tensor:
    """Synchronous reduce; returns a new tensor (reference:
    torch/ops.py push_pull)."""
    return synchronize(push_pull_async(tensor, average=average, name=name))

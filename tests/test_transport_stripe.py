"""Transport wire-speed work (VERDICT r4 #4): connection striping
protocol correctness + a utilization regression floor.

The round-5 fast paths (whole-frame token charge, zero-copy pull
receive, reused server recv buffer) lifted the 10 Gbps emulated-NIC
push utilization from 32% (r4 single-stream) to 79-98% depending on
payload mix. Wall clock on a shared box cannot hold a floor (40% under
six parallel test workers), so the guard counts pacing charges per
frame instead: a regression to chunked-Python pacing fails CI whatever
the load.

Striping (BPS_STRIPE_MIN > 0) splits one logical push/pull over the
connection pool with server-side reassembly/scatter. It is OFF by
default (measured negative on single-core hosts — no extra cycles to
win) but the protocol must stay exact for the multi-core deployments
it exists for.
"""

import os
import threading
import time

import numpy as np
import pytest

from byteps_tpu.server.engine import PSServer
from byteps_tpu.server.throttle import Nic
from byteps_tpu.server.transport import PSTransportServer, RemotePSBackend


@pytest.fixture
def rig():
    made = []

    def make(nic_rate: float = 0.0, stripe_min: int = 0):
        os.environ["BPS_STRIPE_MIN"] = str(stripe_min)
        mk = (lambda: Nic(nic_rate)) if nic_rate else (lambda: None)
        be = PSServer(num_workers=1, engine_threads=2)
        srv = PSTransportServer(be, host="127.0.0.1", port=0, nic=mk())
        cli = RemotePSBackend([f"127.0.0.1:{srv.port}"], nic=mk())
        made.append((cli, srv, be))
        return cli

    yield make
    os.environ.pop("BPS_STRIPE_MIN", None)
    for cli, srv, be in made:
        cli.close()
        srv.close()
        be.close()


def test_striped_push_pull_matches_dense(rig):
    """The striped wire path must be byte-exact with the dense one,
    across rounds, for sizes that do and don't divide the part count."""
    cli = rig(stripe_min=1 << 20)
    rs = np.random.RandomState(0)
    for key, elems in ((0, (8 << 20) // 4), (1, 1_000_003)):
        x = rs.randn(elems).astype(np.float32)
        cli.init_key(key, x.nbytes)
        out = np.empty_like(x)
        for rnd in range(1, 4):
            cli.push(key, x)
            cli.pull(key, out, round=rnd, timeout_ms=60000)
            np.testing.assert_array_equal(out, x)


def test_striped_retry_applies_once(rig):
    """Re-sent parts (same dedup token) must not double-apply: the
    server reassembles per (key, token) and dedups the logical push."""
    cli = rig(stripe_min=1 << 20)
    x = np.ones((4 << 20) // 4, np.float32)
    cli.init_key(0, x.nbytes)
    tok = cli._push_token(0)
    view = memoryview(x).cast("B")
    from byteps_tpu.server.transport import _PART, OP_PUSH_PART
    ranges = cli._stripe_ranges(len(view))
    assert ranges and len(ranges) >= 2
    n = len(ranges)
    for _ in range(2):                     # send the whole set TWICE
        for pi, (off, ln) in enumerate(ranges):
            cli._rpc(OP_PUSH_PART, 0, tok, len(view), 0, "float32",
                     (_PART.pack(off, ln, pi, n, 0), view[off:off + ln]))
    out = np.empty_like(x)
    cli.pull(0, out, round=1, timeout_ms=60000)
    np.testing.assert_array_equal(out, x)  # ones, not twos


def test_throttled_push_pacing_granularity(rig):
    """Regression guard for the wire fast path, in what a CPU run can
    count. r4's path paced an 8 MB push in 64 KB Python-loop chunks
    (128 token-bucket charges per frame, 32% of a 10 Gbps NIC); the
    fast path charges a frame whole when the bucket covers it and
    otherwise in ~2 ms-of-link chunks (2.5 MB at this rate: at most 4
    charges after the one whole-frame attempt). Wall-clock utilization
    is a property of the box's load (40% under six xdist workers, 79-98%
    alone — docs/performance.md), so only its hard bounds are asserted:
    every byte was booked, and the emulated NIC was never outrun."""
    rate = 10e9 / 8
    cli = rig(nic_rate=rate)
    NB = 8 << 20
    x = np.random.RandomState(0).randn(NB // 4).astype(np.float32)
    cli.init_key(0, NB)
    cli.push(0, x)                         # warm (dials, first buffers)
    nic = cli._nic
    charges = {"n": 0}
    for name in ("consume", "try_consume"):
        real = getattr(nic.tx, name)

        def counted(n, _real=real):
            if n > nic.SMALL_FRAME:        # control frames are exempt
                charges["n"] += 1
            return _real(n)
        setattr(nic.tx, name, counted)
    iters = 12
    tx0 = nic.tx_bytes
    t0 = time.perf_counter()
    for _ in range(iters):
        cli.push(0, x)
    dt = time.perf_counter() - t0
    sent = nic.tx_bytes - tx0
    assert NB * iters <= sent <= NB * iters * 1.01, sent
    per_push = charges["n"] / iters
    assert 1 <= per_push <= 1 + -(-NB // nic.chunk_size()) + 1, \
        f"{per_push} pacing charges per 8 MB push: chunked-Python " \
        f"pacing is back (r4 made 128)"
    # the bucket starts at most one burst ahead; past that it paces
    assert dt >= (sent - nic.tx.burst) / rate * 0.95, \
        f"pushes outran the emulated NIC: {sent / dt / rate:.2%}"


def test_byte_accounting_exact_for_large_frames(rig):
    """tx accounting must cover EVERY chunk of a multi-chunk frame (the
    r4 counter only saw the first 64 KB of each chunked send)."""
    rate = 10e9 / 8
    cli = rig(nic_rate=rate)
    NB = 8 << 20
    x = np.zeros(NB // 4, np.float32)
    cli.init_key(0, NB)
    nic = cli._nic
    tx0 = nic.tx_bytes
    cli.push(0, x)
    sent = nic.tx_bytes - tx0
    assert NB <= sent <= NB * 1.01, sent


def test_concurrent_striped_async_pulls_never_tear():
    """ADVICE.md medium: pull stages keyed by bare (key, round) collide
    across workers in async mode (round=0) — one puller's stragglers
    could be served a NEWER store value fetched for the other puller,
    assembling a torn tensor. The per-logical-op nonce gives every
    striped pull its own stage, so each op's parts all come from ONE
    engine fetch: with a pusher continuously bumping a uniform vector,
    every pulled tensor must still be internally uniform."""
    os.environ["BPS_STRIPE_MIN"] = "262144"
    be = PSServer(num_workers=1, engine_threads=2, async_mode=True)
    srv = PSTransportServer(be, host="127.0.0.1", port=0)
    clis = [RemotePSBackend([f"127.0.0.1:{srv.port}"], async_mode=True)
            for _ in range(2)]
    try:
        n = (2 << 20) // 4
        clis[0].init_key(0, n * 4, init=np.zeros(n, np.float32))
        stop = threading.Event()
        errs: list = []

        def pusher():
            one = np.ones(n, np.float32)
            while not stop.is_set():
                clis[0].push(0, one)     # store accumulates: stays uniform

        def puller(cli):
            out = np.empty(n, np.float32)
            try:
                for _ in range(30):
                    cli.pull(0, out, round=0, timeout_ms=30000)
                    assert cli._stripe_ranges(out.nbytes), \
                        "test rig: pull was not striped"
                    lo, hi = out.min(), out.max()
                    if lo != hi:
                        errs.append(f"torn pull: min={lo} max={hi}")
                        return
            except Exception as e:        # noqa: BLE001 — surfaced below
                errs.append(repr(e))

        ts = [threading.Thread(target=puller, args=(c,)) for c in clis]
        pt = threading.Thread(target=pusher)
        pt.start()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        stop.set()
        pt.join()
        assert not errs, errs
    finally:
        os.environ.pop("BPS_STRIPE_MIN", None)
        for c in clis:
            c.close()
        srv.close()
        be.close()

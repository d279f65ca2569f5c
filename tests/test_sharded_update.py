"""ZeRO-style sharded weight update on the PS path (ISSUE 10,
byteps_tpu/sharded_update.py).

Contracts under test:
  - OWNERSHIP PLAN: byte-balanced, deterministic across replicas, and
    covering (every group exactly one owner; every bucket either pulled
    or released by param fetches; owned leaves = streamed leaves);
  - PARAM MAILBOX: last-wins per (key, seq), NON-destructive reads
    (dp-1 replicas read each frame), bounded retention, loud timeout —
    in-process and over the real TCP transport;
  - GRAD-EXACTNESS PARITY (test_grad_exactness style): sharded-vs-full
    update lands on bitwise-identical weights for the mlp chain
    (dp ∈ {2, 4}, multi-step adam) and within the transformer tolerance
    contract (rtol 2e-3 / atol 2e-5) for bert — including with
    BPS_CROSS_STEP=1 and two rounds in flight;
  - OBSERVABILITY: registry-measured grad pull bytes drop to ~1/dp of
    the full-apply arm, param put/fetch counters move, per-layer
    ps/pull_bytes/<layer> counters register dynamically;
  - WIRE SCHEDULER: a param frame is the LATENCY class — enqueued after
    a grad burst it overtakes it (trace-asserted end to end);
  - FAULT: an owner dying between its grad pull and its param publish
    surfaces as a loud per-key diagnostic on the non-owner (fetch
    timeout naming group/owner/step) and in the watchdog dump
    (await_param state), never a silent wait_epoch hang.
"""

import os
import sys
import threading
import time

import jax
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import byteps_tpu as bps
from byteps_tpu.common.naming import NameRegistry
from byteps_tpu.obs.metrics import get_registry
from byteps_tpu.server.engine import HostPSBackend, PSServer
from byteps_tpu.server.ps_mode import PSGradientExchange
from byteps_tpu.server.transport import PSTransportServer, RemotePSBackend
from byteps_tpu.sharded_update import (ParamStore, ShardedUpdatePlan,
                                       build_sharded_state)
from byteps_tpu.training import DistributedTrainer

_ENV = ("BPS_ENABLE_PS", "BPS_NUM_WORKER", "BPS_SERVER_ADDRS",
        "BPS_SHARDED_UPDATE", "BPS_CROSS_STEP", "BPS_PS_CONNS",
        "BPS_PARAM_TIMEOUT_MS", "BPS_WATCHDOG_SEC")


@pytest.fixture
def _clean_env():
    saved = {k: os.environ.get(k) for k in _ENV}
    try:
        yield
    finally:
        bps.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# --------------------------------------------------------------- plan

def _plan_inputs(n_leaves=5, size=3000, partition=4 << 10):
    rng = np.random.RandomState(0)
    tree = {f"k{i}": rng.randn(size + 64 * i).astype(np.float32)
            for i in range(n_leaves)}
    be = HostPSBackend(num_servers=1, num_workers=1, engine_threads=1)
    ex = PSGradientExchange(be, partition_bytes=partition)
    _, _, keyed = ex._plan(tree, "plan")
    groups = ex.leaf_groups(tree, name="plan")
    meta = ShardedUpdatePlan.leaf_meta_of(tree)
    ex.close()
    be.close()
    return keyed, groups, meta


@pytest.mark.parametrize("world", [2, 4])
def test_ownership_plan_balanced_deterministic_covering(world):
    keyed, groups, meta = _plan_inputs()
    plans = [ShardedUpdatePlan(keyed, groups, meta, r, world)
             for r in range(world)]
    # identical assignment on every replica
    for p in plans[1:]:
        assert p.owner == plans[0].owner
        assert p.group_bytes == plans[0].group_bytes
    # every group exactly one owner; owned partition covers all groups
    owned_union = set()
    for p in plans:
        assert not (owned_union & set(p.owned))
        owned_union |= set(p.owned)
    assert owned_union == set(range(len(groups)))
    # every bucket either pulled by its owner or released by fetches
    for p in plans:
        assert p.pull_buckets | set(p.skip_groups) == set(
            range(len(keyed)))
        assert not (p.pull_buckets & set(p.skip_groups))
        # streamed leaves are exactly the owned groups' leaves
        want = {li for gi in p.owned for li in groups[gi]}
        assert set(p.stream_leaves) == want
        # skipped buckets name non-owned groups only
        for bi, gs in p.skip_groups.items():
            assert gs and all(p.owner[gi] != p.rank for gi in gs)
    # byte balance: imbalance bounded by the largest single group
    tot = sum(plans[0].group_bytes)
    biggest = max(plans[0].group_bytes)
    assert max(plans[0].load) - min(plans[0].load) <= biggest, \
        (plans[0].load, plans[0].group_bytes)
    assert sum(plans[0].load) == tot


def test_plan_param_frame_pack_unpack_roundtrip():
    keyed, groups, meta = _plan_inputs()
    plan = ShardedUpdatePlan(keyed, groups, meta, 0, 2)
    rng = np.random.RandomState(1)
    gi = plan.owned[0]
    leaves = [rng.randn(*meta[li][0]).astype(meta[li][1])
              for li in groups[gi]]
    payload = plan.pack_group(gi, leaves)
    out = plan.unpack_group(gi, payload)
    for a, b in zip(leaves, out):
        np.testing.assert_array_equal(a, b)
    # a mismatched frame (different program) is refused loudly
    with pytest.raises(ValueError, match="different bucket plans"):
        plan.unpack_group(gi, payload + b"\0")


# -------------------------------------------------------- param store

def test_param_store_nondestructive_retention_timeout():
    st = ParamStore(retain=2)
    st.put(7, 1, b"one")
    assert st.get(7, 1, timeout_ms=100) == b"one"
    assert st.get(7, 1, timeout_ms=100) == b"one"    # non-destructive
    st.put(7, 1, b"one")                             # idempotent resend
    assert st.get(7, 1, timeout_ms=100) == b"one"
    st.put(7, 2, b"two")
    st.put(7, 3, b"three")          # retain=2: seq 1 pruned
    assert st.get(7, 3, timeout_ms=100) == b"three"
    assert st.get(7, 2, timeout_ms=100) == b"two"
    with pytest.raises(TimeoutError, match="owner never published"):
        st.get(7, 1, timeout_ms=50)
    # a blocked get wakes on put
    got = {}

    def getter():
        got["v"] = st.get(9, 5, timeout_ms=5000)

    t = threading.Thread(target=getter)
    t.start()
    time.sleep(0.05)
    st.put(9, 5, b"late")
    t.join(5)
    assert got.get("v") == b"late"


def test_param_wire_roundtrip_tcp():
    """OP_PARAM_PUT/OP_PARAM_GET through the real transport: idempotent
    last-wins put, non-destructive blocking get, TimeoutError on a
    never-published frame."""
    eng = PSServer(num_workers=1, engine_threads=1)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    cli = RemotePSBackend([f"127.0.0.1:{srv.port}"])
    try:
        key = (1 << 41) | 3
        payload = np.arange(5000, dtype=np.float32).tobytes()
        cli.param_put(key, 1, payload)
        assert cli.param_get(key, 1, timeout_ms=2000) == payload
        assert cli.param_get(key, 1, timeout_ms=2000) == payload
        # blocking get resolved by a later put
        got = {}

        def getter():
            got["v"] = cli.param_get(key, 2, timeout_ms=10000)

        t = threading.Thread(target=getter)
        t.start()
        time.sleep(0.1)
        cli.param_put(key, 2, b"x" * 1000)
        t.join(10)
        assert got.get("v") == b"x" * 1000
        with pytest.raises(TimeoutError):
            cli.param_get(key, 99, timeout_ms=300)
    finally:
        cli.close()
        srv.close()
        eng.close()


def test_param_routing_through_the_server_plane():
    """PlanePSBackend param ops: stateless ring-successor routing
    (identical on every worker, no placement entry), plane-held stores
    for in-process shards, and a shard death rerouting to the next
    successor — the op's OWN shard is the one blamed, idempotently."""
    from byteps_tpu.server.plane import PlanePSBackend

    shards = [PSServer(num_workers=1, engine_threads=1)
              for _ in range(3)]
    plane = PlanePSBackend(shards, num_workers=1, replicas=1,
                           owns_shards=True)
    try:
        key = (1 << 41) | (2 << 16) | 1
        _, s0 = plane._param_client(key)
        plane.param_put(key, 1, b"frame-one")
        assert plane.param_get(key, 1, timeout_ms=1000) == b"frame-one"
        # two plane views (two "workers") resolve the same shard
        plane2 = PlanePSBackend(shards, num_workers=1, replicas=1)
        _, s0b = plane2._param_client(key)
        assert s0b == s0
        # the mailbox's shard dies: routing moves to the next successor
        # and a fresh put/get lands there (frames are recomputable)
        plane.fail_shard(s0)
        _, s1 = plane._param_client(key)
        assert s1 != s0
        plane.param_put(key, 2, b"frame-two")
        assert plane.param_get(key, 2, timeout_ms=1000) == b"frame-two"
    finally:
        plane.close()


# ------------------------------------------------------ parity harness

def _chain_loss(p, batch):
    x, y = batch
    h = x
    for i in range(len(p)):
        h = jax.numpy.tanh(h @ p[f"w{i}"])
    return ((h - y) ** 2).mean()


def _chain_setup(depth=4, dim=128, seed=3):
    rng = np.random.RandomState(seed)
    params = {f"w{i}": (rng.randn(dim, dim) / 12).astype(np.float32)
              for i in range(depth)}
    return params


def _chain_batches(dim, seed, n, bs=32):
    r = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = r.randn(bs, dim).astype(np.float32)
        out.append((x, np.tanh(x)))
    return out


def _one_dev_mesh():
    from byteps_tpu.parallel.mesh import make_mesh
    return make_mesh({"data": 1}, devices=jax.devices()[:1])


class _SlowPulls:
    """Delegating proxy: every grad pull sleeps first, so a round's
    pulls (and the param publishes behind them) are still outstanding
    when the next round's pushes arrive — the two-round window rig."""

    def __init__(self, inner, delay=0.04):
        self._inner = inner
        self._delay = delay

    def pull(self, key, out, round=0, timeout_ms=30000):
        time.sleep(self._delay)
        return self._inner.pull(key, out, round=round,
                                timeout_ms=timeout_ms)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _run_dp_arm(loss_fn, params0, worker_batches, *, dp, sharded,
                cross="0", name, partition_bytes, steps, tx=None,
                conns=8, expect_engaged=None, slow_pulls=0.0):
    """Run ``dp`` replica trainers (threads) over a real TCP server,
    each with its OWN transport backend (separate connection pools —
    the deployment shape: one socket pool per worker process). Returns
    (per-worker final leaves, registry snapshot)."""
    eng = PSServer(num_workers=dp, engine_threads=2)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    os.environ.update(BPS_ENABLE_PS="1", BPS_NUM_WORKER=str(dp),
                      BPS_SERVER_ADDRS=f"127.0.0.1:{srv.port}",
                      BPS_SHARDED_UPDATE=sharded, BPS_CROSS_STEP=cross,
                      BPS_PS_CONNS=str(conns))
    bps.init(config=bps.Config.from_env())
    get_registry().reset()
    mesh = _one_dev_mesh()
    privs = []
    try:
        trs = []
        for w in range(dp):
            tr = DistributedTrainer(loss_fn, dict(params0),
                                    tx or optax.adam(1e-3), mesh=mesh,
                                    partition_bytes=partition_bytes,
                                    name=name, shard_rank=w)
            priv = RemotePSBackend([f"127.0.0.1:{srv.port}"],
                                   conns_per_shard=conns)
            tr._ps_exchange.backend = (_SlowPulls(priv, slow_pulls)
                                       if slow_pulls else priv)
            privs.append(priv)
            trs.append(tr)
        errs = []

        def run(w):
            try:
                for b in worker_batches[w][:steps]:
                    trs[w].step(b)
                trs[w].drain()
            except BaseException as e:   # noqa: BLE001 — asserted below
                errs.append((w, e))

        ts = [threading.Thread(target=run, args=(w,)) for w in range(dp)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(180)
        assert not any(t.is_alive() for t in ts), \
            "workers hung: " + repr([tr._ps_exchange.debug_state()
                                     for tr in trs])
        assert not errs, errs
        engaged = (sharded == "1" and dp > 1
                   if expect_engaged is None else expect_engaged)
        for tr in trs:
            assert (tr._sharded is not None) == engaged, \
                f"sharded engage mismatch (want {engaged})"
        if engaged:
            # the ZeRO memory claim: optimizer state exists ONLY for
            # the replica's owned groups
            for tr in trs:
                alloc = {gi for gi, s in enumerate(tr._chunked.states)
                         if s is not None}
                assert alloc == set(tr._sharded.plan.owned), \
                    (alloc, tr._sharded.plan.owned)
        finals = [[np.asarray(l)
                   for l in jax.tree_util.tree_leaves(tr.params)]
                  for tr in trs]
        snap = get_registry().snapshot()
        for tr in trs:
            tr.close()
        return finals, snap
    finally:
        bps.shutdown()
        for p in privs:
            p.close()
        srv.close()
        eng.close()


@pytest.mark.parametrize("dp", [2, 4])
def test_sharded_parity_mlp_chain(dp, _clean_env):
    """Sharded-vs-full parity, multi-step adam, dp ∈ {2, 4}. Within an
    arm, REPLICAS agree bitwise at any dp (every worker installs the
    owner's exact bytes). Across arms: bitwise at dp=2; at dp=4 the
    SERVER's merge is arrival-order dependent (reduce_sum is applied in
    task order, and float addition of 4 pushes is not associative —
    ±1 ulp run to run, a pre-existing engine property orthogonal to
    sharding), so the cross-arm comparison is near-ulp tolerance."""
    dim, steps = 96, 4
    params0 = _chain_setup(depth=4, dim=dim)
    batches = [_chain_batches(dim, 10 + w, steps) for w in range(dp)]
    finals = {}
    pulls = {}
    for mode in ("1", "0"):
        f, snap = _run_dp_arm(_chain_loss, params0, batches, dp=dp,
                              sharded=mode, name=f"zx{dp}-{mode}",
                              partition_bytes=dim * dim * 4, steps=steps)
        # replicas agree bitwise within an arm
        for other in f[1:]:
            for a, b in zip(f[0], other):
                np.testing.assert_array_equal(a, b)
        finals[mode] = f[0]
        pulls[mode] = snap
    for a, b in zip(finals["1"], finals["0"]):
        if dp == 2:          # 2-push sums are commutative: exact
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # registry-measured pull reduction: the sharded arm's grad pull
    # bytes are ~1/dp of the full arm's (dp workers pulled everything)
    full, shard = pulls["0"]["ps/pull_bytes"], pulls["1"]["ps/pull_bytes"]
    assert shard < full * (1.0 / dp + 0.2), (shard, full, dp)
    assert pulls["1"]["ps/param_put_bytes"] > 0
    assert pulls["1"]["ps/param_fetch_bytes"] > 0
    assert pulls["0"]["ps/param_put_bytes"] == 0
    # per-layer pull counters registered dynamically and moving
    per_layer = [k for k, v in pulls["1"].items()
                 if k.startswith("ps/pull_bytes/") and v]
    assert per_layer, sorted(pulls["1"])


def test_sharded_parity_cross_step_two_rounds_in_flight(_clean_env):
    """Cross-step composition: BPS_CROSS_STEP=1 with slowed pulls (two
    rounds genuinely in flight per key) must stay bitwise-identical to
    the sharded draining arm AND to the full-apply arm."""
    dim, steps, dp = 96, 5, 2
    params0 = _chain_setup(depth=4, dim=dim)
    batches = [_chain_batches(dim, 20 + w, steps) for w in range(dp)]
    finals = {}
    for mode, cross in (("1", "1"), ("1", "0"), ("0", "1")):
        f, _ = _run_dp_arm(_chain_loss, params0, batches, dp=dp,
                           sharded=mode, cross=cross,
                           name=f"zc-{mode}{cross}",
                           partition_bytes=dim * dim * 4, steps=steps,
                           slow_pulls=0.04 if cross == "1" else 0.0)
        for other in f[1:]:
            for a, b in zip(f[0], other):
                np.testing.assert_array_equal(a, b)
        finals[(mode, cross)] = f[0]
    for key in [("1", "0"), ("0", "1")]:
        for a, b in zip(finals[("1", "1")], finals[key]):
            np.testing.assert_array_equal(a, b)


def test_sharded_parity_bert_tolerance(_clean_env):
    """Transformer parity under the test_grad_exactness tolerance
    contract (rtol 2e-3 / atol 2e-5), dp=2, multi-step adam."""
    from byteps_tpu.models import bert, transformer
    from test_grad_exactness import equal_count_mlm_batch

    cfg = bert.bert_tiny()
    params0 = transformer.init_params(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, b):
        return bert.mlm_loss(p, cfg, b)

    steps, dp = 3, 2
    batches = [[equal_count_mlm_batch(np.random.RandomState(30 + w + s),
                                      4, 32, cfg.vocab_size)
                for s in range(steps)] for w in range(dp)]
    finals = {}
    for mode in ("1", "0"):
        f, _ = _run_dp_arm(loss_fn, params0, batches, dp=dp,
                           sharded=mode, name=f"zb-{mode}",
                           partition_bytes=64 << 10, steps=steps)
        finals[mode] = f[0]
    for a, b in zip(finals["1"], finals["0"]):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def test_sharded_falls_back_dp1_and_coupled_tx(_clean_env):
    """Probe-or-fallback: dp=1 and a non-decomposable optimizer both
    run the FULL apply (state is None) and still train correctly."""
    dim = 64
    params0 = _chain_setup(depth=2, dim=dim)
    batches = [_chain_batches(dim, 40, 2)]
    f, _ = _run_dp_arm(_chain_loss, params0, batches, dp=1, sharded="1",
                       name="zf1", partition_bytes=dim * dim * 4,
                       steps=2)
    # dp=1: engage assertion inside the harness is skipped via the
    # trainer itself — verify by re-running and checking the state
    eng = PSServer(num_workers=1, engine_threads=1)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    os.environ.update(BPS_ENABLE_PS="1", BPS_NUM_WORKER="1",
                      BPS_SERVER_ADDRS=f"127.0.0.1:{srv.port}",
                      BPS_SHARDED_UPDATE="1", BPS_CROSS_STEP="0")
    try:
        bps.init(config=bps.Config.from_env())
        tr = DistributedTrainer(_chain_loss, dict(params0),
                                optax.adam(1e-3), mesh=_one_dev_mesh(),
                                partition_bytes=dim * dim * 4,
                                name="zf2")
        tr.step(batches[0][0])
        assert tr._sharded is None           # dp=1 fallback
        tr.close()
        bps.shutdown()
        # coupled tx: clip_by_global_norm spans the tree — even with a
        # declared shard world of 2 the decomposability probe refuses
        os.environ["BPS_SHARD_WORLD"] = "2"
        bps.init(config=bps.Config.from_env())
        tx = optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(0.1))
        tr2 = DistributedTrainer(_chain_loss, dict(params0), tx,
                                 mesh=_one_dev_mesh(),
                                 partition_bytes=dim * dim * 4,
                                 name="zf3", shard_rank=0)
        tr2.step(batches[0][0])
        assert tr2._sharded is None
        tr2.close()
    finally:
        os.environ.pop("BPS_SHARD_WORLD", None)
        bps.shutdown()
        srv.close()
        eng.close()


def test_sharded_fallback_keeps_training_when_disabled_mid_config(
        _clean_env):
    """BPS_SHARDED_UPDATE with BPS_APPLY_CHUNKED=0 logs the fallback
    and trains on the fused tail."""
    os.environ["BPS_APPLY_CHUNKED"] = "0"
    try:
        dim = 64
        params0 = _chain_setup(depth=2, dim=dim)
        batches = [_chain_batches(dim, 41, 2), _chain_batches(dim, 42, 2)]
        f, _ = _run_dp_arm(_chain_loss, params0, batches, dp=2,
                           sharded="1", name="zfa",
                           partition_bytes=dim * dim * 4, steps=2,
                           expect_engaged=False)
        assert f
    finally:
        os.environ.pop("BPS_APPLY_CHUNKED", None)


# -------------------------------------------------- scheduler overtake

def test_param_frame_overtakes_grad_burst_under_throttle():
    """A param frame enqueued AFTER a large grad burst is admitted
    ahead of the queued grads (CLASS_ACT base + first-use priority) —
    trace-asserted through the real transport under a throttled NIC."""
    from byteps_tpu.server import admission as wire_sched
    from byteps_tpu.server.throttle import Nic

    wire_sched.configure_send(512 << 10)
    eng = PSServer(num_workers=1, engine_threads=2)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    cli = RemotePSBackend([f"127.0.0.1:{srv.port}"], nic=Nic(8e6))
    try:
        nb = 4 << 20
        for k in (1, 2, 3):
            cli.init_key(k, nb)
        pkey = (1 << 41) | (1 << 16)
        cli.set_send_priority(pkey, 100)    # next-step first-use prio
        blob = np.ones(nb // 4, np.float32)

        def grad(k):
            cli.push(k, blob)

        gts = [threading.Thread(target=grad, args=(k,))
               for k in (1, 2, 3)]
        for t in gts:
            t.start()
        time.sleep(0.3)            # the burst holds the credit first
        cli.param_put(pkey, 1, b"p" * (256 << 10))
        for t in gts:
            t.join()
        tr = wire_sched.send_scheduler().trace()
        params = [e for e in tr if e["class"] == "act"
                  and e["key"] == pkey]
        assert params, tr
        assert params[0]["overtook"], params
        assert params[0]["prio"] == 100
        # the mailbox really got the frame
        assert srv.param_store().get(pkey, 1, timeout_ms=2000)
    finally:
        wire_sched.configure_send(0)
        cli.close()
        srv.close()
        eng.close()


# ------------------------------------------------------- owner death

def _mini_workers(dp=2, n_leaves=4, size=2048):
    rng = np.random.RandomState(0)
    grads = [{f"k{i}": rng.randn(size).astype(np.float32)
              for i in range(n_leaves)} for _ in range(dp)]
    params = {f"k{i}": np.zeros(size, np.float32)
              for i in range(n_leaves)}
    be = HostPSBackend(num_servers=1, num_workers=dp, engine_threads=2)
    reg = NameRegistry()
    exs = [PSGradientExchange(be, partition_bytes=4 << 10, registry=reg)
           for _ in range(dp)]
    tx = optax.adam(1e-3)
    states = [build_sharded_state(exs[w], params, tx, "od", w, dp)
              for w in range(dp)]
    return be, exs, tx, params, grads, states


def test_owner_death_surfaces_loud_diagnostic_and_watchdog():
    """SATELLITE: worker 1 (an owner) pushes its grads and pulls its
    shard but DIES before publishing its param frames. Worker 0 must
    (a) raise a loud per-key diagnostic naming group/owner/step from
    the param-fetch timeout, and (b) show ``await_param`` buckets in
    the watchdog's dump while it waits — never a silent hang."""
    from concurrent.futures import ThreadPoolExecutor

    from byteps_tpu.obs.watchdog import StallWatchdog, format_dump
    from byteps_tpu.optim import ChunkedApply

    os.environ["BPS_PARAM_TIMEOUT_MS"] = "2500"
    be, exs, tx, params, grads, states = _mini_workers()
    try:
        plan0 = states[0].plan
        assert states[0].timeout_ms == 2500
        dumps = []
        wd = StallWatchdog(exs[0], stall_sec=0.4,
                           on_dump=lambda s, d: dumps.append((s, d)))

        # worker 1: pushes everything (grad pulls of its owned buckets
        # run automatically), then dies — NO tail, NO param publish
        h1 = exs[1].exchange_ingest(params, name="od",
                                    sharded=states[1].plan.round_view())
        h1.feed(range(4), [grads[1][f"k{i}"] for i in range(4)])
        h1.finish()

        # worker 0 runs its full tail and must fail LOUDLY on the fetch
        chunked = ChunkedApply(tx, params,
                               [list(g) for g in plan0.groups],
                               donate=False, owned=plan0.owned_set)
        h2d_ex = ThreadPoolExecutor(1)
        flat = [jax.numpy.asarray(params[f"k{i}"]) for i in range(4)]
        h0 = exs[0].exchange_ingest(params, name="od",
                                    sharded=plan0.round_view())
        h0.feed(range(4), [grads[0][f"k{i}"] for i in range(4)])
        h0.finish()
        with pytest.raises(RuntimeError) as ei:
            states[0].run_tail(
                h0, chunked, flat, 1, states[0].next_seq(),
                lambda li, arr: jax.device_put(arr / 2.0),
                lambda li, a: jax.device_put(a), h2d_ex, None)
        msg = str(ei.value)
        assert "param frame for group" in msg
        assert "owner replica 1" in msg
        assert "never arrived" in msg
        # the watchdog saw the await_param wedge while the fetch hung
        assert dumps, "watchdog never fired"
        state = dumps[-1][0]
        awaits = [b for r in state["rounds"] for b in r["buckets"]
                  if b["state"] == "await_param"]
        assert awaits and all(b.get("owner") == 1 for b in awaits), state
        text = format_dump(state, 1.0)
        assert "awaiting param publish from owner replica 1" in text
        assert "owner replica never published" in text
        wd.stop()
        h2d_ex.shutdown(wait=False)
    finally:
        os.environ.pop("BPS_PARAM_TIMEOUT_MS", None)
        for ex in exs:
            ex.close()
        for st in states:
            if st is not None:
                st.close()
        be.close()


def test_skipped_bucket_push_failure_blames_itself_not_the_owner():
    """A failed push of a NON-owned bucket streams no leaf and feeds no
    fetch, so it only lands in the round's error slot — the tail must
    surface it as THIS replica's push failure, never as a spurious
    owner-death diagnostic blaming a healthy peer."""
    from concurrent.futures import ThreadPoolExecutor

    from byteps_tpu.optim import ChunkedApply

    os.environ["BPS_PARAM_TIMEOUT_MS"] = "1500"
    be, exs, tx, params, grads, states = _mini_workers()
    try:
        plan0 = states[0].plan
        bad_key = exs[0]._plan(params, "od")[2][
            sorted(plan0.skip_groups)[0]][0]

        class _FailPush:
            def __init__(self, inner):
                self._inner = inner

            def push(self, key, data, **kw):
                if key == bad_key:
                    raise ConnectionError("injected push failure")
                return self._inner.push(key, data, **kw)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        exs[0].backend = _FailPush(be)
        chunked = ChunkedApply(tx, params,
                               [list(g) for g in plan0.groups],
                               donate=False, owned=plan0.owned_set)
        h2d_ex = ThreadPoolExecutor(1)
        flat = [jax.numpy.asarray(params[f"k{i}"]) for i in range(4)]
        h0 = exs[0].exchange_ingest(params, name="od",
                                    sharded=plan0.round_view())
        h0.feed(range(4), [grads[0][f"k{i}"] for i in range(4)])
        h0.finish()
        # two legitimate surfacing paths, depending on whether the
        # reader was still draining when the push died: the raw error
        # via the readyq, or the round's _pull_err via the tail's
        # final check / the fetch root-cause rewrite. NEVER the
        # owner-death blame aimed at a healthy peer.
        with pytest.raises((RuntimeError, ConnectionError)) as ei:
            states[0].run_tail(
                h0, chunked, flat, 1, states[0].next_seq(),
                lambda li, arr: jax.device_put(arr / 2.0),
                lambda li, a: jax.device_put(a), h2d_ex, None)
        msg = str(ei.value)
        assert "owner died" not in msg, msg
        chain = repr(ei.value) + repr(ei.value.__cause__)
        assert "injected push failure" in chain, chain
        h2d_ex.shutdown(wait=False)
    finally:
        os.environ.pop("BPS_PARAM_TIMEOUT_MS", None)
        for ex in exs:
            ex.close()
        for st in states:
            if st is not None:
                st.close()
        be.close()


@pytest.mark.slow
def test_sharded_parity_transformer_dp4_tolerance(_clean_env):
    """Slow-lane dp=4 transformer sweep: bert under the grad-exactness
    tolerance contract with four replicas, multi-step adam, cross-step
    on (two rounds in flight on every key)."""
    from byteps_tpu.models import bert, transformer
    from test_grad_exactness import equal_count_mlm_batch

    cfg = bert.bert_tiny()
    params0 = transformer.init_params(jax.random.PRNGKey(1), cfg)

    def loss_fn(p, b):
        return bert.mlm_loss(p, cfg, b)

    steps, dp = 3, 4
    batches = [[equal_count_mlm_batch(np.random.RandomState(50 + w + s),
                                      4, 32, cfg.vocab_size)
                for s in range(steps)] for w in range(dp)]
    finals = {}
    for mode in ("1", "0"):
        f, _ = _run_dp_arm(loss_fn, params0, batches, dp=dp,
                           sharded=mode, cross="1", name=f"zb4-{mode}",
                           partition_bytes=64 << 10, steps=steps)
        for other in f[1:]:
            for a, b in zip(f[0], other):
                np.testing.assert_array_equal(a, b)
        finals[mode] = f[0]
    for a, b in zip(finals["1"], finals["0"]):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


@pytest.mark.slow
def test_bench_ps_zero_smoke():
    """CI slow-lane smoke of the bench A/B: the sharded arm must
    engage, the registry must show the grad-pull reduction, and the
    ratio must be finite. The win-margin assertion lives in the bench
    environment, not on a loaded 2-core CI runner."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    out = bench.ps_zero_breakdown(iters=3, warm=1, dim=256, depth=4,
                                  batch=64, pairs=1)
    assert out["sharded_engaged"], out
    assert out["sharded_vs_full"] > 0, out
    assert out["grad_pull_ratio"] < 0.75, out
    assert out["param_fetch_bytes"] > 0, out


# ------------------------------------------- elasticity (ISSUE 13)

def test_param_latest_tcp_and_store():
    """OP_PARAM_SEQ: the mailbox's newest retained seq, 0 when empty —
    in-process and over the real transport."""
    st = ParamStore(retain=4)
    assert st.latest(7) == 0
    st.put(7, 3, b"x")
    st.put(7, 5, b"y")
    assert st.latest(7) == 5
    eng = PSServer(num_workers=1, engine_threads=1)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    cli = RemotePSBackend([f"127.0.0.1:{srv.port}"])
    try:
        key = (1 << 41) | 9
        assert cli.param_latest(key) == 0
        cli.param_put(key, 4, b"frame")
        assert cli.param_latest(key) == 4
    finally:
        cli.close()
        srv.close()
        eng.close()


def test_param_seq_resumes_from_retained_frames(_clean_env):
    """Elastic-rejoin regression (ISSUE 13 satellite): a rejoining
    sharded-update owner must resume its param-mailbox sequence from
    the server's RETAINED frames, not re-publish from seq 0 — stale
    seqs overwrite nothing in the last-wins mailbox while every
    non-owner blocks on the real next seq."""
    eng = PSServer(num_workers=1, engine_threads=1)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    cli = RemotePSBackend([f"127.0.0.1:{srv.port}"])
    exs, sts = [], []
    try:
        rng = np.random.RandomState(0)
        tree = {f"k{i}": rng.randn(3000).astype(np.float32)
                for i in range(4)}
        ex = PSGradientExchange(cli, partition_bytes=4 << 10)
        exs.append(ex)
        st = build_sharded_state(ex, tree, optax.adam(1e-3), "seq", 0, 2)
        sts.append(st)
        assert st is not None
        assert st.next_seq() == 1          # cold mailbox: starts at 1
        # the predecessor's frames survive in the mailbox up to seq 5
        key = next(iter(st.plan.param_keys.values()))
        cli.param_put(key, 5, b"x" * 64)
        ex2 = PSGradientExchange(cli, partition_bytes=4 << 10)
        exs.append(ex2)
        st2 = build_sharded_state(ex2, tree, optax.adam(1e-3), "seq",
                                  0, 2)
        sts.append(st2)
        assert st2.next_seq() == 6, \
            "rejoining owner restarted its param seqs from 0"
    finally:
        for st in sts:
            if st is not None:
                st.close()
        for ex in exs:
            ex.close()
        cli.close()
        srv.close()
        eng.close()


def test_reshard_minimal_movement_and_determinism():
    """Membership epoch bumps move only the delta: a LEAVE reassigns
    the departed rank's orphans alone (kept owners stay put), a JOIN
    levels the newcomer up by bounded moves — and every rank computes
    the identical next plan from the same inputs."""
    keyed, groups, meta = _plan_inputs()
    world = 4
    plans = [ShardedUpdatePlan(keyed, groups, meta, r, world)
             for r in range(world)]
    p0 = plans[0]
    leaver = p0.owner[0]
    live = frozenset(range(world)) - {leaver}
    q = [p.reshard(live) for p in plans]
    for r in q[1:]:
        assert r.owner == q[0].owner         # deterministic across ranks
    assert all(o in live for o in q[0].owner)
    kept = [gi for gi in range(len(groups)) if p0.owner[gi] != leaver]
    assert all(q[0].owner[gi] == p0.owner[gi] for gi in kept), \
        "a live owner's group moved on an unrelated LEAVE"
    # JOIN back: the rejoined rank is leveled up, spread bounded by the
    # largest single weight, again identically on every rank
    j = [r.reshard(frozenset(range(world))) for r in q]
    for r in j[1:]:
        assert r.owner == j[0].owner
    assert any(o == leaver for o in j[0].owner), "joiner got nothing"
    lv = sorted(j[0].live)
    spread = max(j[0].load[r] for r in lv) - min(j[0].load[r] for r in lv)
    assert spread <= max(j[0].weights), (spread, j[0].weights)
    # a rank OUTSIDE the live set owns nothing but keeps a valid plan
    # (it still pushes grads and fetches every group's params)
    drained = ShardedUpdatePlan(keyed, groups, meta, leaver, world,
                                live=live)
    assert drained.owned == ()
    assert drained.pull_buckets == frozenset()
    assert set(drained.fetch_order) == set(range(len(groups)))
    # the authoritative-map path (checkpoint meta) installs verbatim
    w = j[0].with_owner_map(j[0].owner)
    assert w.owner == j[0].owner


def test_reshard_weights_quantized_from_live_counters():
    """live_group_weights: reads the per-layer push/pull byte counters,
    quantizes to ratio rungs, None on a cold registry."""
    from byteps_tpu.sharded_update import live_group_weights
    keyed, groups, meta = _plan_inputs()
    plan = ShardedUpdatePlan(keyed, groups, meta, 0, 2)
    reg = get_registry()
    reg.reset()
    assert live_group_weights(plan, "wq", registry=reg) is None
    # traffic on the first group's buckets only
    for bi in plan.needed[0]:
        reg.counter(
            f"ps/push_bytes/wq.{plan.bucket_labels[bi]}").inc(1 << 20)
    w = live_group_weights(plan, "wq", registry=reg)
    assert w is not None and len(w) == len(groups)
    assert w[0] == max(w)
    assert all(x >= 1 for x in w)            # floor: no zero weights


def test_reshard_crashed_owner_falls_back_loud(caplog):
    """A LEAVE by death: the dead rank never publishes its handoff
    frames — the gaining rank's fetch times out, WARNs naming the
    group and dead rank, and the group's moments restart from init
    (training continues; a sharded checkpoint restore is the lossless
    path)."""
    import logging

    from byteps_tpu.common.logging import get_logger
    from byteps_tpu.optim import ChunkedApply

    keyed, groups, meta = _plan_inputs()
    rng = np.random.RandomState(1)
    tree = {f"k{i}": rng.randn(3000 + 64 * i).astype(np.float32)
            for i in range(5)}
    leaves = jax.tree_util.tree_leaves(tree)
    be = HostPSBackend(num_servers=1, num_workers=1, engine_threads=1)
    ex = PSGradientExchange(be, partition_bytes=4 << 10)
    try:
        st = build_sharded_state(ex, tree, optax.adam(1e-3), "crash",
                                 0, 2)
        assert st is not None
        plan = st.plan
        dead = 1
        victim_groups = [gi for gi, o in enumerate(plan.owner)
                         if o == dead]
        assert victim_groups, "rank 1 owned nothing — degenerate plan"
        chunked = ChunkedApply(optax.adam(1e-3), tree,
                               plan.groups, donate=False,
                               owned=plan.owned_set)
        records = []
        handler = logging.Handler()
        handler.emit = lambda r: records.append(r.getMessage())
        logger = get_logger()
        logger.addHandler(handler)
        try:
            out = st.reshard(chunked, leaves, frozenset({0}),
                             handoff_timeout_ms=200)
        finally:
            logger.removeHandler(handler)
        assert out["member_epoch"] == 2
        assert set(out["gained"]) == set(victim_groups)
        warned = [m for m in records if "never published" in m]
        assert warned, records
        # ownership flipped; fresh-init state allocated for the gained
        # groups, so training continues
        assert chunked.owned == frozenset(range(len(plan.groups)))
        for gi in victim_groups:
            assert chunked.states[gi] is not None
        st.close()
    finally:
        ex.close()
        be.close()


def _phased_rig(phases, params0, wb, name, dp=2):
    """dp trainer threads over one TCP server; between phases every
    rank reshards CONCURRENTLY (publish-before-fetch per rank — the
    protocol's no-deadlock shape). Returns per-worker final flats."""
    eng = PSServer(num_workers=dp, engine_threads=2)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    os.environ.update(BPS_ENABLE_PS="1", BPS_NUM_WORKER=str(dp),
                      BPS_SERVER_ADDRS=f"127.0.0.1:{srv.port}",
                      BPS_SHARDED_UPDATE="1", BPS_CROSS_STEP="0")
    bps.init(config=bps.Config.from_env())
    get_registry().reset()
    from byteps_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    privs, trs = [], []
    try:
        for w in range(dp):
            tr = DistributedTrainer(_chain_loss, dict(params0),
                                    optax.adam(1e-3), mesh=mesh,
                                    partition_bytes=8 << 10, name=name,
                                    shard_rank=w)
            priv = RemotePSBackend([f"127.0.0.1:{srv.port}"],
                                   conns_per_shard=8)
            tr._ps_exchange.backend = priv
            privs.append(priv)
            trs.append(tr)
        done = 0
        for steps, live in phases:
            if live is not None:
                rerrs = []

                def rs(w):
                    try:
                        trs[w].reshard(live, handoff_timeout_ms=20000)
                    except BaseException as e:  # noqa: BLE001
                        rerrs.append((w, e))

                rts = [threading.Thread(target=rs, args=(w,))
                       for w in range(dp)]
                for t in rts:
                    t.start()
                for t in rts:
                    t.join(60)
                assert not rerrs, rerrs
                owners = {tuple(tr._sharded.plan.owner) for tr in trs}
                assert len(owners) == 1, \
                    f"reshard diverged across ranks: {owners}"
            errs = []

            def run(w, s=done, n=steps):
                try:
                    for i in range(n):
                        trs[w].step(wb[w][s + i])
                    trs[w].drain()
                except BaseException as e:  # noqa: BLE001
                    errs.append((w, e))

            ts = [threading.Thread(target=run, args=(w,))
                  for w in range(dp)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(180)
            assert not any(t.is_alive() for t in ts), "workers hung"
            assert not errs, errs
            done += steps
        # the 1/dp memory contract survives membership changes: state
        # allocated exactly for the CURRENT owned groups
        for tr in trs:
            alloc = {gi for gi, s in enumerate(tr._chunked.states)
                     if s is not None}
            assert alloc == set(tr._sharded.plan.owned), \
                (alloc, tr._sharded.plan.owned)
        finals = [[np.asarray(l)
                   for l in jax.tree_util.tree_leaves(tr.params)]
                  for tr in trs]
        for tr in trs:
            tr.close()
        return finals
    finally:
        bps.shutdown()
        for p in privs:
            p.close()
        srv.close()
        eng.close()


def test_reshard_leave_join_bitwise_with_handoff(_clean_env):
    """LIVE MEMBERSHIP CHANGE end to end: dp=2 trains 3 steps, rank 1
    gracefully LEAVES the ownership plan (its groups' optimizer state
    hands off through the param mailbox), 3 more steps run with rank 0
    owning everything, then rank 1 REJOINS (state hands back) for 2
    steps — and the whole trajectory is BITWISE identical to an
    uninterrupted run, on both replicas. No server re-init, no key
    migration, no global drain: only group ownership moved."""
    params0 = _chain_setup(depth=3, dim=64)
    wb = [_chain_batches(64, 10 + w, 8, bs=16) for w in range(2)]
    ref = _phased_rig([(8, None)], params0, wb, "rsref")
    got = _phased_rig([(3, None), (3, frozenset({0})),
                       (2, frozenset({0, 1}))], params0, wb, "rsgot")
    for a, b in zip(got[0], got[1]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref[0], got[0]):
        np.testing.assert_array_equal(a, b)
    # membership transitions are first-class flight events — a
    # post-reshard postmortem names the epoch, whatever keys it filters
    from byteps_tpu.obs import flight
    evs = flight.get_recorder().events(keys=[12345])   # unrelated key
    kinds = {e["kind"] for e in evs}
    assert "reshard" in kinds, kinds
    assert "member_leave" in kinds and "member_join" in kinds, kinds


def test_sharded_checkpoint_roundtrip_no_fallback(_clean_env, tmp_path):
    """DURABLE SHARDED STATE: save under BPS_SHARDED_UPDATE=1 (each
    owner persists its 1/dp opt_state slice), restore into fresh
    trainers, and continue WITHOUT the restored-full-tree fallback
    firing — the continued run is BITWISE identical to an
    uninterrupted one at dp=2."""
    from byteps_tpu.checkpoint import save_sharded_checkpoint

    params0 = _chain_setup(depth=3, dim=64)
    wb = [_chain_batches(64, 20 + w, 8, bs=16) for w in range(2)]
    ck = str(tmp_path / "ck")

    def run_rig(steps, restore=False, save=False, start=0, name="ckpt"):
        dp = 2
        eng = PSServer(num_workers=dp, engine_threads=2)
        srv = PSTransportServer(eng, host="127.0.0.1", port=0)
        os.environ.update(BPS_ENABLE_PS="1", BPS_NUM_WORKER=str(dp),
                          BPS_SERVER_ADDRS=f"127.0.0.1:{srv.port}",
                          BPS_SHARDED_UPDATE="1", BPS_CROSS_STEP="0")
        bps.init(config=bps.Config.from_env())
        get_registry().reset()
        from byteps_tpu.parallel.mesh import make_mesh
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        privs, trs = [], []
        try:
            for w in range(dp):
                tr = DistributedTrainer(
                    _chain_loss, dict(params0), optax.adam(1e-3),
                    mesh=mesh, partition_bytes=8 << 10, name=name,
                    shard_rank=w)
                priv = RemotePSBackend([f"127.0.0.1:{srv.port}"],
                                       conns_per_shard=8)
                tr._ps_exchange.backend = priv
                privs.append(priv)
                trs.append(tr)
            if restore:
                for tr in trs:
                    meta = tr.restore_sharded(ck)
                assert meta["step"] == 3
                assert trs[0].step_count == 3
            errs = []

            def run(w):
                try:
                    for i in range(steps):
                        trs[w].step(wb[w][start + i])
                    trs[w].drain()
                except BaseException as e:  # noqa: BLE001
                    errs.append((w, e))

            ts = [threading.Thread(target=run, args=(w,))
                  for w in range(dp)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(180)
            assert not errs, errs
            # the acceptance bound: restore composes with the sharded
            # tail — the full-tree-opt_state fallback never fired
            for tr in trs:
                assert tr._sharded is not None, \
                    "sharded update fell back after restore"
                alloc = {gi for gi, s in enumerate(tr._chunked.states)
                         if s is not None}
                assert alloc == set(tr._sharded.plan.owned)
            if save:
                for tr in trs:
                    save_sharded_checkpoint(ck, tr)
            finals = [[np.asarray(l)
                       for l in jax.tree_util.tree_leaves(tr.params)]
                      for tr in trs]
            for tr in trs:
                tr.close()
            return finals
        finally:
            bps.shutdown()
            for p in privs:
                p.close()
            srv.close()
            eng.close()

    ref = run_rig(6, name="ckref")
    run_rig(3, save=True, name="cksave")
    got = run_rig(3, restore=True, start=3, name="ckrest")
    for a, b in zip(got[0], got[1]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref[0], got[0]):
        np.testing.assert_array_equal(a, b)

"""Correctness tests for push_pull / broadcast over the fake 8-chip mesh —
the analogue of the reference's tests/test_mxnet.py push_pull sum tests
(random 1/2/3-D tensors, multiple dtypes, reference: test_mxnet.py:59-121).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import byteps_tpu as bps
from byteps_tpu.parallel.collectives import PushPullEngine, bucketed_allreduce
from byteps_tpu.parallel.mesh import make_mesh

DP = 8


def stacked(mesh, arrs):
    """Place a [dp, ...] stacked array sharded over the data axis."""
    sharding = NamedSharding(mesh, P("data"))
    return jax.device_put(jnp.asarray(arrs), sharding)


@pytest.mark.parametrize("shape", [(5,), (4, 7), (2, 3, 4)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_push_pull_sums_across_ranks(mesh8, shape, dtype):
    rng = np.random.RandomState(0)
    x = rng.randn(DP, *shape).astype(dtype)
    eng = PushPullEngine(mesh8, average=False)
    out = np.asarray(eng.push_pull(stacked(mesh8, x)), dtype="float64")
    want = x.astype("float64").sum(axis=0)
    tol = 1e-5 if dtype == "float32" else 1e-1
    for r in range(DP):
        np.testing.assert_allclose(out[r], want, rtol=tol, atol=tol)


def test_push_pull_average(mesh8):
    x = np.ones((DP, 16), np.float32) * np.arange(DP)[:, None]
    eng = PushPullEngine(mesh8, average=True)
    out = np.asarray(eng.push_pull(stacked(mesh8, x)))
    np.testing.assert_allclose(out, np.full((DP, 16), np.arange(DP).mean()), rtol=1e-6)


def test_push_pull_pytree_multibucket(mesh8):
    rng = np.random.RandomState(1)
    tree = {
        "w1": rng.randn(DP, 300).astype(np.float32),
        "w2": rng.randn(DP, 40, 10).astype(np.float32),
        "b": rng.randn(DP, 7).astype(np.float32),
    }
    dev = {k: stacked(mesh8, v) for k, v in tree.items()}
    # force several buckets: 100 floats per bucket
    eng = PushPullEngine(mesh8, partition_bytes=400, average=False)
    out = eng.push_pull(dev)
    for k in tree:
        want = tree[k].sum(axis=0)
        got = np.asarray(out[k])
        for r in range(DP):
            np.testing.assert_allclose(got[r], want, rtol=1e-4, atol=1e-4)


def test_engine_caches_compiled_plan(mesh8):
    eng = PushPullEngine(mesh8, average=False)
    x = stacked(mesh8, np.ones((DP, 10), np.float32))
    eng.push_pull(x)
    assert len(eng._programs) == 1
    eng.push_pull(x)
    assert len(eng._programs) == 1


def test_broadcast_parameters(mesh8):
    x = np.arange(DP * 6, dtype=np.float32).reshape(DP, 6)
    eng = PushPullEngine(mesh8)
    out = np.asarray(eng.broadcast(stacked(mesh8, x), root_rank=3))
    for r in range(DP):
        np.testing.assert_allclose(out[r], x[3])


def test_broadcast_replicated_leaves_identity(mesh8):
    """Replicated params — plain numpy, any shape, even leading dim == dp —
    must pass through untouched: they are rank-consistent by construction
    and masked-psum on a replicated [dp, k] weight would corrupt it."""
    eng = PushPullEngine(mesh8)
    tree = {
        "w": np.arange(6.0, dtype=np.float32),          # not divisible by dp
        "v": np.arange(DP * 3.0, dtype=np.float32).reshape(DP, 3),  # ambiguous
        "s": np.float32(2.5),
        "none": None,
        "fn": len,
    }
    out = eng.broadcast(tree, root_rank=3)
    np.testing.assert_allclose(np.asarray(out["w"]), tree["w"])
    np.testing.assert_allclose(np.asarray(out["v"]), tree["v"])
    np.testing.assert_allclose(np.asarray(out["s"]), 2.5)
    assert out["none"] is None and out["fn"] is len


def test_broadcast_stacked_flag_commits_host_arrays(mesh8):
    """stacked=True treats uncommitted [dp, ...] leaves as per-rank rows."""
    eng = PushPullEngine(mesh8)
    x = np.arange(DP * 4, dtype=np.float32).reshape(DP, 4)
    out = np.asarray(eng.broadcast({"g": x}, root_rank=2, stacked=True)["g"])
    for r in range(DP):
        np.testing.assert_allclose(out[r], x[2])
    # stacked=False: even a committed data-sharded leaf passes through
    dev = stacked(mesh8, x)
    keep = np.asarray(eng.broadcast({"g": dev}, root_rank=2,
                                    stacked=False)["g"])
    np.testing.assert_allclose(keep, x)


def test_bucketed_allreduce_inside_shard_map(mesh8):
    """The in-jit form: grads computed per-shard, reduced in buckets."""
    rng = np.random.RandomState(2)
    g1 = rng.randn(DP, 50).astype(np.float32)
    g2 = rng.randn(DP, 30).astype(np.float32)

    def step(ga, gb):
        tree = bucketed_allreduce({"a": ga, "b": gb}, axes=("data",),
                                  partition_bytes=100, average=True)
        return tree["a"], tree["b"]

    fn = jax.jit(jax.shard_map(step, mesh=mesh8, in_specs=P("data"),
                               out_specs=P("data"), check_vma=False))
    oa, ob = fn(stacked(mesh8, g1), stacked(mesh8, g2))
    for r in range(DP):
        np.testing.assert_allclose(np.asarray(oa)[r], g1.mean(0), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ob)[r], g2.mean(0), rtol=1e-5, atol=1e-5)


def test_public_api_push_pull(mesh8):
    bps.init(mesh=mesh8)
    assert bps.size() == DP
    x = stacked(mesh8, np.ones((DP, 4), np.float32))
    out = np.asarray(bps.push_pull(x, average=False))
    np.testing.assert_allclose(out, np.full((DP, 4), DP, np.float32))


def test_public_api_declare_and_resume(mesh8):
    bps.init(mesh=mesh8)
    k1 = bps.declare_tensor("layer0/w")
    k2 = bps.declare_tensor("layer1/w")
    bps.suspend()
    bps.resume(config=bps.Config.from_env(), mesh=mesh8)
    assert bps.declare_tensor("layer0/w") == k1
    assert bps.declare_tensor("layer1/w") == k2


def test_scheduling_credit_bounds_inflight():
    """BPS_SCHEDULING_CREDIT: dispatch still produces correct sums when
    flow control forces blocking on outstanding buckets (reference:
    scheduled_queue.cc:33-45)."""
    import byteps_tpu as bps
    from byteps_tpu.common.config import Config
    # tiny partition → many buckets; tiny credit → constant blocking
    bps.init(Config.from_env(partition_bytes=256, scheduling_credit=512))
    from byteps_tpu.common.global_state import GlobalState
    eng = GlobalState.get().engine
    assert eng.scheduling_credit == 512
    tree = {f"w{i}": jnp.broadcast_to(jnp.full((32,), float(i)), (8, 32))
            for i in range(8)}
    # the gate must actually block on outstanding buckets, not just exist
    calls = []
    real_block = jax.block_until_ready

    def counting_block(x):
        calls.append(1)
        return real_block(x)

    jax.block_until_ready, restore = counting_block, real_block
    try:
        out = eng.push_pull(tree, average=True)
    finally:
        jax.block_until_ready = restore
    assert calls, "credit gate never blocked despite credit < tree bytes"
    for i in range(8):
        np.testing.assert_allclose(np.asarray(out[f"w{i}"]),
                                   np.full((8, 32), float(i)))
    # async path is exempt: non-blocking dispatch contract
    calls.clear()
    jax.block_until_ready = counting_block
    try:
        h = eng.push_pull_async(tree)
        assert not calls, "push_pull_async must not credit-block dispatch"
    finally:
        jax.block_until_ready = restore
    eng.synchronize(h)
    bps.shutdown()


# ---------------------------------------------------------------------------
# The in-jit exchange's two forms (ISSUE 37): the leaves as they are on an
# ICI-only mesh with the default reducer, flat buckets where a reducer
# needs a flat buffer.
# ---------------------------------------------------------------------------

def _grad_tree(rng):
    """Per-rank gradients [DP, ...]: a stacked [L, a, b] leaf, a bfloat16
    leaf, a scalar, a bias."""
    return {
        "stack": rng.randn(DP, 3, 8, 16).astype(np.float32),
        "half": rng.randn(DP, 33).astype(jnp.bfloat16),
        "scalar": rng.randn(DP).astype(np.float32),
        "bias": rng.randn(DP, 7).astype(np.float32),
    }


@pytest.mark.parametrize("average", [True, False], ids=["mean", "sum"])
@pytest.mark.parametrize("empty", [False, True], ids=["tree", "empty_tree"])
def test_leaf_form_equals_bucketed_form_and_the_per_rank_mean(
        mesh8, average, empty):
    from byteps_tpu.parallel.collectives import leaf_allreduce, tree_allreduce
    tree = {} if empty else _grad_tree(np.random.RandomState(3))

    def run(fn):
        def step(t):
            # a rank's row of every leaf, reduced, and handed back a row
            local = jax.tree_util.tree_map(lambda x: x[0], t)
            out = fn(local)
            return jax.tree_util.tree_map(lambda x: x[None], out)
        return jax.jit(jax.shard_map(step, mesh=mesh8, in_specs=P("data"),
                                     out_specs=P("data"), check_vma=False))(
            {k: stacked(mesh8, v) for k, v in tree.items()})

    leaf = run(lambda t: leaf_allreduce(t, ("data",), average=average))
    auto = run(lambda t: tree_allreduce(t, ("data",), partition_bytes=256,
                                        average=average))
    bucket = run(lambda t: bucketed_allreduce(
        t, ("data",), partition_bytes=256, average=average))
    assert set(leaf) == set(tree) == set(auto) == set(bucket)
    for k, x in tree.items():
        want = np.asarray(x, np.float64).sum(0) / (DP if average else 1)
        tol = 1e-5 if x.dtype == np.float32 else 5e-2
        for got in (leaf[k], auto[k], bucket[k]):
            assert got.shape == x.shape and got.dtype == x.dtype
            for r in range(DP):
                np.testing.assert_allclose(
                    np.asarray(got, np.float64)[r], want, rtol=tol, atol=tol)
        # the same float sum over the same ranks, then the same division
        np.testing.assert_array_equal(np.asarray(leaf[k], np.float64),
                                      np.asarray(auto[k], np.float64))
        np.testing.assert_allclose(np.asarray(leaf[k], np.float64),
                                   np.asarray(bucket[k], np.float64),
                                   rtol=tol / 10, atol=tol / 10)


def _exchange_ops(lowered):
    """(op name, location, operand types, enclosing op names) of every op
    of a lowering that lies under the scope ``bps.exchange``."""
    out = []

    def walk(op, parents):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    name = o.operation.name
                    loc = str(o.location)
                    if "bps.exchange" in loc:
                        out.append((name, loc,
                                    [str(x.type) for x in o.operands],
                                    parents))
                    walk(o, parents + (name,))

    walk(lowered.compiler_ir().operation, ())
    return out


_PACKING = ("stablehlo.concatenate", "stablehlo.dynamic_slice",
            "stablehlo.dynamic_update_slice")


def _tiny_trainer(mesh, **kw):
    from byteps_tpu.models import bert, transformer
    from byteps_tpu.training import DistributedTrainer
    import optax
    cfg = bert.bert_tiny()
    loss = lambda p, b: bert.mlm_loss(p, cfg, b, max_predictions=8)  # noqa: E731
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    trainer = DistributedTrainer(loss, params, optax.adamw(1e-3), mesh=mesh,
                                 **kw)
    batch = bert.synth_mlm_batch(np.random.RandomState(0), 16, 32, 128)
    return trainer, batch


def _lower(trainer, batch):
    return trainer._step_fn.lower(trainer.params, trainer.opt_state, batch)


def test_ici_step_reduces_the_leaves_as_they_are(mesh8):
    """No ravel, slice, concatenate or update-slice of a gradient under
    ``bps.exchange``, and every all-reduce operand has a leaf's shape."""
    trainer, batch = _tiny_trainer(mesh8, partition_bytes=1 << 14)
    ops = _exchange_ops(_lower(trainer, batch))
    names = {name for name, _, _, _ in ops}
    assert "stablehlo.all_reduce" in names
    assert not names & set(_PACKING), names
    assert "stablehlo.reshape" not in names
    leaves = jax.tree_util.tree_leaves(trainer.params)
    want = sorted("tensor<" + "x".join(map(str, l.shape + ("f32",))) + ">"
                  for l in leaves)
    got = sorted(t for name, _, types, _ in ops
                 if name == "stablehlo.all_reduce" for t in types)
    assert got == want
    assert all("bps.exchange.reduce" in loc for name, loc, _, _ in ops
               if name == "stablehlo.all_reduce")


def _flat_psum(x, axes):
    return jax.lax.psum(x, axes)


@pytest.mark.parametrize("case", ["custom_reducer", "dcn_mesh",
                                  "compression"])
def test_buckets_stay_where_a_reducer_needs_a_flat_buffer(case):
    from byteps_tpu.parallel.collectives import exchange_form, psum_reducer
    if case == "custom_reducer":
        mesh, kw = make_mesh({"data": 8}), {"reducer": _flat_psum}
        form = exchange_form(("data",), _flat_psum)
    elif case == "dcn_mesh":
        mesh, kw = make_mesh({"dcn": 2, "data": 4}), {}
        form = exchange_form(("dcn", "data"), psum_reducer)
    else:
        mesh = make_mesh({"data": 8})
        kw = {"compression": {"compressor_type": "onebit"},
              "min_compress_bytes": 0}
        form = exchange_form(("data",), psum_reducer, kw["compression"])
    assert form[0] == "buckets"
    trainer, batch = _tiny_trainer(mesh, partition_bytes=1 << 14, **kw)
    ops = _exchange_ops(_lower(trainer, batch))
    names = {name for name, _, _, _ in ops}
    # the flat buffers: packed by concatenation, written back by slices
    assert {"stablehlo.concatenate", "stablehlo.dynamic_update_slice"} <= names
    # and every collective's operand is a flat buffer, no leaf's shape
    collectives = [types[0] for name, _, types, _ in ops
                   if name in ("stablehlo.all_reduce",
                               "stablehlo.reduce_scatter",
                               "stablehlo.all_gather")]
    assert collectives
    if case != "compression":     # whose payloads are packed words
        assert all(t.count("x") <= 1 for t in collectives), collectives
    if case == "dcn_mesh":
        assert {"stablehlo.reduce_scatter", "stablehlo.all_gather"} <= names


def test_exchange_form_reads_the_reducer_and_the_axes_alone():
    from byteps_tpu.parallel.collectives import exchange_form, psum_reducer
    assert exchange_form(("data",))[0] == "leaves"
    assert exchange_form(("data", "model"), psum_reducer)[0] == "leaves"
    assert exchange_form(())[0] == "leaves"
    assert exchange_form(("dcn", "data"))[0] == "buckets"
    assert exchange_form(("data",), _flat_psum)[0] == "buckets"
    assert exchange_form((), _flat_psum)[0] == "buckets"
    assert exchange_form(("data",), psum_reducer,
                         {"compressor_type": "onebit"})[0] == "buckets"
    for axes in (("data",), ("dcn", "data")):
        assert exchange_form(axes)[1]       # a reason, for the log


def test_partition_bytes_changes_nothing_on_the_leaf_path(mesh8):
    texts = []
    for pb in (1 << 10, 4 << 20, None):
        kw = {} if pb is None else {"partition_bytes": pb}
        trainer, batch = _tiny_trainer(mesh8, **kw)
        texts.append(_lower(trainer, batch).as_text())
    assert texts[0] == texts[1] == texts[2]


def test_trainer_logs_its_exchange_form_once(mesh8, caplog):
    import logging
    from byteps_tpu.common.logging import get_logger
    logger = get_logger()
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            _tiny_trainer(mesh8)
            _tiny_trainer(mesh8, reducer=_flat_psum)
    finally:
        logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("BPS exchange:")]
    assert len(lines) == 2
    assert "form=leaves" in lines[0] and "ICI" in lines[0]
    assert "form=buckets" in lines[1] and "custom reducer" in lines[1]


def test_accumulation_communicates_every_second_step_only(mesh8):
    """``backward_passes_per_step=2``: the all-reduces lie under the
    every-k branch, and a step that only accumulates moves no weight."""
    trainer, batch = _tiny_trainer(mesh8, backward_passes_per_step=2,
                                   donate=False)
    ops = _exchange_ops(_lower(trainer, batch))
    reduces = [parents for name, _, _, parents in ops
               if name == "stablehlo.all_reduce"]
    assert reduces
    assert all("stablehlo.case" in parents or "stablehlo.if" in parents
               for parents in reduces)
    assert not {name for name, _, _, _ in ops} & set(_PACKING)
    before = jax.tree_util.tree_map(np.asarray, trainer.params)
    trainer.step(batch)
    mid = jax.tree_util.tree_map(np.asarray, trainer.params)
    trainer.step(batch)
    after = jax.tree_util.tree_map(np.asarray, trainer.params)
    moved = lambda a, b: max(  # noqa: E731
        float(np.abs(x - y).max()) for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert moved(before, mid) == 0.0
    assert moved(mid, after) > 0.0

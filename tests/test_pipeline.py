"""Pipeline parallelism: primitive equivalence + end-to-end training.

Additive scope vs the reference (SURVEY §2.5: PP absent there). The gold
standard is exactness: a pp=N run must compute the same loss trajectory
as the unpipelined model."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from byteps_tpu.models import bert, gpt2, transformer
from byteps_tpu.parallel.mesh import make_mesh
from byteps_tpu.parallel.pipeline import last_stage_value, pipeline
from byteps_tpu.training import DistributedTrainer, ShardedTrainer


def test_pipeline_primitive_matches_sequential():
    """8 residual-linear layers over pipe=4 == sequential application."""
    n_layers, pipe, n_micro, mb, dim = 8, 4, 4, 2, 16
    rng = np.random.RandomState(0)
    ws = rng.randn(n_layers, dim, dim).astype(np.float32) * 0.1
    x = rng.randn(n_micro, mb, dim).astype(np.float32)

    def stage_fn(stage_ws, h):
        def body(carry, w):
            return carry + jnp.tanh(carry @ w), None
        out, _ = jax.lax.scan(body, h, stage_ws)
        return out

    want = np.asarray(stage_fn(jnp.asarray(ws), jnp.asarray(x.reshape(-1, dim))))
    want = want.reshape(n_micro, mb, dim)

    mesh = make_mesh({"pipe": pipe}, devices=jax.devices()[:pipe])

    def run(ws, x):
        out = pipeline(stage_fn, ws, x, "pipe")
        # replicate last stage's outputs so out_specs can be P()
        return last_stage_value(out, "pipe")

    fn = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P("pipe"), P()),
                               out_specs=P(), check_vma=False))
    got = np.asarray(fn(
        jax.device_put(ws, NamedSharding(mesh, P("pipe"))), jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_pipeline_loss_matches_unpipelined():
    """bert_tiny forward loss under pp=2 equals the plain model's loss."""
    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    cfg_pp = bert.bert_tiny(pp_axis="pipe")
    cfg_ref = bert.bert_tiny()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg_ref)
    batch = bert.synth_mlm_batch(np.random.RandomState(1), 4, 32,
                                 cfg_ref.vocab_size)
    want = float(bert.mlm_loss(params, cfg_ref,
                               tuple(jnp.asarray(b) for b in batch)))

    specs = transformer.param_specs(cfg_pp)

    def loss(p, b):
        return bert.mlm_loss(p, cfg_pp, b)

    fn = jax.jit(jax.shard_map(loss, mesh=mesh, in_specs=(specs, P()),
                               out_specs=P(), check_vma=False))
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: not isinstance(x, (dict, list)))
    got = float(fn(sharded, tuple(jnp.asarray(b) for b in batch)))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_pipeline_training_matches_data_parallel():
    """3 training steps under {pipe:2, data:2} track the pure-DP loss
    trajectory — pipelining must not change the math."""
    cfg_pp = bert.bert_tiny(pp_axis="pipe", pp_microbatches=4)
    cfg_ref = bert.bert_tiny()
    params = transformer.init_params(jax.random.PRNGKey(2), cfg_ref)
    rng = np.random.RandomState(3)
    batches = [bert.synth_mlm_batch(rng, 16, 32, cfg_ref.vocab_size)
               for _ in range(3)]

    # same dp degree (2) in both runs: lm_loss is a per-shard masked mean,
    # so a different batch decomposition would shift the mean-of-means
    # weighting and mask a real pipeline bug behind tolerance slack
    mesh_dp = make_mesh({"data": 2}, devices=jax.devices()[:2])
    ref_tr = DistributedTrainer(lambda p, b: bert.mlm_loss(p, cfg_ref, b),
                                params, optax.adam(1e-3), mesh=mesh_dp)
    want = [float(ref_tr.step(b)) for b in batches]

    mesh_pp = make_mesh({"pipe": 2, "data": 2}, devices=jax.devices()[:4])
    tr = ShardedTrainer(lambda p, b: bert.mlm_loss(p, cfg_pp, b),
                        params, transformer.param_specs(cfg_pp),
                        optax.adam(1e-3), mesh=mesh_pp)
    got = [float(tr.step(b)) for b in batches]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_pipeline_with_tensor_parallel_trains():
    """pp × tp compose: {pipe:2, model:2, data:2} training decreases loss."""
    cfg = gpt2.gpt2_tiny(pp_axis="pipe", tp_axis="model", pp_microbatches=2)
    mesh = make_mesh({"pipe": 2, "model": 2, "data": 2})
    params = transformer.init_params(jax.random.PRNGKey(4), cfg)
    tr = ShardedTrainer(lambda p, b: gpt2.causal_lm_loss(p, cfg, b),
                        params, transformer.param_specs(cfg),
                        optax.adam(3e-3), mesh=mesh)
    fixed = gpt2.synth_lm_batch(np.random.RandomState(5), 8, 33,
                                cfg.vocab_size)
    losses = [float(tr.step(fixed)) for _ in range(20)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8


def test_interleaved_primitive_matches_sequential():
    """Interleaved schedule (V=2 chunks/rank over pipe=2) == sequential;
    chunk c of rank r runs semantic layers (c*n + r)*Lc.. per the
    interleave_permutation layout."""
    from byteps_tpu.parallel.pipeline import (interleave_permutation,
                                              pipeline_interleaved)

    n_layers, pipe, V, n_micro, mb, dim = 8, 2, 2, 4, 2, 16
    rng = np.random.RandomState(0)
    ws = rng.randn(n_layers, dim, dim).astype(np.float32) * 0.1
    x = rng.randn(n_micro, mb, dim).astype(np.float32)

    def stage_fn(stage_ws, h):
        def body(carry, w):
            return carry + jnp.tanh(carry @ w), None
        out, _ = jax.lax.scan(body, h, stage_ws)
        return out

    want = np.asarray(stage_fn(jnp.asarray(ws),
                               jnp.asarray(x.reshape(-1, dim))))
    want = want.reshape(n_micro, mb, dim)

    perm = interleave_permutation(n_layers, pipe, V)
    mesh = make_mesh({"pipe": pipe}, devices=jax.devices()[:pipe])

    def run(ws_r, x):
        Lr = ws_r.shape[0]
        chunks = ws_r.reshape(V, Lr // V, dim, dim)
        out = pipeline_interleaved(stage_fn, chunks, x, "pipe")
        return last_stage_value(out, "pipe")

    fn = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P("pipe"), P()),
                               out_specs=P(), check_vma=False))
    got = np.asarray(fn(
        jax.device_put(ws[perm], NamedSharding(mesh, P("pipe"))),
        jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_interleaved_grads_match_gpipe():
    """V=2 interleaved gradients == GPipe gradients == sequential
    gradients (after undoing the layout permutation)."""
    from byteps_tpu.parallel.pipeline import (interleave_permutation,
                                              pipeline, pipeline_interleaved)

    n_layers, pipe, V, n_micro, mb, dim = 8, 2, 2, 4, 2, 8
    rng = np.random.RandomState(1)
    ws = rng.randn(n_layers, dim, dim).astype(np.float32) * 0.1
    x = rng.randn(n_micro, mb, dim).astype(np.float32)
    tgt = rng.randn(n_micro, mb, dim).astype(np.float32)

    def stage_fn(stage_ws, h):
        def body(carry, w):
            return carry + jnp.tanh(carry @ w), None
        out, _ = jax.lax.scan(body, h, stage_ws)
        return out

    def seq_loss(ws):
        out = stage_fn(ws, jnp.asarray(x.reshape(-1, dim)))
        return ((out - tgt.reshape(-1, dim)) ** 2).mean()

    g_seq = np.asarray(jax.grad(seq_loss)(jnp.asarray(ws)))

    mesh = make_mesh({"pipe": pipe}, devices=jax.devices()[:pipe])

    # / pipe: every rank computes the replicated loss, so the psum in
    # last_stage_value multiplies gradients by the stage count (the
    # trainers' uniform-rescale convention; see lm_loss's pp note)
    def pp_loss(ws_r, x):
        out = pipeline(stage_fn, ws_r, x, "pipe")
        out = last_stage_value(out, "pipe")
        return ((out - tgt) ** 2).mean() / pipe

    def il_loss(ws_r, x):
        chunks = ws_r.reshape(V, ws_r.shape[0] // V, dim, dim)
        out = pipeline_interleaved(stage_fn, chunks, x, "pipe")
        out = last_stage_value(out, "pipe")
        return ((out - tgt) ** 2).mean() / pipe

    def grad_of(loss_fn, ws_in):
        fn = jax.jit(jax.shard_map(
            jax.grad(loss_fn), mesh=mesh, in_specs=(P("pipe"), P()),
            out_specs=P("pipe"), check_vma=False))
        return np.asarray(fn(
            jax.device_put(ws_in, NamedSharding(mesh, P("pipe"))),
            jnp.asarray(x)))

    g_pp = grad_of(pp_loss, ws)
    np.testing.assert_allclose(g_pp, g_seq, rtol=1e-4, atol=1e-6)

    perm = interleave_permutation(n_layers, pipe, V)
    g_il_perm = grad_of(il_loss, ws[perm])
    g_il = g_il_perm[np.argsort(perm)]       # back to semantic order
    np.testing.assert_allclose(g_il, g_seq, rtol=1e-4, atol=1e-6)


def test_bubble_fraction():
    from byteps_tpu.parallel.pipeline import bubble_fraction
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 4) == 3 / 7
    assert bubble_fraction(4, 4, interleave=2) == 3 / 11
    assert bubble_fraction(4, 16, interleave=4) < bubble_fraction(4, 16)


def test_interleaved_transformer_loss_matches_unpipelined():
    """bert (4-layer) loss under pp=2 x V=2 interleave == plain model."""
    import dataclasses
    from byteps_tpu.parallel.pipeline import interleave_permutation

    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    cfg_ref = dataclasses.replace(bert.bert_tiny(), layers=4)
    cfg_pp = dataclasses.replace(
        bert.bert_tiny(pp_axis="pipe", pp_microbatches=2),
        layers=4, pp_interleave=2)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg_ref)
    batch = bert.synth_mlm_batch(np.random.RandomState(1), 4, 32,
                                 cfg_ref.vocab_size)
    want = float(bert.mlm_loss(params, cfg_ref,
                               tuple(jnp.asarray(b) for b in batch)))

    perm = np.array(interleave_permutation(4, 2, 2))
    params_il = dict(params)
    params_il["blocks"] = jax.tree_util.tree_map(lambda p: p[perm],
                                                 params["blocks"])
    specs = transformer.param_specs(cfg_pp)
    fn = jax.jit(jax.shard_map(
        lambda p, b: bert.mlm_loss(p, cfg_pp, b), mesh=mesh,
        in_specs=(specs, P()), out_specs=P(), check_vma=False))
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params_il, specs,
        is_leaf=lambda x: not isinstance(x, (dict, list)))
    got = float(fn(sharded, tuple(jnp.asarray(b) for b in batch)))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_interleaved_ragged_microbatches():
    """n_micro NOT divisible by n_stages: ghost-padded internally,
    outputs and GRADIENTS exact vs sequential (r3: lifted the
    n_micro % n_stages == 0 restriction)."""
    from byteps_tpu.parallel.pipeline import (interleave_permutation,
                                              pipeline_interleaved)

    n_layers, pipe, V, n_micro, mb, dim = 8, 2, 2, 5, 2, 16
    rng = np.random.RandomState(3)
    ws = rng.randn(n_layers, dim, dim).astype(np.float32) * 0.1
    x = rng.randn(n_micro, mb, dim).astype(np.float32)

    def stage_fn(stage_ws, h):
        def body(carry, w):
            return carry + jnp.tanh(carry @ w), None
        out, _ = jax.lax.scan(body, h, stage_ws)
        return out

    def ref_loss(ws, x):
        out = stage_fn(ws, x.reshape(-1, dim))
        return (out ** 2).mean()

    want = float(ref_loss(jnp.asarray(ws), jnp.asarray(x)))
    want_grad = np.asarray(
        jax.grad(ref_loss)(jnp.asarray(ws), jnp.asarray(x)))

    perm = interleave_permutation(n_layers, pipe, V)
    inv = np.argsort(perm)
    mesh = make_mesh({"pipe": pipe}, devices=jax.devices()[:pipe])

    def pp_loss(ws_r, x):
        Lr = ws_r.shape[0]
        chunks = ws_r.reshape(V, Lr // V, dim, dim)
        out = pipeline_interleaved(stage_fn, chunks, x, "pipe")
        out = last_stage_value(out, "pipe")
        # / pipe: psum-replicated loss convention (see
        # test_interleaved_grads_match_gpipe)
        return (out ** 2).mean() / pipe

    def run(ws_r, x):
        loss, g = jax.value_and_grad(pp_loss)(ws_r, x)
        return loss, g

    fn = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P("pipe"), P()),
                               out_specs=(P(), P("pipe")),
                               check_vma=False))
    loss, grads = fn(
        jax.device_put(ws[perm], NamedSharding(mesh, P("pipe"))),
        jnp.asarray(x))
    np.testing.assert_allclose(float(loss) * pipe, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads)[inv], want_grad,
                               rtol=1e-4, atol=1e-5)


# ===================================================================
# MPMD pipeline over the PS fabric (byteps_tpu.pipeline): the stage
# partitioner's bitwise probe, the 2-stage in-process parity contract,
# the 1F1B schedule, and the two-class wire scheduler.
# ===================================================================

import threading
import time

import pytest

from byteps_tpu.models.mlp import mlp_init, mlp_loss
from byteps_tpu.pipeline import (ActivationExchange, LocalActPeer,
                                 PipelineStageDriver, StagePartitioner,
                                 one_f_one_b, sequential_schedule,
                                 split_microbatches)
from byteps_tpu.pipeline.exchange import ActStore, PeerDead, act_key
from byteps_tpu.server import admission as wire_sched


def _mlp_case(dim=32, depth=4, batch=8, micro=2, seed=0):
    rng = np.random.RandomState(seed)
    params = mlp_init(jax.random.PRNGKey(seed), dim, depth)
    xs = rng.randn(batch, dim).astype(np.float32)
    full = (jnp.asarray(xs), jnp.asarray(np.tanh(xs)))
    mb = jax.tree_util.tree_map(lambda l: l[:batch // micro], full)
    return params, full, mb


def test_stage_partitioner_bitwise_probe():
    """The 2-stage program must reproduce the fused value_and_grad
    BIT-FOR-BIT on the probe (the staged_grad contract, across
    workers), own disjoint covering param groups, and expose nonempty
    wire boundaries in both directions."""
    params, full, mb = _mlp_case()
    prog = StagePartitioner(2).build(mlp_loss, params, mb, name="probe")
    assert prog is not None
    n = len(jax.tree_util.tree_leaves(params))
    owned = sorted(li for g in prog.stage_param_leaves for li in g)
    assert owned == list(range(n))          # disjoint cover
    wire = [b for b in prog.boundaries if not b.local]
    assert {b.kind for b in wire} == {"act", "act_grad"}
    assert all(b.nbytes > 0 for b in wire)
    loss, grads = prog.run_local(params, mb)
    fl, fg = jax.jit(jax.value_and_grad(mlp_loss))(params, mb)
    assert np.array_equal(np.asarray(loss), np.asarray(fl))
    for a, b in zip(grads, jax.tree_util.tree_leaves(fg)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_stage_partitioner_refuses_impossible_splits():
    """Probe-or-drop: more stages than usable param groups returns
    None (loudly counted), never a wrong program."""
    params, full, mb = _mlp_case(depth=2)
    assert StagePartitioner(9).build(mlp_loss, params, mb,
                                     name="toodeep") is None


def test_one_f_one_b_schedule_invariants():
    for P in (2, 3, 4):
        for M in (1, 2, 4, 7):
            for s in range(P):
                sched = one_f_one_b(P, s, M)
                fs = [m for op, m in sched if op == "F"]
                bs = [m for op, m in sched if op == "B"]
                assert fs == list(range(M))
                assert bs == list(range(M))     # bwd in mb order:
                #                     grad-accumulation determinism
                # warmup depth: stage s runs P-1-s forwards before its
                # first backward
                first_b = next(i for i, (op, _) in enumerate(sched)
                               if op == "B")
                assert first_b == min(P - s, M)
    # sequential arm: strict F(m), B(m) interleave
    assert sequential_schedule(2, 0, 2) == [("F", 0), ("B", 0),
                                            ("F", 1), ("B", 1)]


def _parity_reference(prog, params, full, micro, tx, steps):
    """Single-process fused reference with IDENTICAL microbatch
    accumulation and per-stage apply order."""
    import optax
    fused = jax.jit(jax.value_and_grad(mlp_loss))
    treedef = jax.tree_util.tree_structure(params)
    leaves = [jnp.array(np.asarray(l))
              for l in jax.tree_util.tree_leaves(params)]
    own = prog.stage_param_leaves
    states = [tx.init([leaves[li] for li in g]) for g in own]

    @jax.jit
    def apply(p, st, gr):
        up, st = tx.update(gr, st, p)
        return optax.apply_updates(p, up), st

    losses = []
    for _ in range(steps):
        p = jax.tree_util.tree_unflatten(treedef, leaves)
        acc = ls = None
        for mb in split_microbatches(full, micro):
            l, g = fused(p, mb)
            ls = l if ls is None else ls + l
            gl = jax.tree_util.tree_leaves(g)
            acc = gl if acc is None else [a + b for a, b in zip(acc, gl)]
        gl = [a / micro for a in acc]
        for s, grp in enumerate(own):
            ps, states[s] = apply([leaves[li] for li in grp], states[s],
                                  [gl[li] for li in grp])
            for li, v in zip(grp, ps):
                leaves[li] = v
        losses.append(np.asarray(ls / micro))
    return losses, leaves


def _run_stages(drivers, batch, steps, join_s=90):
    results, errs = {}, {}

    def loop(s):
        try:
            results[s] = [l for l in (drivers[s].step(batch)
                                      for _ in range(steps))
                          if l is not None]
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs[s] = e

    ts = [threading.Thread(target=loop, args=(s,))
          for s in range(len(drivers))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(join_s)
    if errs:
        raise next(iter(errs.values()))
    assert all(not t.is_alive() for t in ts), "pipeline hung"
    return results


def test_pipeline_2stage_2micro_matches_fused_bitwise():
    """ACCEPTANCE: a 2-stage x 2-microbatch pipeline run of the mlp
    matches the single-process fused run (same deterministic
    microbatch accumulation) BITWISE — losses and every stage's params
    over several optimizer steps."""
    import optax
    params, full, mb = _mlp_case()
    prog = StagePartitioner(2).build(mlp_loss, params, mb, name="parity")
    assert prog is not None
    stores = [ActStore(), ActStore()]
    acts = [ActivationExchange(0, stores[0],
                               peer_next=LocalActPeer(stores[1]),
                               timeout_ms=15000),
            ActivationExchange(1, stores[1],
                               peer_prev=LocalActPeer(stores[0]),
                               timeout_ms=15000)]
    tx = optax.adam(1e-2)
    drv = [PipelineStageDriver(prog, s, params, tx, acts[s], 2)
           for s in (0, 1)]
    steps = 4
    results = _run_stages(drv, full, steps)
    want_losses, want_leaves = _parity_reference(prog, params, full, 2,
                                                 tx, steps)
    got = [np.asarray(l) for l in results[1]]
    assert len(got) == steps
    for a, b in zip(got, want_losses):
        assert np.array_equal(a, b)
    for s in (0, 1):
        for li, val in drv[s].stage_params_tree().items():
            assert np.array_equal(val, np.asarray(want_leaves[li]))
    # full-batch fused loss within the grad-exactness tolerance
    fl, _ = jax.jit(jax.value_and_grad(mlp_loss))(params, full)
    np.testing.assert_allclose(got[0], np.asarray(fl), rtol=2e-3,
                               atol=2e-5)


def test_pipeline_over_tcp_transport_matches_local():
    """The same 2-stage run with activations crossing REAL sockets
    (each stage's mailbox behind its own PSTransportServer) is bitwise
    identical to the in-process run — the wire hop adds no numerics."""
    import optax

    from byteps_tpu.server.engine import PSServer
    from byteps_tpu.server.transport import (PSTransportServer,
                                             RemotePSBackend)
    params, full, mb = _mlp_case()
    prog = StagePartitioner(2).build(mlp_loss, params, mb, name="tcp")
    assert prog is not None
    tx = optax.adam(1e-2)
    engines = [PSServer(num_workers=1, engine_threads=1)
               for _ in range(2)]
    servers = [PSTransportServer(e, host="127.0.0.1", port=0)
               for e in engines]
    clients = [RemotePSBackend([f"127.0.0.1:{servers[1].port}"]),
               RemotePSBackend([f"127.0.0.1:{servers[0].port}"])]
    try:
        acts = [ActivationExchange(0, servers[0].act_store(),
                                   peer_next=clients[0],
                                   timeout_ms=15000),
                ActivationExchange(1, servers[1].act_store(),
                                   peer_prev=clients[1],
                                   timeout_ms=15000)]
        drv = [PipelineStageDriver(prog, s, params, tx, acts[s], 2)
               for s in (0, 1)]
        results = _run_stages(drv, full, 2)
        want, _ = _parity_reference(prog, params, full, 2, tx, 2)
        for a, b in zip(results[1], want):
            assert np.array_equal(np.asarray(a), b)
    finally:
        for c in clients:
            c.close()
        for s in servers:
            s.close()
        for e in engines:
            e.close()


@pytest.mark.slow
def test_pp_dp_composition_2stages_2replicas():
    """PP x DP: 2 stages x 2 data-parallel replicas — each replica
    pair shares a stage's PS keys through the UNCHANGED PS exchange
    (per-stage declaration names), and the composed run tracks the
    single-process full-batch trajectory within the grad-exactness
    tolerance."""
    import optax

    from byteps_tpu.common.naming import NameRegistry
    from byteps_tpu.server.engine import HostPSBackend
    from byteps_tpu.server.ps_mode import PSGradientExchange

    dim, depth, B, M, steps = 32, 4, 16, 2, 3
    rng = np.random.RandomState(0)
    params = mlp_init(jax.random.PRNGKey(0), dim, depth)
    xs = rng.randn(B, dim).astype(np.float32)
    full = (jnp.asarray(xs), jnp.asarray(np.tanh(xs)))
    halves = [jax.tree_util.tree_map(lambda l, r=r: l[r * (B // 2):
                                                     (r + 1) * (B // 2)],
                                     full) for r in range(2)]
    mb = jax.tree_util.tree_map(lambda l: l[:B // 2 // M], full)
    prog = StagePartitioner(2).build(mlp_loss, params, mb, name="ppdp")
    assert prog is not None
    backend = HostPSBackend(num_servers=1, num_workers=2,
                            engine_threads=2)
    tx = optax.adam(1e-2)
    try:
        drivers = []
        stores = {}
        for r in range(2):
            stores[(r, 0)], stores[(r, 1)] = ActStore(), ActStore()
        for r in range(2):
            acts = [ActivationExchange(
                        0, stores[(r, 0)],
                        peer_next=LocalActPeer(stores[(r, 1)]),
                        timeout_ms=20000),
                    ActivationExchange(
                        1, stores[(r, 1)],
                        peer_prev=LocalActPeer(stores[(r, 0)]),
                        timeout_ms=20000)]
            for s in (0, 1):
                ex = PSGradientExchange(backend,
                                        registry=NameRegistry())
                drivers.append(PipelineStageDriver(
                    prog, s, params, tx, acts[s], M, exchange=ex,
                    world=2, name="ppdp"))
        results, errs = {}, {}

        def loop(i, r):
            try:
                results[i] = [l for l in
                              (drivers[i].step(halves[r])
                               for _ in range(steps))
                              if l is not None]
            except BaseException as e:  # noqa: BLE001
                errs[i] = e

        ts = [threading.Thread(target=loop, args=(i, i // 2))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        assert not errs, errs
        assert all(not t.is_alive() for t in ts), "PPxDP hung"

        # single-process full-batch reference (plain fused step)
        fused = jax.jit(jax.value_and_grad(mlp_loss))
        import optax as _ox
        p = jax.tree_util.tree_map(lambda x: jnp.array(np.asarray(x)),
                                   params)
        st = tx.init(p)

        @jax.jit
        def apply(p, st, g):
            up, st = tx.update(g, st, p)
            return _ox.apply_updates(p, up), st

        ref = []
        for _ in range(steps):
            l, g = fused(p, full)
            p, st = apply(p, st, g)
            ref.append(float(l))
        # replica 0 and 1 last-stage losses are per-half; their mean is
        # the full-batch loss (equal halves)
        got = [(float(a) + float(b)) / 2
               for a, b in zip(results[1], results[3])]
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-5)
    finally:
        backend.close()


# ------------------------------------------------- wire scheduler units

def test_send_scheduler_priority_desc_key_asc_and_credit_cap():
    """BytePS scheduled_queue semantics: entries drain (priority desc,
    key asc, fifo); byte credit caps in-flight bytes; one frame always
    admits even above the whole credit (no giant-bucket deadlock)."""
    s = wire_sched.SendScheduler(credit_bytes=1 << 20)
    # a frame larger than the whole credit admits alone
    big = s.acquire(wire_sched.CLASS_GRAD, 1, 10, 2 << 20)
    assert big is not None and s.inflight() == 2 << 20
    order = []

    def worker(tag, klass, prio, key, nb):
        t = s.acquire(klass, prio, key, nb)
        order.append(tag)
        # while we hold it, in-flight must stay within the credit
        assert s.inflight() <= 1 << 20
        time.sleep(0.01)
        s.release(t)

    ths = [threading.Thread(target=worker,
                            args=("g_k3", wire_sched.CLASS_GRAD, 5, 3,
                                  100_000)),
           threading.Thread(target=worker,
                            args=("g_k2", wire_sched.CLASS_GRAD, 5, 2,
                                  100_000)),
           threading.Thread(target=worker,
                            args=("act", wire_sched.CLASS_ACT, 0, 99,
                                  50_000))]
    for t in ths:
        t.start()
        time.sleep(0.05)       # deterministic enqueue order
    assert s.queued() == 3     # credit exhausted: everyone queues
    s.release(big)
    for t in ths:
        t.join()
    # act outranks both grads; equal-priority grads drain key-asc
    assert order == ["act", "g_k2", "g_k3"]
    assert any(e["class"] == "act" and e["overtook"] for e in s.trace())
    # tiny frames bypass the gate entirely
    assert s.acquire(wire_sched.CLASS_GRAD, 0, 1, 16) is None


def test_act_frame_overtakes_grad_burst_under_throttle():
    """SATELLITE: on a throttle.Nic-constrained link with the byte
    credit engaged, a CLASS_ACT frame enqueued AFTER a large CLASS_GRAD
    burst is admitted (and delivered) before the queued grads — trace
    asserted, end to end through the real transport."""
    from byteps_tpu.server.engine import PSServer
    from byteps_tpu.server.throttle import Nic
    from byteps_tpu.server.transport import (PSTransportServer,
                                             RemotePSBackend)
    wire_sched.configure_send(512 << 10)
    eng = PSServer(num_workers=1, engine_threads=2)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    cli = RemotePSBackend([f"127.0.0.1:{srv.port}"], nic=Nic(8e6))
    try:
        nb = 4 << 20
        for k in (1, 2, 3):
            cli.init_key(k, nb)
        blob = np.ones(nb // 4, np.float32)
        done = []

        def grad(k):
            cli.push(k, blob)
            done.append(("grad", time.monotonic()))

        gts = [threading.Thread(target=grad, args=(k,))
               for k in (1, 2, 3)]
        for t in gts:
            t.start()
        time.sleep(0.3)            # the burst holds the credit first
        cli.act_push(act_key(7), 1, np.ones(64 << 10, np.uint8))
        done.append(("act", time.monotonic()))
        for t in gts:
            t.join()
        # the act frame beat at least one earlier-enqueued grad both in
        # admission (trace) and in delivery (wall order)
        tr = wire_sched.send_scheduler().trace()
        acts = [e for e in tr if e["class"] == "act"]
        assert acts and acts[0]["overtook"]
        finish = [tag for tag, _ in sorted(done, key=lambda d: d[1])]
        assert finish.index("act") < len(finish) - 1
        # the mailbox really got the frame
        assert srv.act_store().take(act_key(7), 1, timeout_ms=2000)
    finally:
        wire_sched.configure_send(0)
        cli.close()
        srv.close()
        eng.close()


def test_exchange_assigns_reverse_first_use_send_priorities():
    """Grads-only jobs get the scheduler too: the PS exchange assigns
    reverse-FIRST-USE priorities at plan time (input-side buckets
    highest), composing with the cross-step pull heap's order."""
    from byteps_tpu.server.engine import HostPSBackend
    from byteps_tpu.server.ps_mode import PSGradientExchange

    class SpyBackend(HostPSBackend):
        def __init__(self):
            super().__init__(num_servers=1, num_workers=1,
                             engine_threads=1)
            self.prios = {}

        def set_send_priority(self, key, prio):
            self.prios[key] = prio

    be = SpyBackend()
    try:
        ex = PSGradientExchange(be, partition_bytes=1 << 10)
        tree = {f"w{i}": np.ones(512, np.float32) for i in range(4)}
        ex.exchange(tree, name="prio")
        assert be.prios
        # bucket priority strictly tracks reverse first-use: the bucket
        # holding leaf 0 outranks the bucket holding the last leaf
        _, _, keyed = ex._plan(tree, "prio")
        by_first = sorted(
            keyed, key=lambda kb: min(s.leaf_index
                                      for s in kb[1].segments))
        prios = [be.prios[k] for k, _ in by_first]
        assert prios == sorted(prios, reverse=True)
    finally:
        be.close()


def test_act_store_retention_and_idempotent_put():
    st = ActStore(retain=4)
    st.put(5, 1, b"a")
    st.put(5, 1, b"a")                     # resend: last-wins, no error
    assert st.take(5, 1, timeout_ms=100) == b"a"
    for seq in range(2, 12):
        st.put(5, seq, bytes([seq]))
        st.take(5, seq, timeout_ms=100)
    # pruned behind the retention window, recent seqs still retryable
    assert st.take(5, 11, timeout_ms=100) == bytes([11])
    with pytest.raises(TimeoutError):
        st.take(5, 2, timeout_ms=50)


def test_split_microbatches_refuses_ragged():
    with pytest.raises(ValueError):
        split_microbatches((np.zeros((7, 3)),), 2)


# ===================================================================
# Interleaved (virtual-stage) 1F1B for the MPMD driver (ISSUE 15):
# schedule invariants, the topology helpers the launcher derives its
# wiring from, and the P=2 x V=2 in-process parity contract.
# ===================================================================

from byteps_tpu.pipeline import interleaved_one_f_one_b
from byteps_tpu.pipeline import topology as ppt


def test_interleaved_schedule_invariants():
    """Every (microbatch, chunk) pair runs F and B exactly once; per
    chunk the backwards run in microbatch order (the grad-accumulation
    determinism the parity contracts rely on); V=1 degenerates to the
    plain 1F1B schedule; the warmup is 2*(P-1-stage) + (V-1)*P deep."""
    for P in (2, 4):
        for V in (2, 3):
            M = 2 * P
            for s in range(P):
                sched = interleaved_one_f_one_b(P, s, M, V)
                fs = [(m, c) for op, m, c in sched if op == "F"]
                bs = [(m, c) for op, m, c in sched if op == "B"]
                want = {(m, c) for m in range(M) for c in range(V)}
                assert set(fs) == want and len(fs) == M * V
                assert set(bs) == want and len(bs) == M * V
                for c in range(V):
                    assert [m for m, cc in bs if cc == c] \
                        == list(range(M))
                # forwards before the first backward == warmup depth
                # (+1 for the steady-state F that precedes each B),
                # capped by the total op count
                first_b = next(i for i, (op, _, _) in enumerate(sched)
                               if op == "B")
                assert first_b == min(2 * (P - 1 - s) + (V - 1) * P + 1,
                                      M * V)
    # V=1 == the plain schedule with a zero chunk index
    for s in range(2):
        assert interleaved_one_f_one_b(2, s, 4, 1) \
            == [(op, m, 0) for op, m in one_f_one_b(2, s, 4)]
    # the layout walks microbatches in groups of P: M % P refused
    with pytest.raises(ValueError, match="divisible"):
        interleaved_one_f_one_b(4, 0, 6, 2)


def test_topology_helpers():
    """virtual stage v runs on phys v % P (chunk v // P); V=1 wires a
    CHAIN (ends have one peer), V>1 closes the RING (chunk boundaries
    wrap P-1 -> 0); the launcher's addr list indexes by phys stage."""
    assert [ppt.phys_stage(v, 4) for v in range(8)] \
        == [0, 1, 2, 3, 0, 1, 2, 3]
    assert [ppt.chunk_of(v, 4) for v in range(8)] \
        == [0, 0, 0, 0, 1, 1, 1, 1]
    assert ppt.virtual_stages(1, 4, 2) == [1, 5]
    assert ppt.act_peer_stages(0, 4, 1) == [1]          # chain end
    assert ppt.act_peer_stages(2, 4, 1) == [1, 3]       # chain middle
    assert ppt.act_peer_stages(0, 4, 2) == [1, 3]       # ring wraps
    assert ppt.act_peer_stages(0, 1, 2) == []           # P=1: no wire
    assert ppt.act_peer_addrs(0, ["a:1", "b:2"], 2) == {1: "b:2"}
    with pytest.raises(ValueError, match="n_micro % stages"):
        ppt.validate_topology(4, 2, 6)


def test_pipeline_interleaved_v2_matches_fused_bitwise():
    """ACCEPTANCE (ISSUE 15): the interleaved driver — 2 physical
    stages each owning 2 chunks of a 4-stage program, ring-routed
    activations — matches the fused microbatched reference BITWISE
    (losses and every leaf) over several optimizer steps, exactly like
    the plain 1F1B parity contract."""
    import optax
    params, full, mb = _mlp_case(micro=4)
    prog = StagePartitioner(4).build(mlp_loss, params, mb,
                                     name="ileave")
    assert prog is not None
    stores = [ActStore(), ActStore()]
    acts = [ActivationExchange(0, stores[0],
                               peers={1: LocalActPeer(stores[1])},
                               num_phys=2, timeout_ms=15000),
            ActivationExchange(1, stores[1],
                               peers={0: LocalActPeer(stores[0])},
                               num_phys=2, timeout_ms=15000)]
    tx = optax.adam(1e-2)
    drv = [PipelineStageDriver(prog, s, params, tx, acts[s], 4,
                               virtual=2) for s in (0, 1)]
    # each phys stage owns its round-robin chunks' leaves
    for s in (0, 1):
        want = [li for v in (s, s + 2)
                for li in prog.stage_param_leaves[v]]
        assert drv[s].own_leaves == want
    steps = 3
    results = _run_stages(drv, full, steps)
    want_losses, want_leaves = _parity_reference(prog, params, full, 4,
                                                 tx, steps)
    got = [np.asarray(l) for l in results[1]]   # loss lands on phys 1
    assert len(got) == steps
    for a, b in zip(got, want_losses):
        assert np.array_equal(a, b)
    for s in (0, 1):
        for li, val in drv[s].stage_params_tree().items():
            assert np.array_equal(val, np.asarray(want_leaves[li]))


def test_interleaved_driver_refusals():
    """A program not divisible by V, or sequential + virtual, refuses
    loudly at construction — never a silently wrong layout."""
    import optax
    params, full, mb = _mlp_case(micro=4)
    prog3 = StagePartitioner(3).build(mlp_loss, params, mb, name="odd")
    assert prog3 is not None
    act = ActivationExchange(0, ActStore(), timeout_ms=1000)
    with pytest.raises(ValueError, match="divisible"):
        PipelineStageDriver(prog3, 0, params, None, act, 4, virtual=2)
    prog4 = StagePartitioner(4).build(mlp_loss, params, mb, name="seq4")
    with pytest.raises(ValueError, match="sequential"):
        PipelineStageDriver(prog4, 0, params, optax.adam(1e-2), act, 4,
                            schedule="sequential", virtual=2)


def _transformer_pp_parity(loss_fn, params, full, micro, name):
    """Shared slow-lane harness: 2-stage x `micro`-microbatch pipeline
    vs the fused microbatched reference, under the grad-exactness
    TOLERANCE contract (stage cuts through a transformer block perturb
    XLA fusion rounding last-ulp — the same reason staged_grad drops
    cuts; the partitioner validates the tolerance contract at build)."""
    import optax
    mb = jax.tree_util.tree_map(
        lambda l: l[:l.shape[0] // micro], full)
    prog = StagePartitioner(2).build(loss_fn, params, mb, name=name,
                                     exact=False)
    assert prog is not None, f"{name} refused to partition"
    stores = [ActStore(), ActStore()]
    acts = [ActivationExchange(0, stores[0],
                               peer_next=LocalActPeer(stores[1]),
                               timeout_ms=120000),
            ActivationExchange(1, stores[1],
                               peer_prev=LocalActPeer(stores[0]),
                               timeout_ms=120000)]
    tx = optax.adam(1e-3)
    drv = [PipelineStageDriver(prog, s, params, tx, acts[s], micro)
           for s in (0, 1)]
    results = _run_stages(drv, full, 2, join_s=600)

    import optax as _ox
    fused = jax.jit(jax.value_and_grad(loss_fn))
    p = jax.tree_util.tree_map(lambda x: jnp.array(np.asarray(x)),
                               params)
    st = tx.init(p)
    losses = []
    for _ in range(2):
        acc = ls = None
        for m in split_microbatches(full, micro):
            l, g = fused(p, m)
            ls = l if ls is None else ls + l
            gl = jax.tree_util.tree_leaves(g)
            acc = gl if acc is None else [a + b for a, b in zip(acc, gl)]
        gl = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(p), [a / micro for a in acc])
        losses.append(np.asarray(ls / micro))
        # one fused optax apply (per-leaf math identical to the
        # drivers' per-stage applies)
        up, st = tx.update(gl, st, p)
        p = _ox.apply_updates(p, up)
    got = [np.asarray(l) for l in results[1]]
    np.testing.assert_allclose(got, losses, rtol=2e-3, atol=2e-5)
    ref_flat = jax.tree_util.tree_leaves(p)
    for s in (0, 1):
        for li, val in drv[s].stage_params_tree().items():
            np.testing.assert_allclose(val, np.asarray(ref_flat[li]),
                                       rtol=2e-3, atol=2e-5)


@pytest.mark.slow
def test_pipeline_bert_2stage_parity():
    cfg = bert.bert_tiny()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    full = tuple(jnp.asarray(v) for v in bert.synth_mlm_batch(
        np.random.RandomState(1), 8, 32, cfg.vocab_size))
    _transformer_pp_parity(lambda p, b: bert.mlm_loss(p, cfg, b),
                           params, full, 2, "bert-pp")


@pytest.mark.slow
def test_pipeline_gpt2_2stage_parity():
    cfg = gpt2.gpt2_tiny()
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    toks = jnp.asarray(gpt2.synth_lm_batch(np.random.RandomState(2), 8,
                                           33, cfg.vocab_size))
    _transformer_pp_parity(
        lambda p, b: gpt2.causal_lm_loss(p, cfg, b), params, toks, 2,
        "gpt2-pp")


@pytest.mark.slow
def test_bench_pp_smoke():
    """The win-condition bench runs end to end on a tiny config: the
    pipelined arm must not LOSE to sequential, and the scheduler trace
    must show the activation frame overtaking the grad burst."""
    import bench
    out = bench.pp_breakdown(iters=4, warm=1, pairs=1, depth=6,
                             batch=128)
    assert out["pp_vs_sequential"] > 1.0, out
    assert out["sched"]["act_overtook_grad_burst"], out["sched"]
    assert out["bwd0_fwd1_overlap_ms"] >= 0.0


def test_pp_env_contract(monkeypatch):
    """BPS_PP_STAGES / BPS_PP_RANK / BPS_PP_MICROBATCH drive the
    default construction — the deployment path where each stage worker
    is launched with only its env."""
    import optax
    monkeypatch.setenv("BPS_PP_STAGES", "2")
    monkeypatch.setenv("BPS_PP_RANK", "1")
    monkeypatch.setenv("BPS_PP_MICROBATCH", "2")
    params, full, mb = _mlp_case()
    prog = StagePartitioner().build(mlp_loss, params, mb, name="env")
    assert prog is not None and prog.num_stages == 2
    drv = PipelineStageDriver(prog, None, params, optax.adam(1e-2),
                              ActivationExchange(1, ActStore()))
    assert drv.stage == 1 and drv.n_micro == 2


# ---------------------------------------------- activation compression

def test_act_exchange_codec_roundtrip_and_counters(monkeypatch):
    """BPS_ACT_COMPRESS: boundary frames ride the self-describing
    codecs — wire bytes shrink, the receiver disambiguates by SIZE and
    decodes by header (no receiver-side config), ineligible (non-f32)
    boundaries ship raw, and resends stay idempotent (seed pinned to
    (channel, seq))."""
    from byteps_tpu.compress import wire as cwire
    from byteps_tpu.obs.metrics import get_registry

    class B:
        index = 3
        kind = "fwd"
        src_stage, dst_stage = 0, 1
        vars = ["a", "b"]

        def __init__(self, dtypes):
            self._d = dtypes

        def specs(self):
            return [((64, 32), self._d[0]), ((16,), self._d[1])]

    monkeypatch.setenv("BPS_ACT_COMPRESS_MIN", "0")
    reg = get_registry()
    store = ActStore()
    sender = ActivationExchange(0, ActStore(),
                                peer_next=LocalActPeer(store),
                                codec="fp8_e4m3")
    recver = ActivationExchange(1, store, codec="none")  # receiver
    #                                  needs NO codec config: size-first
    rng = np.random.RandomState(70)
    env_s = {"a": rng.randn(64, 32).astype(np.float32),
             "b": rng.randn(16).astype(np.float32)}
    b = B(("float32", "float32"))
    w0 = reg.counter("pp/act_send_bytes").value
    r0 = reg.counter("pp/act_raw_bytes").value
    sender.send(b, mb=0, seq=7, env=env_s)
    wire_bytes = reg.counter("pp/act_send_bytes").value - w0
    raw_bytes = reg.counter("pp/act_raw_bytes").value - r0
    assert raw_bytes == (64 * 32 + 16) * 4
    assert wire_bytes < raw_bytes / 3          # ~4x minus header
    env_r = {}
    recver.recv(b, mb=0, seq=7, env=env_r)
    for v in ("a", "b"):
        # fp8 SR error ≤ one grid step at the value's binade (~amax/14
        # at the top binade for e4m3)
        np.testing.assert_allclose(env_r[v], env_s[v], atol=0.35)
        assert env_r[v].shape == env_s[v].shape
    # resend = identical bytes (seed from (channel, seq)): last-wins
    # mailbox sees the same frame
    sender.send(b, mb=0, seq=7, env=env_s)
    env_r2 = {}
    recver.recv(b, mb=0, seq=7, env=env_r2)
    np.testing.assert_array_equal(env_r2["a"], env_r["a"])
    # non-f32 boundary ships RAW even with the codec configured
    bi = B(("int32", "int32"))
    env_i = {"a": np.arange(64 * 32, dtype=np.int32).reshape(64, 32),
             "b": np.arange(16, dtype=np.int32)}
    sender.send(bi, mb=0, seq=8, env=env_i)
    env_o = {}
    recver.recv(bi, mb=0, seq=8, env=env_o)
    np.testing.assert_array_equal(env_o["a"], env_i["a"])
    del cwire


def test_pipeline_parity_with_activation_compression(monkeypatch):
    """ACCEPTANCE: activation compression composes with the PP parity
    contract — a 2-stage x 2-microbatch run with fp16 boundary frames
    matches the fused reference within the grad-exactness tolerance
    (lossy boundaries trade the bitwise contract for the tolerance one,
    loudly opt-in via BPS_ACT_COMPRESS)."""
    import optax
    monkeypatch.setenv("BPS_ACT_COMPRESS_MIN", "0")
    params, full, mb = _mlp_case()
    prog = StagePartitioner(2).build(mlp_loss, params, mb, name="actc")
    assert prog is not None
    stores = [ActStore(), ActStore()]
    acts = [ActivationExchange(0, stores[0],
                               peer_next=LocalActPeer(stores[1]),
                               timeout_ms=15000, codec="fp16"),
            ActivationExchange(1, stores[1],
                               peer_prev=LocalActPeer(stores[0]),
                               timeout_ms=15000, codec="fp16")]
    tx = optax.adam(1e-2)
    drv = [PipelineStageDriver(prog, s, params, tx, acts[s], 2)
           for s in (0, 1)]
    steps = 4
    results = _run_stages(drv, full, steps)
    want_losses, _ = _parity_reference(prog, params, full, 2, tx, steps)
    got = [np.asarray(l) for l in results[1]]
    for a, b in zip(got, want_losses):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)

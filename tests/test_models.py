"""Model-family tests: shapes, distributed training, TP/SP equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import byteps_tpu as bps
from byteps_tpu.models import bert, gpt2, resnet, transformer, vgg
from byteps_tpu.parallel.mesh import make_mesh
from byteps_tpu.training import DistributedTrainer


def test_bert_tiny_forward_shape():
    cfg = bert.bert_tiny()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = np.zeros((2, 16), np.int32)
    h = transformer.apply(params, cfg, jnp.asarray(toks))
    assert h.shape == (2, 16, cfg.hidden)
    lg = transformer.logits(params, cfg, h)
    assert lg.shape == (2, 16, cfg.vocab_size)


def test_bert_tiny_trains(mesh8):
    bps.init(mesh=mesh8)
    cfg = bert.bert_tiny()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)

    def loss_fn(p, batch):
        return bert.mlm_loss(p, cfg, batch)

    trainer = DistributedTrainer(loss_fn, params, optax.adam(3e-3), mesh=mesh8)
    fixed = bert.synth_mlm_batch(rng, 16, 32, cfg.vocab_size)
    losses = [float(trainer.step(fixed)) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.8  # memorizes the fixed batch


def test_gpt2_tiny_trains(mesh8):
    bps.init(mesh=mesh8)
    cfg = gpt2.gpt2_tiny()
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.RandomState(1)

    def loss_fn(p, batch):
        return gpt2.causal_lm_loss(p, cfg, batch)

    trainer = DistributedTrainer(loss_fn, params, optax.adam(3e-3), mesh=mesh8)
    fixed = gpt2.synth_lm_batch(rng, 16, 33, cfg.vocab_size)
    losses = [float(trainer.step(fixed)) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.8  # memorizes the fixed batch


def test_resnet_forward_and_grad():
    params = resnet.init_resnet50(jax.random.PRNGKey(0), num_classes=10,
                                  stages=[(1, 64), (1, 128)])
    x, y = resnet.synth_imagenet_batch(np.random.RandomState(0), 2, size=32,
                                       classes=10)
    lg = resnet.resnet50_apply(params, jnp.asarray(x))
    assert lg.shape == (2, 10)
    g = jax.grad(resnet.resnet_loss)(params, (jnp.asarray(x), jnp.asarray(y)))
    assert np.isfinite(float(jax.tree_util.tree_reduce(
        lambda a, b: a + jnp.abs(b).sum(), g, 0.0)))


def test_vgg_forward():
    params = vgg.init_vgg16(jax.random.PRNGKey(0), num_classes=10, in_hw=32)
    x = np.zeros((2, 32, 32, 3), np.float32)
    lg = vgg.vgg16_apply(params, jnp.asarray(x))
    assert lg.shape == (2, 10)


# ----------------------------------------------------- TP / SP correctness

def _tiny_cfg(**kw):
    return bert.bert_tiny(**kw)


def test_tensor_parallel_matches_single_device():
    """TP=4 forward must equal the unsharded forward — the Megatron
    column/row split is an exact reparameterization."""
    mesh = make_mesh({"model": 4}, devices=jax.devices()[:4])
    cfg_tp = _tiny_cfg(tp_axis="model")
    cfg_ref = _tiny_cfg()
    params = transformer.init_params(jax.random.PRNGKey(2), cfg_ref)
    toks = np.asarray(np.random.RandomState(3).randint(1, 100, (2, 16)),
                      dtype=np.int32)
    want = np.asarray(transformer.apply(params, cfg_ref, jnp.asarray(toks)))

    specs = transformer.param_specs(cfg_tp)

    def fwd(p, t):
        return transformer.apply(p, cfg_tp, t)

    fn = jax.jit(jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda s: s, specs,
                                         is_leaf=lambda x: isinstance(x, P)),
                  P()),
        out_specs=P(), check_vma=False))
    sharded_params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: not isinstance(x, (dict, list)))
    got = np.asarray(fn(sharded_params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_sequence_parallel_matches_single_device():
    """SP=4 (ring attention) forward must equal the unsharded forward."""
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    cfg_sp = _tiny_cfg(sp_axis="seq")
    cfg_ref = _tiny_cfg()
    params = transformer.init_params(jax.random.PRNGKey(4), cfg_ref)
    toks = np.asarray(np.random.RandomState(5).randint(1, 100, (2, 32)),
                      dtype=np.int32)
    want = np.asarray(transformer.apply(params, cfg_ref, jnp.asarray(toks)))

    def fwd(p, t):
        return transformer.apply(p, cfg_sp, t)

    fn = jax.jit(jax.shard_map(fwd, mesh=mesh,
                               in_specs=(P(), P(None, "seq")),
                               out_specs=P(None, "seq"), check_vma=False))
    got = np.asarray(fn(params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_dp_tp_sp_combined_train_step():
    """2×2×2 mesh: data × model × seq all at once through ShardedTrainer —
    the full multi-way sharding the driver's dryrun exercises. Training on
    a fixed batch must reduce the loss (grad sync across every axis must
    be correct for that to happen)."""
    from byteps_tpu.training import ShardedTrainer
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2})
    cfg = _tiny_cfg(tp_axis="model", sp_axis="seq")
    params = transformer.init_params(jax.random.PRNGKey(6), cfg)
    specs = transformer.param_specs(cfg)

    def loss_fn(p, batch):
        return bert.mlm_loss(p, cfg, batch)

    trainer = ShardedTrainer(loss_fn, params, specs, optax.adam(3e-3),
                             mesh=mesh)
    rng = np.random.RandomState(7)
    toks, tgts = bert.synth_mlm_batch(rng, 8, 32, cfg.vocab_size)
    losses = [float(trainer.step((toks, tgts))) for _ in range(25)]
    assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0] * 0.8, losses[::5]


class TestMLMGatheredHead:
    """mlm_loss(max_predictions=K) — LM head on gathered masked positions
    must match the full-sequence path exactly when K covers every mask."""

    def _setup(self):
        from byteps_tpu.models import bert, transformer
        cfg = bert.bert_tiny()
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(3)
        batch = bert.synth_mlm_batch(rng, 4, 64, cfg.vocab_size)
        return bert, cfg, params, batch

    def test_loss_and_grads_match_full_path(self):
        bert, cfg, params, batch = self._setup()
        full = bert.mlm_loss(params, cfg, batch)
        gath = bert.mlm_loss(params, cfg, batch, max_predictions=64)
        np.testing.assert_allclose(float(full), float(gath), rtol=1e-6)
        gf = jax.grad(lambda p: bert.mlm_loss(p, cfg, batch))(params)
        gg = jax.grad(lambda p: bert.mlm_loss(
            p, cfg, batch, max_predictions=64))(params)
        for a, b in zip(jax.tree_util.tree_leaves(gf),
                        jax.tree_util.tree_leaves(gg)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    def test_cap_overflow_drops_latest_positions(self):
        bert, cfg, params, batch = self._setup()
        tokens, targets = batch
        n_masked = int((targets >= 0).sum(axis=1).max())
        k = max(1, n_masked - 2)        # force overflow on some row
        loss = bert.mlm_loss(params, cfg, batch, max_predictions=k)
        assert np.isfinite(float(loss))
        # truncated loss equals the full loss computed on the truncated
        # target set (earliest k masked positions per row kept)
        t2 = np.asarray(targets).copy()
        for r in range(t2.shape[0]):
            pos = np.where(t2[r] >= 0)[0]
            t2[r, pos[k:]] = -1
        ref = bert.mlm_loss(params, cfg, (tokens, t2.astype(np.int32)))
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)

    def test_zero_masks_safe(self):
        bert, cfg, params, batch = self._setup()
        tokens, targets = batch
        none = np.full_like(np.asarray(targets), -1)
        loss = bert.mlm_loss(params, cfg, (tokens, none), max_predictions=8)
        assert float(loss) == 0.0


@pytest.mark.parametrize("policy", [None, "dots", "mlp_only", "save_attn"])
def test_remat_policies_match_no_remat(policy):
    """Every remat_policy computes the same function as remat=False."""
    import dataclasses
    cfg0 = bert.bert_tiny()                        # remat=False
    cfg = dataclasses.replace(cfg0, remat=True, remat_policy=policy)
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    batch = bert.synth_mlm_batch(np.random.RandomState(1), 4, 32,
                                 cfg.vocab_size)

    def lg(c):
        loss, grads = jax.value_and_grad(
            lambda p: bert.mlm_loss(p, c, batch))(params)
        return loss, grads

    l_ref, g_ref = lg(cfg0)
    l, g = lg(cfg)
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g, g_ref)


def test_remat_policy_validation():
    import dataclasses
    cfg = bert.bert_tiny()
    with pytest.raises(ValueError, match="remat_policy"):
        dataclasses.replace(cfg, remat=True, remat_policy="bogus")
    with pytest.raises(ValueError, match="ignored"):
        dataclasses.replace(cfg, remat=False, remat_policy="dots")


def test_causal_lm_loss_keeps_full_length():
    """causal_lm_loss must not shift the sequence to s-1: that silently
    disqualified the flash kernels (seq % 128 != 0) — the full-length
    form with a masked last target computes the identical loss."""
    from byteps_tpu.models.transformer import lm_loss
    cfg = gpt2.gpt2_tiny()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(1, cfg.vocab_size, (2, 64)),
        jnp.int32)
    got = float(gpt2.causal_lm_loss(params, cfg, tokens))
    want = float(lm_loss(params, cfg, (tokens[:, :-1], tokens[:, 1:])))
    np.testing.assert_allclose(got, want, rtol=1e-6)

    # grads identical too (the extra masked position contributes nothing)
    g1 = jax.grad(lambda p: gpt2.causal_lm_loss(p, cfg, tokens))(params)
    g2 = jax.grad(lambda p: lm_loss(p, cfg, (tokens[:, :-1],
                                             tokens[:, 1:])))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        g1, g2)


def test_chunked_lm_head_matches_full():
    """lm_head_chunk computes the identical loss AND gradients to the
    full [s, vocab] head — only the memory profile changes."""
    import dataclasses

    from byteps_tpu.models import gpt2

    cfg_full = gpt2.gpt2_tiny()    # max_seq 64 built in
    cfg_chunk = dataclasses.replace(cfg_full, lm_head_chunk=16)
    params = transformer.init_params(jax.random.PRNGKey(3), cfg_full)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg_full.vocab_size, (2, 64)))

    def loss(c):
        return lambda p: gpt2.causal_lm_loss(p, c, tokens)

    lf, gf = jax.value_and_grad(loss(cfg_full))(params)
    lc, gc = jax.value_and_grad(loss(cfg_chunk))(params)
    np.testing.assert_allclose(float(lf), float(lc), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    # chunk not dividing s falls back to the full head (same value)
    cfg_odd = dataclasses.replace(cfg_full, lm_head_chunk=17)
    np.testing.assert_allclose(
        float(loss(cfg_odd)(params)), float(lf), rtol=1e-6)


def test_chunked_lm_head_composes_with_sequence_parallel():
    """lm_head_chunk under SP: each rank chunks its LOCAL sequence shard;
    the psum'd global loss must match the unsharded full-head loss."""
    import dataclasses

    from byteps_tpu.models import gpt2

    cfg_ref = gpt2.gpt2_tiny()
    cfg_sp = dataclasses.replace(cfg_ref, sp_axis="seq", lm_head_chunk=8)
    params = transformer.init_params(jax.random.PRNGKey(5), cfg_ref)
    tokens = jnp.asarray(np.random.RandomState(6).randint(
        1, cfg_ref.vocab_size, (2, 64)))
    want = float(gpt2.causal_lm_loss(params, cfg_ref, tokens))

    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    fn = jax.jit(jax.shard_map(
        lambda p, t: gpt2.causal_lm_loss(p, cfg_sp, t),
        mesh=mesh, in_specs=(P(), P(None, "seq")), out_specs=P(),
        check_vma=False))
    got = float(fn(params, tokens))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_remat_layers_validation_and_exactness():
    """remat_layers must be gated on remat=True; partial remat computes
    the same loss/grads as full remat."""
    import dataclasses
    import pytest as _pt
    from byteps_tpu.models import transformer as T

    with _pt.raises(ValueError, match="remat_layers"):
        T.TransformerConfig(layers=4, remat=False, remat_layers=2)
    with _pt.raises(ValueError, match="remat_layers"):
        T.TransformerConfig(layers=4, remat_layers=9)

    cfg = T.TransformerConfig(vocab_size=128, hidden=64, layers=4, heads=4,
                              mlp_dim=128, max_seq=32, attn_impl="naive")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    tgt = jnp.where(jax.random.uniform(jax.random.PRNGKey(2), (2, 32)) < 0.2,
                    tok, -1)

    def loss(cfgv):
        return lambda p: T.lm_loss(p, cfgv, (tok, tgt))

    l_full, g_full = jax.value_and_grad(loss(cfg))(params)
    cfg2 = dataclasses.replace(cfg, remat_layers=2)
    l_part, g_part = jax.value_and_grad(loss(cfg2))(params)
    assert jnp.allclose(l_full, l_part, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g_full, g_part)


def _block_eqns(cfg, b, s):
    """(pallas_call, transpose) equations of one block's value and
    gradient, the flash kernels in the interpreter."""
    from test_flash_attention import equations
    params = transformer.init_params(jax.random.PRNGKey(5), cfg)
    blk = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    x = jnp.zeros((b, s, cfg.hidden), jnp.bfloat16)

    def loss(x, blk):
        return transformer._block(x, blk, cfg, 1).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(x, blk)
    return equations(jaxpr, "pallas_call"), equations(jaxpr, "transpose")


def test_projection_hands_the_kernels_what_they_read(monkeypatch):
    """Four heads of 64: q, k and v leave their products as [b, s, 256]
    and reach the flash kernels so, out enters ``attn_out`` so, and no
    transpose of an activation stands between a product and a kernel
    (the ones left turn weights for their gradients)."""
    import byteps_tpu.ops.flash_attention as fa
    flash = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: flash(
        *a, **dict(kw, interpret=True)))
    cfg = transformer.TransformerConfig(
        vocab_size=64, hidden=256, layers=1, heads=4, mlp_dim=512,
        max_seq=128, remat=False, attn_impl="flash")
    calls, transposes = _block_eqns(cfg, 2, 128)
    assert [str(e.params["name"]) for e in calls] == [
        "bps_flash_fwd", "bps_flash_bwd_fused"]
    for eqn in calls:
        wide = [v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)
                if v.aval.dtype == jnp.bfloat16]
        assert wide and set(wide) == {(2, 128, 256)}, wide
    assert all(t.invars[0].aval.ndim == 2 for t in transposes), [
        t.invars[0].aval.shape for t in transposes]


def test_three_products_are_the_one_product():
    """``_attention``'s three products on slices of the stored
    [h, 3, heads, head_dim] weight compute what one einsum to
    [b, s, 3, heads, head_dim] and three slices did, gradient to the
    weight (one leaf, stored shape) included."""
    from byteps_tpu.ops.flash_attention import local_attention
    cfg = bert.bert_tiny()
    params = transformer.init_params(jax.random.PRNGKey(6), cfg)
    blk = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    x = jnp.asarray(np.random.RandomState(6).randn(2, 16, cfg.hidden),
                    jnp.float32)

    def one_product(x, blk):
        qkv = jnp.einsum("bsh,hcnd->bscnd", x, blk["qkv"])
        out = local_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        return out.reshape(*x.shape[:2], -1) @ blk["attn_out"]

    def three(x, blk):
        return transformer._attention(x, blk, cfg, 1)

    np.testing.assert_allclose(np.asarray(three(x, blk)),
                               np.asarray(one_product(x, blk)),
                               rtol=1e-5, atol=1e-6)
    g3, g1 = (jax.grad(lambda x, blk: jnp.sum(jnp.sin(f(x, blk))), (0, 1))(
        x, blk) for f in (three, one_product))
    assert g3[1]["qkv"].shape == (cfg.hidden, 3, cfg.heads, cfg.head_dim)
    for a, b_ in ((g3[0], g1[0]), (g3[1]["qkv"], g1[1]["qkv"]),
                  (g3[1]["attn_out"], g1[1]["attn_out"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-6)

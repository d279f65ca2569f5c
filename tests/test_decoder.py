"""The decoder whose layers are a list of kinds (``models/decoder.py``,
the afmoe family) against its plain reference
(``benchmark/reference/afmoe_share.py``) on seeded random weights: the
loss and EVERY leaf's gradient, with a window shorter than the sequence,
two and four query heads a kv head, a dense and both routed kinds of
layer; through ``DistributedTrainer``; and what the configuration
refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import decoder
from byteps_tpu.parallel.mesh import make_mesh
from byteps_tpu.training import DistributedTrainer

from benchmark.reference import afmoe_share as ref

SIZES = dict(vocab_size=128, hidden=64, heads=4, kv_heads=2, head_dim=16,
             mlp_dim=96, moe_dim=32, window=8, top_k=2, router_outputs=8,
             held=[0, 1, 2, 3], route_scale=2.0, shared_experts=1,
             max_seq=64, rope_theta=10000.0, norm_eps=1e-5,
             layer_kinds=["dense_sliding", "moe_sliding", "moe_full"])


def _tokens(rows=2, seq=32, seed=0):
    return np.random.default_rng(seed).integers(1, 128, size=(rows, seq),
                                                dtype=np.int32)


def _reference(params, tokens, sizes, precision="float32"):
    toks, targets = ref.targets_of(tokens, "lm")
    shape = (tokens.shape[0], 1, tokens.shape[1])
    return ref.loss_and_grads(params, toks.reshape(shape),
                              targets.reshape(shape),
                              dict(ref._static(sizes)), precision)


@pytest.mark.parametrize("change", [
    {}, {"kv_heads": 1}, {"held": [1, 6], "top_k": 4},
    {"layer_kinds": ["dense_full", "moe_full", "moe_sliding", "moe_sliding"],
     "window": 5},
    {"shared_experts": 0,
     "routed_kw": {"impl": "gmm_interpret", "row_tile": 128}},
    {"balanced": True},
], ids=["two_q_heads_a_kv", "four_q_heads_a_kv", "two_of_eight_held",
        "other_pattern", "kernels_no_shared", "balanced_choice"])
def test_loss_and_every_leaf_gradient_match_the_reference(change):
    sizes = {**SIZES, **change}
    routed_kw = sizes.pop("routed_kw", {"row_tile": 8})
    params = ref.make_params(3, sizes)
    cfg = decoder.afmoe_config(**sizes, dtype="float32", lm_head_chunk=16,
                               routed_kw=routed_kw)
    tokens = _tokens()
    loss, grads = jax.value_and_grad(
        lambda p: decoder.causal_lm_loss(p, cfg, jnp.asarray(tokens)))(params)
    want_loss, want = _reference(params, tokens, sizes)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    names = ref.leaf_names(params)
    assert len(names) == len(jax.tree_util.tree_leaves(grads))
    for name, got, exp in zip(names, jax.tree_util.tree_leaves(grads),
                              jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(exp).max())
        assert scale > 0, name          # every leaf is reached
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5 * scale, rtol=1e-4,
                                   err_msg=name)


def test_the_programs_own_init_is_the_references_tree():
    cfg = decoder.afmoe_config(**SIZES)
    mine = decoder.init_params(jax.random.PRNGKey(0), cfg)
    theirs = ref.make_params(0, SIZES)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(
        theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32


def test_lower_precisions_of_the_reference_differ_and_keep_the_choice():
    """bfloat16 and float8 move the loss; the router's scores stay
    float32 in each, as the configuration states them."""
    params = ref.make_params(5, SIZES)
    tokens = _tokens()
    loss = {p: float(_reference(params, tokens, SIZES, p)[0])
            for p in ("float32", "bfloat16", "float8")}
    assert 0 < abs(loss["bfloat16"] - loss["float32"]) < abs(
        loss["float8"] - loss["float32"]) < 0.05 * loss["float32"]


def test_distributed_trainer_trains_it_as_the_reference_does():
    """Three AdamW steps of the unchanged trainer on two devices against
    the reference's ``train_steps``: each loss and every leaf's change."""
    sizes = dict(SIZES)
    optimizer = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                     weight_decay=1e-4)
    params = ref.make_params(7, sizes)
    cfg = decoder.afmoe_config(**sizes, dtype="float32",
                               routed_kw={"row_tile": 8})
    batches = [_tokens(4, 32, seed=s) for s in range(3)]
    want = ref.train_steps(params, batches, sizes, optimizer, "lm", 2)
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    trainer = DistributedTrainer(
        lambda p, b: decoder.causal_lm_loss(p, cfg, b), params,
        optax.adamw(**optimizer), mesh=mesh)
    losses = [float(trainer.step(b)) for b in batches]
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    change = ref.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, trainer.params, params))
    np.testing.assert_allclose(np.asarray(change), want["change_norm"],
                               rtol=2e-2)


@pytest.mark.parametrize("dtype,rtol,change_rtol", [
    ("float32", 2e-5, 2e-2), ("bfloat16", 1e-3, 5e-2)])
def test_distributed_trainer_trains_the_same_under_the_layers_checkpoints(
        dtype, rtol, change_rtol):
    """Three AdamW steps of the trainer on ``afmoe_tiny`` with every layer
    under its checkpoint (which keeps the experts' weights in the compute
    dtype and what the norms after the halves read) against the same with
    no checkpoint: each loss and every leaf's change. In bf16 the jitted
    steps round a remade value apart from a kept one (5e-4 of the second
    loss before the checkpoint kept these, 3e-4 with them; Adam's step
    carries that into a small leaf's change at up to 3.4e-2)."""
    optimizer = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                     weight_decay=1e-4)
    params = decoder.init_params(jax.random.PRNGKey(7),
                                 decoder.afmoe_tiny())
    batches = [_tokens(4, 32, seed=s) for s in range(3)]
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])

    def train(remat):
        cfg = decoder.afmoe_tiny(dtype=dtype, remat=remat)
        trainer = DistributedTrainer(
            lambda p, b: decoder.causal_lm_loss(p, cfg, b), params,
            optax.adamw(**optimizer), mesh=mesh)
        losses = [float(trainer.step(b)) for b in batches]
        return losses, ref.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, trainer.params, params))

    losses, change = train(True)
    want_losses, want_change = train(False)
    np.testing.assert_allclose(losses, want_losses, rtol=rtol)
    np.testing.assert_allclose(np.asarray(change), np.asarray(want_change),
                               rtol=change_rtol)


def test_what_a_configuration_refuses():
    with pytest.raises(ValueError, match="none of"):
        decoder.afmoe_config(**{**SIZES, "layer_kinds": ["dense"]})
    with pytest.raises(ValueError, match="heads over"):
        decoder.afmoe_config(**{**SIZES, "kv_heads": 3})
    with pytest.raises(ValueError, match="distinct outputs"):
        decoder.afmoe_config(**{**SIZES, "held": [1, 1]})
    with pytest.raises(ValueError, match="distinct outputs"):
        decoder.afmoe_config(**{**SIZES, "held": [8]})


def test_a_balanced_choice_holds_every_routed_layer_at_its_mean_load():
    """The seed's random routers send the held experts far more or fewer
    rows than their share; with ``balanced`` every expert of every routed
    layer is chosen about ``T top_k / outputs`` times, on any batch."""
    from byteps_tpu.models import moe
    sizes = dict(SIZES, layer_kinds=["dense_sliding", "moe_sliding",
                                     "moe_full", "moe_sliding"])
    z = dict(ref._static(sizes))
    params = ref.make_params(11, sizes)
    tokens = jnp.asarray(_tokens(8, 64, seed=1))
    mean = tokens.size * sizes["top_k"] / sizes["router_outputs"]

    def counts(balanced):
        cfg = decoder.afmoe_config(**sizes, dtype="float32",
                                   balanced=balanced)
        zb, dot, out = dict(z, balanced=balanced), ref.partial(
            ref._dot, "float32"), []
        x = params["embed"][tokens] * np.sqrt(sizes["hidden"])
        for kind, blk in zip(sizes["layer_kinds"], params["layers"]):
            x = x + ref._attention(x, blk["attn"], z,
                                   kind.endswith("sliding"), dot)
            if kind.startswith("moe"):
                f = ref._rmsnorm(x, blk["ffn"]["norm_pre"], 1e-5)
                _, chosen = moe.route(f.reshape(-1, f.shape[-1]),
                                      blk["ffn"]["router"], cfg.routed)
                out.append(np.bincount(np.asarray(chosen).ravel(),
                                       minlength=sizes["router_outputs"]))
            x = x + ref._ffn(x, blk["ffn"], zb, kind.startswith("moe"), dot)
        return np.stack(out)

    before, after = counts(False), counts(True)
    assert before.shape == (3, 8)
    assert np.abs(before - mean).max() > 0.3 * mean
    assert np.abs(after - mean).max() <= 0.2 * mean

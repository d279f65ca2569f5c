"""What a block's checkpoint keeps by default (ISSUE 36): the flash
kernel's output and row statistics in both families of block, and the
routed layer's plan. The values are ``remat=False``'s; the kernel's
forward and the plan are made once a layer, not again in the backward's
recompute; and the minimum-memory form (``remat_policy=None``) still
makes the kernel's forward twice. CPU: the kernels in the interpreter,
counted in the gradient's jaxpr (what the chip's compiler makes of the
same steps is ``tests/test_chip_compile.py``'s)."""

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu.ops.flash_attention as fa
from byteps_tpu.models import bert, decoder, gpt2, moe, transformer

# the primitives a ``jax.checkpoint`` region is in a gradient's jaxpr
CHECKPOINTS = ("checkpoint", "remat", "remat2")


@pytest.fixture
def interpreted(monkeypatch):
    """The flash kernels in the interpreter, in both families: the
    transformer's by ``attn_impl="flash"``, the decoder's by its
    ``attention`` bound to them."""
    flash = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: flash(
        *a, **dict(kw, interpret=True)))
    monkeypatch.setattr(decoder, "attention",
                        functools.partial(fa.attention, impl="flash"))


def _preset(family):
    """(config with ``remat=False`` at a length the kernels take,
    parameters, ``loss(params, config)``) of a family's tiny preset."""
    rng = np.random.RandomState(0)
    if family in ("bert", "gpt2"):
        tiny = bert.bert_tiny() if family == "bert" else gpt2.gpt2_tiny()
        cfg = dataclasses.replace(tiny, max_seq=128, attn_impl="flash")
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        if family == "bert":
            batch = bert.synth_mlm_batch(rng, 2, 128, cfg.vocab_size)
            return cfg, params, lambda p, c: bert.mlm_loss(
                p, c, batch, max_predictions=8)
        tokens = gpt2.synth_lm_batch(rng, 2, 128, cfg.vocab_size)
        return cfg, params, lambda p, c: gpt2.causal_lm_loss(p, c, tokens)
    cfg = getattr(decoder, family + "_tiny")(balanced=True)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    tokens = gpt2.synth_lm_batch(rng, 2, 128, cfg.vocab_size)
    return cfg, params, lambda p, c: decoder.causal_lm_loss(p, c, tokens)


def _counts(jaxpr, inside=False, acc=None):
    """``{(primitive or kernel name, inside a checkpoint's recompute):
    equations}`` over a jaxpr and everything nested in it."""
    acc = collections.Counter() if acc is None else acc
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            name = str(eqn.params["name"])
        acc[name, inside] += 1
        deeper = inside or eqn.primitive.name in CHECKPOINTS
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _counts(sub, deeper, acc)
    return acc


def _gradient_counts(loss, params, cfg):
    return _counts(jax.make_jaxpr(jax.grad(lambda p: loss(p, cfg)))(
        params).jaxpr)


@pytest.mark.parametrize("family", ["bert", "gpt2", "afmoe", "nemotron_h"])
def test_the_default_checkpoint_computes_what_no_checkpoint_does(
        interpreted, family):
    """The loss and every gradient leaf under ``remat=True`` as it comes
    equal those under ``remat=False``, the kernels and the named values
    in the program."""
    cfg0, params, loss = _preset(family)
    cfg = dataclasses.replace(cfg0, remat=True)
    assert _gradient_counts(loss, params, cfg)["bps_flash_fwd", False]
    want_loss, want = jax.value_and_grad(lambda p: loss(p, cfg0))(params)
    got_loss, got = jax.value_and_grad(lambda p: loss(p, cfg))(params)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(want)]
    for path, a, b in zip(paths, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6, err_msg=path)


@pytest.mark.parametrize("family,forwards", [
    ("bert", 1), ("gpt2", 1), ("afmoe", 3), ("nemotron_h", 1)])
def test_the_kernels_forward_runs_once_a_layer(interpreted, family, forwards):
    """``bps_flash_fwd`` is in the forward once an attending layer (once
    in the body of the transformer's scan over its layers) and nowhere in
    a checkpoint's recompute; the backward kernels are, as ever."""
    cfg0, params, loss = _preset(family)
    counts = _gradient_counts(loss, params,
                              dataclasses.replace(cfg0, remat=True))
    assert counts["bps_flash_fwd", False] == forwards
    assert counts["bps_flash_fwd", True] == 0
    backward = sum(n for (name, inside), n in counts.items()
                   if name.startswith("bps_flash_bwd") and inside)
    assert backward >= forwards


def test_the_minimum_memory_form_still_recomputes_the_kernel(interpreted):
    """``remat_policy=None`` saves a block's input alone: the backward
    runs the kernel's forward again (the field's meaning from before the
    default kept the kernel's output)."""
    cfg0, params, loss = _preset("bert")
    assert transformer.TransformerConfig().remat_policy == "save_attn"
    cfg = dataclasses.replace(cfg0, remat=True, remat_policy=None)
    counts = _gradient_counts(loss, params, cfg)
    assert counts["bps_flash_fwd", False] == 1
    assert counts["bps_flash_fwd", True] == 1


@pytest.mark.parametrize("family,balanced", [
    ("afmoe", True), ("nemotron_h", True), ("afmoe", False)])
def test_the_plan_is_made_once_a_routed_layer(family, balanced):
    """In the gradient's jaxpr of the layers under their checkpoints the
    plan's sort and the choice's top-k appear once a routed layer, in the
    forward, under either choice; without the policy's name for the plan
    they appear again in every recompute (next test)."""
    cfg0, params, loss = _preset(family)
    cfg = dataclasses.replace(
        cfg0, remat=True,
        routed=dataclasses.replace(cfg0.routed, balanced=balanced))
    routed = sum("moe" in kind for kind in cfg.layer_kinds)
    assert routed == 2
    counts = _gradient_counts(loss, params, cfg)
    for primitive in ("sort", "top_k"):
        assert counts[primitive, False] == routed, primitive
        assert counts[primitive, True] == 0, primitive


def test_without_the_plans_name_the_recompute_makes_it_again(monkeypatch):
    """The same count with the plan saved under a name the policy does not
    keep: a sort and a top-k a routed layer in the recompute too. What the
    name is for, held by a test."""
    monkeypatch.setattr(moe, "PLAN_NAME", "not_saved")
    cfg0, params, loss = _preset("afmoe")
    counts = _gradient_counts(loss, params,
                              dataclasses.replace(cfg0, remat=True))
    for primitive in ("sort", "top_k"):
        assert counts[primitive, False] == 2, primitive
        assert counts[primitive, True] == 2, primitive


@pytest.mark.parametrize("balanced", [True, False])
def test_every_array_of_the_plan_carries_the_name(balanced):
    """``plan_rows`` names each int32 array it returns, and ``route`` the
    choice the plan is made from and the chosen scores, a gather by the
    named choice: 13 ``name`` equations a layer, whichever the choice."""
    cfg = moe.RoutedConfig(8, (0, 1, 2, 3), 2, row_tile=8, balanced=balanced)
    f = jnp.asarray(np.random.RandomState(0).randn(32, 16), jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).randn(16, 8), jnp.float32)

    def plan(f, w):
        return moe.plan_rows(moe.route(f, w, cfg)[1], cfg)

    jaxpr = jax.make_jaxpr(plan)(f, w).jaxpr
    named = [e for e in jaxpr.eqns if e.primitive.name == "name"]
    assert {e.params["name"] for e in named} == {moe.PLAN_NAME}
    out = jax.eval_shape(plan, f, w)
    assert len(named) == len(out) + 2
    assert all(v.dtype == jnp.int32 for v in out.values())
    floats = [e for e in named if e.outvars[0].aval.dtype == jnp.float32]
    assert [e.outvars[0].aval.shape for e in floats] == [(32, 2)]

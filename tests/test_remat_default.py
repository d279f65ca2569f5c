"""What a block's checkpoint keeps by default (ISSUE 36): the flash
kernel's output and row statistics in both families of block, and the
routed layer's plan; in the decoder family (ISSUE 52) the held experts'
weights in the compute dtype and what a norm after a half reads. The
values are ``remat=False``'s; the kernel's forward, the plan, the
weights' cast and afmoe's combine are made once a layer, not again in the
backward's recompute; and the minimum-memory form (``remat_policy=None``)
still makes the kernel's forward twice. CPU: the kernels in the interpreter,
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


def _kernel_or_primitive(eqn):
    name = eqn.primitive.name
    return str(eqn.params["name"]) if name == "pallas_call" else name


def _counts(jaxpr, inside=False, acc=None, name_of=_kernel_or_primitive):
    """``{(primitive or kernel name, inside a checkpoint's recompute):
    equations}`` over a jaxpr and everything nested in it; ``name_of``:
    another name for an equation."""
    acc = collections.Counter() if acc is None else acc
    for eqn in jaxpr.eqns:
        acc[name_of(eqn), inside] += 1
        deeper = inside or eqn.primitive.name in CHECKPOINTS
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _counts(sub, deeper, acc, name_of)
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


# ------------- the experts' bf16 weights and what a post-norm reads (ISSUE 52)

DECODERS = ["afmoe", "nemotron_h", "deepseek_v3"]


def _expert_shapes(params):
    """The shapes of the held experts' weights, a routed layer each."""
    return [{w.shape for w in blk.get("ffn", blk)["experts"].values()}
            for blk in params["layers"] if "experts" in blk.get("ffn", blk)]


@pytest.mark.parametrize("family", DECODERS)
def test_an_experts_weight_is_cast_once_a_routed_layer(family):
    """Under the checkpoints in bf16 the gradient's jaxpr holds ONE
    float32 -> bf16 convert an expert weight a routed layer, in the
    forward; without the policy's name for it the recompute casts every
    one again (what the name is for)."""
    cfg0, params, loss = _preset(family)
    cfg = dataclasses.replace(cfg0, remat=True, dtype="bfloat16")
    by_layer = _expert_shapes(params)
    weights = sum(len(shapes) for shapes in by_layer)
    assert len(by_layer) == 2 and weights == 4
    shapes = set().union(*by_layer)

    def is_cast(eqn):
        """A float32 -> bf16 convert of an array shaped as an expert's."""
        return (eqn.primitive.name == "convert_element_type"
                and eqn.invars[0].aval.shape in shapes
                and eqn.invars[0].aval.dtype == jnp.float32
                and eqn.params["new_dtype"] == jnp.bfloat16)

    def casts():
        counts = _counts(jax.make_jaxpr(jax.grad(
            lambda p: loss(p, cfg)))(params).jaxpr, name_of=is_cast)
        return counts[True, False], counts[True, True]

    assert casts() == (weights, 0)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "WEIGHTS_NAME", "not_saved")
        assert casts() == (weights, weights)


@pytest.mark.parametrize("family", DECODERS)
def test_a_layers_checkpoint_holds_the_weights_and_a_post_norms_input(
        family, capsys):
    """What ``jax.ad_checkpoint`` says the gradient keeps, under the
    checkpoints in bf16: a routed layer's two expert weights in bf16, in
    all three families; the [b, s, hidden] sums that a norm after a half
    reads in afmoe alone (two a layer: no other family has such a norm,
    so it names nothing and keeps nothing). A float kept under a policy's
    name is listed as the ``reduce_precision`` JAX wraps it in, at the
    line that names it."""
    cfg0, params, loss = _preset(family)
    cfg = dataclasses.replace(cfg0, remat=True, dtype="bfloat16")
    jax.ad_checkpoint.print_saved_residuals(lambda p: loss(p, cfg), params)
    kept = [line.split(" ", 1) for line in capsys.readouterr().out.splitlines()
            if "reduce_precision" in line]
    weights = sorted(aval for aval, why in kept if "routed_ffn" in why)
    assert weights == sorted(
        "bf16[%s]" % ",".join(map(str, shape))
        for shapes in _expert_shapes(params) for shape in shapes)
    sums = [aval for aval, why in kept
            if "_ffn_half" in why or "_attention_half" in why]
    halves = 2 * len(cfg.layer_kinds) if family == "afmoe" else 0
    assert sums == ["bf16[2,128,%d]" % cfg.hidden] * halves
    # ... and nothing else of a layer's floats but the plan's chosen
    # scores and the rows' weights (``moe.PLAN_NAME``)
    assert all("moe.py" in why for aval, why in kept
               if aval not in weights + sums)


@pytest.mark.parametrize("family", DECODERS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_decoders_checkpoint_computes_what_no_checkpoint_does(
        family, dtype):
    """The loss and every gradient leaf under ``remat=True`` against
    ``remat=False``, op by op (no ``jit``: nothing is fused, so a kept
    value is the value the forward wrote and a remade one the same product
    on the same operands): float32 bit for bit, bf16 to this file's
    tolerance. (Under ``jit`` XLA:CPU remakes a bf16 value inside a fusion
    at another rounding than the forward wrote it, with or without the
    names: a leaf moves by up to 1.4e-2 of its largest entry.)"""
    cfg0, params, loss = _preset(family)
    cfg0 = dataclasses.replace(cfg0, dtype=dtype)
    cfg = dataclasses.replace(cfg0, remat=True)
    want_loss, want = jax.value_and_grad(lambda p: loss(p, cfg0))(params)
    got_loss, got = jax.value_and_grad(lambda p: loss(p, cfg))(params)
    assert float(got_loss) == float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(b).max() > 0, path
        if dtype == "float32":
            np.testing.assert_array_equal(a, b, err_msg=str(path))
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       err_msg=str(path))


@pytest.mark.parametrize("family", DECODERS)
def test_the_combine_runs_three_times_a_routed_layer(family):
    """``bps_moe_combine`` in the gradient's jaxpr, the layers under their
    checkpoints, a routed layer: once in the forward and twice in the
    backward (the take's transpose and the weights' gradient), which lies
    inside the checkpoint's equation beside the recompute, and there is
    no third there. In afmoe a norm reads the feed-forward's output: its
    checkpoint keeps that sum, or the recompute would run the combine a
    fourth time (shown with the name taken away)."""
    sizes = dict(hidden=128, moe_dim=64, remat=True,
                 routed_kw={"impl": "gmm", "row_tile": 128})
    cfg = getattr(decoder, family + "_tiny")(**sizes)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    tokens = gpt2.synth_lm_batch(np.random.RandomState(0), 2, 128,
                                 cfg.vocab_size)
    routed = sum("moe" in kind for kind in cfg.layer_kinds)

    def combines():
        counts = _gradient_counts(
            lambda p, c: decoder.causal_lm_loss(p, c, tokens), params, cfg)
        return [counts["bps_moe_combine", inside] / routed
                for inside in (False, True)]

    assert combines() == [1, 2]
    if family == "afmoe":
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(decoder, "checkpoint_name", lambda x, name: x)
            assert combines() == [1, 3]

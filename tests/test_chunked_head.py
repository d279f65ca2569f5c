"""The chunked head (``transformer._chunked_nll_sum``): one product a
chunk where nothing is differentiated, three where it is (the logits, d h,
d head, the gradient formed while the chunk's logits are live), nothing
recomputed, no ``[s, vocab]`` value; value and gradients against plain
``jax.grad`` of the unchunked log-softmax form."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import decoder, gpt2, transformer
from byteps_tpu.models.transformer import _chunked_nll_sum

B, S, HIDDEN, VOCAB = 2, 64, 32, 97     # a vocabulary no other size equals


def _plain_nll_sum(h, emb, targets, mask, dt):
    """All positions at once, differentiated by JAX."""
    lg = jnp.einsum("bsh,vh->bsv", h.astype(dt), emb.astype(dt),
                    preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.where(mask, targets, 0)[..., None],
                               axis=-1)[..., 0]
    return (nll * mask).sum()


def _inputs(masking: str, seed=0, h_dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((B, S, HIDDEN)), h_dtype)
    emb = jnp.asarray(rng.standard_normal((VOCAB, HIDDEN)) * 0.3, jnp.float32)
    targets = rng.integers(0, VOCAB, size=(B, S)).astype(np.int32)
    if masking == "some":
        targets[rng.random((B, S)) < 0.3] = -1
    elif masking == "a_whole_chunk":        # positions 16..31 of every row
        targets[:, 16:32] = -1
        targets[0, 5] = -1
    targets = jnp.asarray(targets)
    return h, emb, targets, targets >= 0


COTANGENTS = {
    "unit": lambda nll, mask: nll,
    "times_3": lambda nll, mask: 3.0 * nll,
    "mean": lambda nll, mask: nll / jnp.maximum(
        mask.sum().astype(jnp.float32), 1.0),
}
# bf16 products: this function rounds d logits to bf16 before its two
# gradient products, as the chip's default precision does; the CPU's
# transposed einsum takes them in fp32 and rounds its result to bf16. Two
# roundings of 2**-9 apart, against the largest element of the leaf.
TOLERANCE = {"float32": 1e-6, "bfloat16": 2.0 ** -7}


@pytest.mark.parametrize("cotangent", list(COTANGENTS))
@pytest.mark.parametrize("masking", ["none", "some", "a_whole_chunk"])
@pytest.mark.parametrize("chunk", [16, S], ids=["four_chunks", "one_chunk"])
@pytest.mark.parametrize("dtype", list(TOLERANCE))
def test_value_and_gradients_match_the_unchunked_form(dtype, chunk, masking,
                                                      cotangent):
    dt, tol = jnp.dtype(dtype), TOLERANCE[dtype]
    h, emb, targets, mask = _inputs(
        masking, h_dtype=jnp.float32 if dtype == "float32" else jnp.bfloat16)
    scale = COTANGENTS[cotangent]

    def of(nll_sum):
        return jax.jit(jax.value_and_grad(
            lambda h, emb: scale(nll_sum(h, emb), mask), argnums=(0, 1)))

    got, (dh, demb) = of(lambda h, emb: _chunked_nll_sum(
        h, emb, targets, mask, chunk, dt))(h, emb)
    want, (dh_w, demb_w) = of(lambda h, emb: _plain_nll_sum(
        h, emb, targets, mask, dt))(h, emb)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    assert dh.dtype == h.dtype and demb.dtype == emb.dtype
    assert dh.shape == h.shape and demb.shape == emb.shape
    for name, a, b in (("h", dh, dh_w), ("head", demb, demb_w)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 0, name
        np.testing.assert_allclose(a, b, rtol=0, atol=tol * np.abs(b).max(),
                                   err_msg=name)
    # a masked position takes no gradient at all
    assert not np.asarray(dh, np.float32)[~np.asarray(mask)].any()
    # and the value alone (no differentiation) is the same sum
    alone = jax.jit(lambda h, emb: scale(_chunked_nll_sum(
        h, emb, targets, mask, chunk, dt), mask))(h, emb)
    np.testing.assert_allclose(float(alone), float(got), rtol=1e-6)


@pytest.mark.parametrize("dtype", list(TOLERANCE))
@pytest.mark.parametrize("masking", ["some", "a_whole_chunk"])
def test_tied_head_through_lm_loss(masking, dtype):
    """``lm_loss`` with ``lm_head_chunk`` against the full head: the
    table takes the head's gradient and the embedding's."""
    full = dataclasses.replace(gpt2.gpt2_tiny(), dtype=dtype)
    chunked = dataclasses.replace(full, lm_head_chunk=16)
    params = transformer.init_params(jax.random.PRNGKey(3), full)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, full.vocab_size, (2, 64)), jnp.int32)
    targets = np.array(jnp.roll(tokens, -1, axis=1))
    if masking == "some":
        targets[rng.random(targets.shape) < 0.3] = -1
    else:
        targets[:, 16:32] = -1
    batch = (tokens, jnp.asarray(targets))
    lf, gf = jax.jit(jax.value_and_grad(
        lambda p: transformer.lm_loss(p, full, batch)))(params)
    lc, gc = jax.jit(jax.value_and_grad(
        lambda p: transformer.lm_loss(p, chunked, batch)))(params)
    np.testing.assert_allclose(float(lc), float(lf), rtol=2e-6)
    # bf16: d h differs by a rounding (TOLERANCE) and the two blocks'
    # bf16 backward passes round what they make of it again
    tol = 2e-5 if dtype == "float32" else 2.0 ** -5
    for a, b in zip(jax.tree_util.tree_leaves(gc),
                    jax.tree_util.tree_leaves(gf)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=tol * max(float(jnp.abs(b).max()), 1e-6))


@pytest.mark.parametrize("chunk", [16, 0], ids=["two_chunks", "one_chunk"])
def test_untied_head_through_the_decoders_loss(chunk):
    """``decoder.causal_lm_loss`` (every position through the chunked
    function, one chunk where ``lm_head_chunk`` is 0) against the plain
    form over ``decoder.apply``: the loss and every leaf's gradient."""
    cfg = decoder.afmoe_tiny(lm_head_chunk=chunk)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        1, cfg.vocab_size, size=(2, 32), dtype=np.int32))

    def plain(p):
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.full((2, 1), -1, tokens.dtype)], axis=1)
        mask = targets >= 0
        total = _plain_nll_sum(decoder.apply(p, cfg, tokens), p["head"],
                               targets, mask, jnp.dtype(cfg.dtype))
        return total / mask.sum()

    got, grads = jax.jit(jax.value_and_grad(
        lambda p: decoder.causal_lm_loss(p, cfg, tokens)))(params)
    want, grads_w = jax.jit(jax.value_and_grad(plain))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(grads_w)):
        scale = float(jnp.abs(b).max())
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-5 * scale, err_msg=str(path))


# ------------------------------------------------------------ the jaxprs

def _eqns(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold,
    each with the names of the primitives it lies inside."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside + (eqn.primitive.name,))


def _head_scans(jaxpr):
    """The bodies of the scans opened under ``bps.head``."""
    return [eqn.params["jaxpr"].jaxpr for eqn, _ in _eqns(jaxpr)
            if eqn.primitive.name == "scan"
            and "bps.head" in str(eqn.source_info.name_stack)]


def _products(jaxpr):
    return sum(eqn.primitive.name == "dot_general" for eqn, _ in _eqns(jaxpr))


def _head_alone(chunk=16):
    h, emb, targets, mask = _inputs("some")
    def f(h, emb):
        return _chunked_nll_sum(h, emb, targets, mask, chunk, jnp.bfloat16)
    return f, (h, emb)


def test_one_product_a_chunk_alone_and_three_differentiated():
    f, args = _head_alone()
    primal = jax.make_jaxpr(f)(*args).jaxpr
    (body,) = _head_scans(primal)
    assert _products(body) == 1 and _products(primal) == 1
    grad = jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(*args).jaxpr
    (body,) = _head_scans(grad)             # the backward opens no scan
    assert _products(body) == 3 and _products(grad) == 3
    # the two that the differentiation added stand under a scope of
    # their own (a body's names are relative to its scan's)
    names = [str(eqn.source_info.name_stack) for eqn, _ in _eqns(body)
             if eqn.primitive.name == "dot_general"]
    assert sum("bps.head.grad" in n for n in names) == 2


@pytest.mark.parametrize("family", ["afmoe", "nemotron_h", "deepseek_v3",
                                    "gpt2_chunked"])
def test_the_differentiated_step_recomputes_nothing_under_the_head(family):
    """A whole model's gradient, its layers checkpointed: one scan under
    ``bps.head`` with three products in it, no equation under ``bps.head``
    inside a checkpoint, and no ``rematted_computation`` beside
    ``bps.head`` in the lowered step's names."""
    if family == "gpt2_chunked":
        cfg = dataclasses.replace(gpt2.gpt2_tiny(), lm_head_chunk=16)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        loss = lambda p, t: gpt2.causal_lm_loss(p, cfg, t)
    else:
        cfg = getattr(decoder, family + "_tiny")(lm_head_chunk=16, remat=True)
        params = decoder.init_params(jax.random.PRNGKey(0), cfg)
        loss = lambda p, t: decoder.causal_lm_loss(p, cfg, t)
    tokens = jnp.ones((2, 64), jnp.int32)
    step = jax.grad(loss)
    jaxpr = jax.make_jaxpr(step)(params, tokens).jaxpr
    (body,) = _head_scans(jaxpr)
    assert _products(body) == 3
    under_head = [(eqn, inside) for eqn, inside in _eqns(jaxpr)
                  if "bps.head" in str(eqn.source_info.name_stack)]
    assert under_head
    assert not [eqn for eqn, inside in under_head
                if "checkpoint" in inside or eqn.primitive.name == "checkpoint"]
    lowered = jax.jit(step).lower(params, tokens).as_text(debug_info=True)
    named = [line for line in lowered.splitlines() if "bps.head" in line]
    assert named and not [l for l in named if "rematted_computation" in l]
    assert any("bps.head.grad" in l for l in named)


@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["alone", "differentiated"])
def test_no_value_the_size_of_all_positions_times_the_vocabulary(
        differentiated):
    """Nothing in either jaxpr holds the vocabulary at ``s`` positions or
    more: a chunk's ``[b, chunk, vocab]`` is the largest."""
    f, args = _head_alone()
    if differentiated:
        f = jax.grad(f, argnums=(0, 1))
    jaxpr = jax.make_jaxpr(f)(*args).jaxpr
    seen = 0
    for eqn, _ in _eqns(jaxpr):
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if VOCAB in shape:
                seen = max(seen, int(np.prod(shape)))
                assert np.prod(shape) < S * VOCAB, (eqn.primitive, shape)
    assert seen == max(B * 16 * VOCAB, VOCAB * HIDDEN)

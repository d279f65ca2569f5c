"""The embedding's backward by sorted ids (``transformer.embed_grad``,
``ops.grouped_matmul.embed_dw``): the grouped form in Pallas' interpreter
against a float64 ``segment_sum``, for the distributions of ids that the
static shapes must hold, and inside the models against the one-hot form.
CPU only: what the forms cost on the chip is PERF.md's (PR 42)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common import setup_record
from byteps_tpu.models import bert, decoder, gpt2, transformer
from byteps_tpu.ops import grouped_matmul as gm

TOKENS, HIDDEN = 600, 128       # 600: the last row tile is part pad rows


def _uniform(rng, vocab):
    return rng.randint(0, vocab, size=TOKENS)


def _one_id(rng, vocab):
    return np.full(TOKENS, vocab // 3)


def _last_block(rng, vocab):
    """Only ids of the block that hangs over the vocabulary's end."""
    return rng.randint((vocab - 1) // gm.EMBED_BLOCK * gm.EMBED_BLOCK, vocab,
                       size=TOKENS)


def _zipf(rng, vocab):
    """A Zipfian draw: the first block holds more than half the tokens."""
    ids = np.minimum(rng.zipf(1.3, size=TOKENS) - 1, vocab - 1)
    assert (ids < gm.EMBED_BLOCK).sum() > TOKENS // 2
    return ids


def _empty_between(rng, vocab):
    """Two full runs and nothing between them: the third sixth of the
    vocabulary (a block of its own where there are several) stays empty."""
    low = rng.randint(0, vocab // 6, size=TOKENS // 2)
    high = rng.randint(vocab - vocab // 6, vocab, size=TOKENS - TOKENS // 2)
    return rng.permutation(np.concatenate([low, high]))


DRAWS = {"uniform": _uniform, "one_id": _one_id, "last_block": _last_block,
         "zipf": _zipf, "empty_between": _empty_between}


def _segment_sum64(ids, ct, vocab, scale):
    """The sum in float64, on the host (x64 is off in these tests)."""
    want = np.zeros((vocab, ct.shape[1]), np.float64)
    np.add.at(want, ids, np.asarray(ct.astype(jnp.float32), np.float64))
    return want * (1.0 if scale is None else scale)


@pytest.mark.parametrize("scale", [None, 2048 ** 0.5], ids=["plain", "scaled"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
@pytest.mark.parametrize("vocab", [25024, 50257, 512])
@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_the_grouped_form_is_the_sum_by_id(draw, vocab, dtype, scale):
    """Every block of the table's gradient is written (an empty one as
    zeros), a block's run may be all the tokens or none, the last block
    hangs over a vocabulary that is no multiple of 256, and the rows past
    the tokens in the last row tile add nothing. The sum of a row's
    tokens is float32's: exact for bf16 rows up to the one rounding of
    the scale."""
    rng = np.random.RandomState(len(draw) + vocab)
    ids = DRAWS[draw](rng, vocab).astype(np.int32)
    ct = jnp.asarray(rng.randn(TOKENS, HIDDEN), dtype)
    got = transformer.embed_grad(jnp.asarray(ids), ct, vocab, scale,
                                 impl="kernels_interpret")
    assert got.shape == (vocab, HIDDEN) and got.dtype == jnp.float32
    want = _segment_sum64(ids, ct, vocab, scale)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())
    hit = np.zeros(vocab, bool)
    hit[ids] = True
    assert not np.asarray(got)[~hit].any()      # zeros, not small numbers


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_the_same_bits_on_two_runs_and_from_both_forms_rows(dtype):
    """A stable sort: a row's tokens are summed in one order every run.
    With rows whose sums float32 holds exactly, the one-hot form gives
    the same bits too."""
    rng = np.random.RandomState(3)
    vocab = 1500
    ids = jnp.asarray(rng.randint(0, vocab, size=TOKENS), jnp.int32)
    ct = jnp.asarray(rng.randint(-64, 64, size=(TOKENS, HIDDEN)), dtype)
    grouped = functools.partial(transformer.embed_grad, vocab=vocab,
                                scale=3.0, impl="kernels_interpret")
    first, second = np.asarray(grouped(ids, ct)), np.asarray(grouped(ids, ct))
    assert (first == second).all()
    onehot = transformer.embed_grad(ids, ct, vocab, 3.0, impl="xla")
    assert (first == np.asarray(onehot)).all()


@pytest.mark.parametrize("stray", [-1, 1500, np.iinfo(np.int32).max],
                         ids=["below", "past_the_end", "the_pads_own"])
def test_a_nan_in_a_row_no_token_maps_to_changes_nothing(stray):
    """Rows of the sorted buffer that stand for no token of the vocabulary
    (the pad rows behind the tokens, a token whose id is outside it) are
    zeros by the gather's fill, never a product with a zero of the
    one-hot: a NaN there reaches no row of the gradient."""
    rng = np.random.RandomState(4)
    vocab = 1500
    ids = rng.randint(0, vocab, size=TOKENS).astype(np.int32)
    ct = rng.randn(TOKENS, HIDDEN).astype(np.float32)
    lost = rng.rand(TOKENS) < 0.2
    poisoned = np.where(lost[:, None], np.nan, ct)
    got = transformer.embed_grad(
        jnp.asarray(np.where(lost, stray, ids)),
        jnp.asarray(poisoned, jnp.bfloat16), vocab,
        impl="kernels_interpret")
    want = transformer.embed_grad(
        jnp.asarray(ids[~lost]), jnp.asarray(ct[~lost], jnp.bfloat16), vocab,
        impl="kernels_interpret")
    assert np.isfinite(np.asarray(got)).all()
    assert (np.asarray(got) == np.asarray(want)).all()


# ------------------------------------------------------- inside the models

def _model_grads(model):
    if model in ("afmoe_tiny", "nemotron_h_tiny"):
        cfg = getattr(decoder, model)()
        params = decoder.init_params(jax.random.PRNGKey(0), cfg)
        apply = decoder.apply
    else:
        cfg = {"bert_tiny": bert.bert_tiny, "gpt2_tiny": gpt2.gpt2_tiny}[
            model]()
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        apply = transformer.apply
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    weight = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 1))

    def loss(params):
        return (apply(params, cfg, tokens).astype(jnp.float32) * weight).sum()

    return jax.grad(loss)(params)


@pytest.mark.parametrize("model", ["afmoe_tiny", "nemotron_h_tiny",
                                   "bert_tiny", "gpt2_tiny"])
def test_a_models_gradient_by_the_grouped_form_is_the_one_hot_forms(
        monkeypatch, model):
    """``jax.grad`` through ``decoder.apply`` (afmoe scales its embedding
    by sqrt(hidden)) and ``transformer.apply``: every leaf, the table's
    among them, to float32 rounding."""
    want = _model_grads(model)
    form = transformer.embed_grad
    monkeypatch.setattr(transformer, "embed_grad", functools.partial(
        form, impl="kernels_interpret"))
    rec = setup_record.open_record()
    try:
        got = _model_grads(model)
    finally:
        setup_record.close(rec)
    assert rec["choices"]["embed_bwd", "kernels"] == 1
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-6,
                                   atol=2e-6 * float(jnp.abs(w).max()))


def test_the_tied_heads_gradient_to_the_table_is_an_op_of_its_own():
    """``transformer.logits`` hands the table through ``_own_gradient``:
    the head's product for the table's gradient stands behind an
    optimization barrier, so XLA cannot fuse it into the sum with the
    embedding's gradient, which exists only after the whole backward (the
    product's operands would be held until then: PERF.md, PR 42). The
    values are untouched."""
    cfg = bert.bert_tiny()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.hidden))

    def loss(params, through):
        table = params["embed"]["tok"].astype(cfg.dtype)
        return jnp.einsum("bsh,vh->bsv", hidden.astype(cfg.dtype),
                          through(table),
                          preferred_element_type=jnp.float32).sum()

    def head(params):
        return transformer.logits(params, cfg, hidden).sum()

    assert "optimization_barrier" in str(jax.make_jaxpr(jax.grad(head))(
        params))
    got = jax.grad(head)(params)["embed"]["tok"]
    want = jax.grad(loss)(params, lambda t: t)["embed"]["tok"]
    assert (np.asarray(got) == np.asarray(want)).all()


# ------------------------------------------------- the choice and the names

def _backward_jaxpr(hidden=128, dtype=jnp.bfloat16):
    table = jnp.zeros((1000, hidden), jnp.float32)
    tokens = jnp.zeros((2, 256), jnp.int32)

    def loss(table):
        return transformer.embed_lookup(table, tokens, dtype, 2.0).astype(
            jnp.float32).sum()

    return str(jax.make_jaxpr(jax.grad(loss))(table))


def test_the_site_follows_the_platform_and_is_counted_once_a_trace(
        monkeypatch):
    """No argument, config field or environment variable: the grouped form
    on a TPU where the hidden size is whole lane tiles, the one-hot
    product elsewhere. Here, on the CPU, that is an XLA form and no
    fall-back; on a TPU off the lane tile it is one, said once."""
    warned = []
    monkeypatch.setattr(setup_record, "_warned", set())
    monkeypatch.setattr(setup_record.get_logger(), "warning",
                        lambda *a: warned.append(a))
    rec = setup_record.open_record()
    try:
        assert "pallas_call" not in _backward_jaxpr()
        assert dict(rec["choices"]) == {("embed_bwd", "xla"): 1}
        assert "xla" in setup_record.XLA_FORMS
        assert not rec["fallbacks"] and not warned
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert "name=bps_embed_dw" in _backward_jaxpr()
        assert not rec["fallbacks"] and not warned
        for _ in range(2):
            assert "pallas_call" not in _backward_jaxpr(hidden=96)
    finally:
        setup_record.close(rec)
    assert dict(rec["choices"]) == {("embed_bwd", "xla"): 3,
                                    ("embed_bwd", "kernels"): 1}
    (key, count), = rec["fallbacks"].items()
    assert key[:2] == ("embed_bwd", "xla") and count == 2
    assert len(warned) == 1 and "falls back" in warned[0][0]


def test_no_kernel_of_the_embeddings_path_is_counted_with_the_experts(
        monkeypatch):
    """``benchmark/trace/named.py`` gathers every kernel named ``bps_gmm*``
    for the routed layers' time and roofline share, against a count of
    calls a step that holds the experts' alone."""
    from benchmark.trace.named import GMM_PREFIX
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for dtype in (jnp.bfloat16, jnp.float32):
        names = set(re.findall(r"name=(\w+)", _backward_jaxpr(dtype=dtype)))
        kernels = {n for n in names if n.startswith("bps_")}
        assert kernels == {"bps_embed_dw"}
        assert not any(n.startswith(GMM_PREFIX) for n in kernels)

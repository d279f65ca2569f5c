"""The ``qwen3_next`` family on the CPU at a small size, seeded weights:
the decoder's two new kinds of layer (``models/decoder.py``,
``models/gated_delta_net.py``) against the benchmark's plain reference
(``benchmark/reference/qwen3_next_share.py``), whose delta rule goes one
position at a time; the zero-centred norm under weight decay; rotary
positions on a quarter of a head; softmax scores over all the router's
outputs and the shared expert's gate; the convolution without a bias and
the norm-then-gate kernels in the interpreter; the flash dispatcher at a
head of 256 over grouped kv heads; and THE TEST THAT TIES THE SHARE TO THE
MODEL: the routed parts of the 32 shares, with the mixer and the gated
shared expert counted once, add up to the uncut reference's layer."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import decoder, gated_delta_net as gdn, mamba2, moe
from byteps_tpu.ops import flash_attention as fa
from byteps_tpu.ops import mamba2_kernels as K
from byteps_tpu.parallel.mesh import make_mesh
from byteps_tpu.training import DistributedTrainer

from benchmark.reference import qwen3_next_share as ref

KINDS = ["gdn_moe", "gdn_moe", "gdn_moe", "gattn_moe"]
SIZES = dict(vocab_size=128, hidden=64, heads=4, kv_heads=2, head_dim=16,
             rotary_dim=4, moe_dim=24, shared_dim=24, top_k=3,
             router_outputs=8, held=[0, 1, 2, 3], gdn_key_heads=2,
             gdn_value_heads=4, gdn_head_dim=8, conv_kernel=4, chunk=16,
             route_scale=1.0, max_seq=64, rope_theta=1e7, norm_eps=1e-6,
             layer_kinds=KINDS)


def _tokens(rows=2, seq=32, seed=0):
    return np.random.default_rng(seed).integers(1, 128, size=(rows, seq),
                                                dtype=np.int32)


def _config(sizes=SIZES, **kw):
    kw = {"dtype": "float32", "routed_kw": {"row_tile": 8}, **kw}
    return decoder.qwen3_next_config(**sizes, **kw)


def _moved(params, seed=1, by=0.1):
    """The seeded tree with every vector leaf moved off its start, so that
    a norm read as ``w`` where it is ``1 + w`` (or the other way) shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree_util.tree_map(
        lambda x: x + by * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 else x, params)


def _reference(params, tokens, sizes, precision="float32"):
    toks, targets = ref.targets_of(tokens, "lm")
    shape = (tokens.shape[0], 1, tokens.shape[1])
    return ref.loss_and_grads(params, toks.reshape(shape),
                              targets.reshape(shape),
                              dict(ref._static(sizes)), precision)


# ------------------------------------------- program against reference

@pytest.mark.parametrize("change", [
    {}, {"held": [1, 6], "top_k": 5}, {"chunk": 32, "rotary_dim": 8},
    {"routed_kw": {"impl": "gmm_interpret", "row_tile": 128}},
    {"balanced": True},
], ids=["four_of_eight_held", "two_of_eight_held", "chunk_32_half_rotary",
        "routed_kernels", "balanced_choice"])
def test_loss_and_every_leaf_gradient_match_the_reference(change):
    sizes = {**SIZES, **change}
    routed_kw = sizes.pop("routed_kw", {"row_tile": 8})
    params = _moved(ref.make_params(3, sizes))
    cfg = _config(sizes, lm_head_chunk=16, routed_kw=routed_kw)
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
        # a head's gradient of its decay rate is a sum over the positions
        # of terms that cancel, all but zero where the head forgets at
        # once: a thousandth of the leaf's largest is rounding there
        loose = name.endswith(("A_log", "dt_bias"))
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=(1e-3 if loose else 3e-5) * scale,
                                   rtol=1e-4, err_msg=name)


def test_the_programs_own_init_is_the_references_tree():
    cfg = _config()
    mine = decoder.init_params(jax.random.PRNGKey(0), cfg)
    theirs = ref.make_params(0, SIZES)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(
        theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32
    for tree in (mine, theirs):
        first, full = tree["layers"][0], tree["layers"][3]
        # zero-centred norms start at zero, the delta rule's own at one
        for leaf in (tree["final_norm"], first["attn"]["norm"],
                     first["ffn"]["norm"], full["attn"]["q_norm"],
                     full["attn"]["k_norm"]):
            assert float(jnp.abs(leaf).max()) == 0.0
        assert float(jnp.abs(first["attn"]["gdn_norm"] - 1).max()) == 0.0
        assert float(jnp.abs(first["attn"]["dt_bias"] - 1).max()) == 0.0
        rate = jnp.exp(first["attn"]["A_log"])
        assert 0 < float(rate.min()) and float(rate.max()) <= 16
        assert float(jnp.abs(first["attn"]["conv_w"]).max()) <= 0.5
        assert "conv_b" not in first["attn"]
        assert first["ffn"]["shared_gate"].shape == (64, 1)
    tiny = decoder.qwen3_next_tiny()
    assert tiny.layer_kinds == tuple(KINDS) and tiny.zero_centred
    assert (tiny.gdn.key_dim, tiny.gdn.value_dim, tiny.gdn.conv_dim) == (
        16, 32, 64)
    assert tiny.routed.score == "softmax" and not tiny.scale_embedding


@pytest.mark.parametrize("kind", ["gdn_moe", "gattn_moe"])
def test_each_kind_of_layer_is_the_references(kind):
    sizes = dict(SIZES, layer_kinds=[kind])
    blk = _moved(ref.make_params(5, sizes))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    got = decoder._layer(x, blk, _config(sizes), kind)
    want = ref.layer(x, blk, dict(ref._static(sizes)), kind)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=2e-6)
    # the mixer alone, against the reference's position-by-position rule
    if kind == "gdn_moe":
        cfg = _config(sizes)
        a = decoder._norm(x, blk["attn"]["norm"], cfg)
        dot = functools.partial(ref._dot, "float32")
        np.testing.assert_allclose(
            np.asarray(gdn.mixer(a, blk["attn"], cfg.gdn, cfg.norm_eps)),
            np.asarray(ref._gdn(a, blk["attn"], dict(ref._static(sizes)),
                                dot, "float32")), rtol=1e-4, atol=2e-6)


def test_lower_precisions_of_the_reference_differ_and_keep_the_choice():
    """bfloat16 and float8 move the loss (the projections, the delta
    rule's operands, the scores and the experts are among what they
    round); the router's scores stay float32 in each."""
    params = ref.make_params(5, SIZES)
    tokens = _tokens()
    loss = {p: float(_reference(params, tokens, SIZES, p)[0])
            for p in ("float32", "bfloat16", "float8")}
    assert 0 < abs(loss["bfloat16"] - loss["float32"]) < abs(
        loss["float8"] - loss["float32"]) < 0.05 * loss["float32"]


def test_distributed_trainer_trains_it_as_the_reference_does():
    """Three AdamW steps of the unchanged trainer on two devices, every
    layer checkpointed, against the reference's ``train_steps``."""
    optimizer = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                     weight_decay=1e-4)
    params = ref.make_params(7, SIZES)
    cfg = _config(remat=True)
    batches = [_tokens(4, 32, seed=s) for s in range(3)]
    want = ref.train_steps(params, batches, SIZES, optimizer, "lm", 2)
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    trainer = DistributedTrainer(
        lambda p, b: decoder.causal_lm_loss(p, cfg, b), params,
        optax.adamw(**optimizer), mesh=mesh)
    losses = [float(trainer.step(b)) for b in batches]
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    change = np.asarray(ref.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, trainer.params, params)))
    # Adam divides a gradient by its own size: where a head's gradient of
    # its decay rate is all but zero (1e-9 beside eps 1e-8) its rounding
    # is the step's, so those leaves are held more loosely
    loose = np.array([n.endswith(("A_log", "dt_bias"))
                      for n in want["leaf_names"]])
    np.testing.assert_allclose(change[~loose], want["change_norm"][~loose],
                               rtol=2e-2)
    np.testing.assert_allclose(change[loose], want["change_norm"][loose],
                               rtol=2e-1)


def test_the_zero_centred_norm_decays_toward_a_scale_of_one():
    """The leaf is ``w``, the scale ``1 + w``: an AdamW step from ``w =
    0`` with a zero gradient leaves ``w = 0`` (a scale of one), where a
    leaf that WAS the scale would shrink from one toward zero."""
    cfg = _config()
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(1e-2, weight_decay=0.1)
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = tx.update(zero, tx.init(params), params)
    after = optax.apply_updates(params, updates)
    assert float(jnp.abs(after["final_norm"]).max()) == 0.0
    assert float(jnp.abs(after["layers"][3]["attn"]["q_norm"]).max()) == 0.0
    # the delta rule's own norm is a plain scale and does decay
    assert float(after["layers"][0]["attn"]["gdn_norm"].max()) < 1.0
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    np.testing.assert_allclose(
        np.asarray(decoder._norm(x, w, cfg)),
        np.asarray(decoder.rmsnorm(x, 1.0 + w, cfg.norm_eps)), rtol=1e-6)
    plain = decoder.afmoe_tiny()
    np.testing.assert_allclose(
        np.asarray(decoder._norm(x, w, plain)),
        np.asarray(decoder.rmsnorm(x, w, plain.norm_eps)), rtol=1e-6)


def test_rotary_positions_on_the_first_lanes_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3, 256))
    got = decoder.rope(x, 1e7, 64)
    # lanes 64.. are untouched, lanes 0..63 are rope on a 64-wide head
    np.testing.assert_array_equal(np.asarray(got[..., 64:]),
                                  np.asarray(x[..., 64:]))
    np.testing.assert_array_equal(np.asarray(got[..., :64]),
                                  np.asarray(decoder.rope(x[..., :64], 1e7)))
    assert float(jnp.abs(got[:, 1:, :, :64] - x[:, 1:, :, :64]).max()) > 0.1
    # lane i pairs with lane i + 32: position 1 rotates (x_0, x_32) by one
    # radian (the first frequency is 1)
    one = np.asarray(got[0, 1, 0]), np.asarray(x[0, 1, 0])
    np.testing.assert_allclose(
        one[0][[0, 32]],
        [one[1][0] * np.cos(1) - one[1][32] * np.sin(1),
         one[1][32] * np.cos(1) + one[1][0] * np.sin(1)], rtol=1e-5)
    # a width of 0 or of the whole head is the old call
    np.testing.assert_array_equal(np.asarray(decoder.rope(x, 1e4, 0)),
                                  np.asarray(decoder.rope(x, 1e4)))
    np.testing.assert_array_equal(np.asarray(decoder.rope(x, 1e4, 256)),
                                  np.asarray(decoder.rope(x, 1e4)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref._partial_rope(x, 1e7, 64)),
        rtol=1e-6, atol=1e-6)


# ------------------------------------------------------ the routed half

@pytest.mark.parametrize("balanced", [False, True],
                         ids=["by_score", "balanced"])
def test_softmax_weights_sum_to_one_over_all_ten(balanced):
    """The weights of a token's chosen experts are their softmax
    probabilities over ALL the router's outputs, divided by their sum:
    they add to one whether or not the experts are held here."""
    f = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    for held in ((0, 1), tuple(range(16))):
        cfg = moe.RoutedConfig(16, held, 10, score="softmax",
                               balanced=balanced)
        weights, experts = moe.route(f, router, cfg, sequences=2)
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0,
                                   rtol=1e-6)
        probs = jax.nn.softmax(f @ router, -1)
        top = jnp.take_along_axis(probs, experts, -1)
        np.testing.assert_allclose(
            np.asarray(weights), np.asarray(top / top.sum(-1, keepdims=True)),
            rtol=2e-5)
        if not balanced:
            np.testing.assert_array_equal(
                np.sort(np.asarray(experts), -1),
                np.sort(np.asarray(jax.lax.top_k(probs, 10)[1]), -1))
    with pytest.raises(ValueError, match="none of"):
        moe.RoutedConfig(16, (0,), 2, score="tanh")
    assert moe.RoutedConfig(16, (0,), 2).score == "sigmoid"


def test_the_shared_experts_gate_is_a_leaf_the_block_has_or_has_not():
    sizes = dict(SIZES, layer_kinds=["gdn_moe"])
    cfg = _config(sizes)
    blk = ref.make_params(2, sizes)["layers"][0]["ffn"]
    f = jax.random.normal(jax.random.PRNGKey(3), (32, 64))
    gated = moe.routed_ffn(f, blk, cfg.routed)
    bare = moe.routed_ffn(f, {k: v for k, v in blk.items()
                              if k != "shared_gate"}, cfg.routed)
    none = moe.routed_ffn(f, {k: v for k, v in blk.items()
                              if not k.startswith("shared")}, cfg.routed)
    shared = moe.gated_silu(f @ blk["shared"]["gate_up"]) @ blk["shared"][
        "down"]
    np.testing.assert_allclose(np.asarray(bare - none), np.asarray(shared),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(gated - none),
        np.asarray(jax.nn.sigmoid(f @ blk["shared_gate"]) * shared),
        atol=1e-6)


def test_the_32_shares_add_up_to_the_uncut_layer():
    """32 chips hold 1 of 32 experts each (the cell: 32 chips, 16 of 512,
    10 a token). Each computes the Gated DeltaNet mixer, the gated shared
    expert and its own expert's part; the routed parts of all shares with
    the mixer and the shared expert ONCE are the uncut reference's layer
    (the reference of the benchmark, given all 32)."""
    experts = 32
    sizes = dict(SIZES, router_outputs=experts, held=list(range(experts)),
                 top_k=10, balanced=True, layer_kinds=["gdn_moe"])
    whole = _moved(ref.make_params(11, sizes))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, sizes["hidden"]))
    uncut = ref.layer(x, whole, dict(ref._static(sizes)), "gdn_moe")

    def share(held, experts_of):
        blk = dict(whole, ffn=dict(whole["ffn"], experts=experts_of))
        return decoder._layer(x, blk, _config(dict(sizes, held=held)),
                              "gdn_moe"), blk

    # the mixer and the shared expert alone: a share of experts that add
    # nothing
    once, _ = share([0], jax.tree_util.tree_map(
        lambda w: jnp.zeros_like(w[:1]), whole["ffn"]["experts"]))
    total = once
    for chip in range(experts):
        mine, blk = share([chip], jax.tree_util.tree_map(
            lambda w: w[chip:chip + 1], whole["ffn"]["experts"]))
        if chip % 8 == 0:   # the reference, given the share, gives the part
            np.testing.assert_allclose(
                np.asarray(mine), np.asarray(ref.layer(
                    x, blk, dict(ref._static(dict(sizes, held=[chip]))),
                    "gdn_moe")), rtol=1e-4, atol=2e-6)
        total = total + (mine - once)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-4, atol=5e-6)


# ------------------------------------------------- the kernels it reuses

def test_the_convolution_without_a_bias_in_the_interpreter():
    """``bps_ssm_conv_fwd`` / ``_bwd`` with a row of zeros for the bias
    are ``silu(causal_conv(x, w))``, forward and both gradients."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 256)).astype(
        jnp.bfloat16)
    w = jax.random.uniform(jax.random.PRNGKey(1), (4, 256), jnp.float32,
                           -0.5, 0.5)
    zeros = jnp.zeros((256,), jnp.float32)

    def kernels(x, w):
        return K.conv_silu_kernels(x, w, zeros, 256, K.CONV_STRIP, True)

    def plain(x, w):
        return jax.nn.silu(mamba2.causal_conv(x, w)).astype(x.dtype)

    np.testing.assert_allclose(np.asarray(kernels(x, w), np.float32),
                               np.asarray(plain(x, w), np.float32),
                               atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(plain(x, w), np.float32),
        np.asarray(jax.nn.silu(mamba2.causal_conv(x, w, zeros)).astype(
            x.dtype), np.float32))
    loss = lambda fn: lambda x, w: fn(x, w).astype(jnp.float32).sum()  # noqa: E731
    for got, want in zip(jax.grad(loss(kernels), (0, 1))(x, w),
                         jax.grad(loss(plain), (0, 1))(x, w)):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=2e-2 * float(jnp.abs(want.astype(jnp.float32)).max()))
    # off the TPU the entry takes the XLA form
    np.testing.assert_array_equal(np.asarray(mamba2.conv_silu(x, w)),
                                  np.asarray(plain(x, w)))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_norm_then_gate_kernels_in_the_interpreter(dtype, tol):
    """``gate_first`` false: ``rmsnorm_head(y) * scale * silu(z)`` a head
    of 128 lanes, forward and the three gradients, against the XLA form;
    the Mamba-2 order is still what the default gives."""
    heads = 4
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 256, heads * 128)
                          ).astype(dtype)
    z = jax.random.normal(jax.random.PRNGKey(1), y.shape).astype(dtype)
    scale = jnp.tile(1 + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                 (128,)), heads)
    weight = jax.random.normal(jax.random.PRNGKey(3), y.shape)

    def kernels(y, z, scale):
        return K.gated_norm_kernels(y, z, scale, heads, 1e-6, 256,
                                    K.NORM_STRIP, True, False)

    def plain(y, z, scale):
        return mamba2.gated_norm(y, z, scale, heads, 1e-6, gate_first=False)

    def loss(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()

    f32 = jnp.float32
    want = mamba2.group_rmsnorm(y, scale, heads, 1e-6) * jax.nn.silu(
        z.astype(f32))
    np.testing.assert_allclose(np.asarray(plain(y, z, scale), f32),
                               np.asarray(want), atol=tol * 8)
    got = kernels(y, z, scale)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, f32), np.asarray(want),
                               atol=tol * float(jnp.abs(want).max()))
    for g, w in zip(jax.grad(loss(kernels), (0, 1, 2))(y, z, scale),
                    jax.grad(loss(plain), (0, 1, 2))(y, z, scale)):
        np.testing.assert_allclose(
            np.asarray(g, f32), np.asarray(w, f32),
            atol=tol * float(jnp.abs(w.astype(f32)).max()))
    first = K.gated_norm_kernels(y, z, scale, heads, 1e-6, 256,
                                 K.NORM_STRIP, True)
    np.testing.assert_allclose(
        np.asarray(first, f32),
        np.asarray(mamba2.gated_norm(y, z, scale, heads, 1e-6), f32),
        atol=tol * 8)
    assert float(jnp.abs(first.astype(f32) - got.astype(f32)).max()) > 0.1


def test_flash_kernels_at_a_head_of_256_over_grouped_kv_heads():
    """The cell's call at a small length: 8 query heads over 2 kv heads
    of 256 lanes, causal, forward and every gradient against
    ``local_attention``, in the interpreter; the dispatcher says the shape
    is the kernels'."""
    b, s, heads, kv, d = 1, 256, 8, 2, 256
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(k[0], (b, s, heads, d))
    kk = jax.random.normal(k[1], (b, s, kv, d))
    v = jax.random.normal(k[2], (b, s, kv, d))
    weight = jax.random.normal(k[3], (b, s, heads, d))
    assert fa.supported(q.shape, kk.shape, v.shape)
    assert fa.supported((2, 8192, 16, 256), (2, 8192, 2, 256))
    assert not fa.supported((2, 8192, 16, 320), (2, 8192, 2, 320))

    def flash(q, kk, v):
        return fa.flash_attention(q, kk, v, causal=True, block_q=128,
                                  block_k=128, interpret=True)

    def plain(q, kk, v):
        return fa.local_attention(q, kk, v, causal=True)

    np.testing.assert_allclose(np.asarray(flash(q, kk, v)),
                               np.asarray(plain(q, kk, v)), atol=2e-5,
                               rtol=1e-4)
    loss = lambda fn: lambda *a: (fn(*a) * weight).sum()  # noqa: E731
    for got, want in zip(jax.grad(loss(flash), (0, 1, 2))(q, kk, v),
                         jax.grad(loss(plain), (0, 1, 2))(q, kk, v)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want),
            atol=3e-5 * float(jnp.abs(want).max()), rtol=1e-4)


# ------------------------------------------------- names and refusals

def test_the_gated_delta_halfs_scopes():
    """``bps.gdn`` holds the Gated DeltaNet half (its norm inside it)
    where ``bps.attn`` holds the full-attention half; ``bps.gdn.proj``,
    ``.conv``, ``.scan`` and ``.norm`` inside it, forward and backward;
    the chunked rule under ``bps_gdn_xla`` inside ``bps.gdn.scan``; the
    routed half in ``bps.mlp``, its shared expert's gate in
    ``bps.moe.shared``."""
    cfg = decoder.qwen3_next_tiny(remat=True)
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    trainer = DistributedTrainer(
        lambda p, b: decoder.causal_lm_loss(p, cfg, b), params,
        optax.adamw(1e-3), mesh=mesh)
    text = trainer._step_fn.lower(
        trainer.params, trainer.opt_state,
        jnp.asarray(_tokens())).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))

    def some(pattern):
        return any(re.search(pattern, p) for p in paths)

    for part in ("proj", "conv", "scan", "norm"):
        assert some(rf"bps\.model/jvp\(bps\.gdn\)/bps\.gdn\.{part}/"), part
        assert some(rf"bps\.model/transpose\(.*bps\.gdn/bps\.gdn\.{part}/"
                    ), part
    assert some(r"bps\.gdn\.scan/bps_gdn_xla/")
    assert not some(r"bps\.gdn\.(proj|conv|norm)/.*bps_gdn_xla")
    assert some(r"jvp\(bps\.attn\)/.*bps_attn_xla") and not some(
        r"bps\.gdn.*bps_attn_xla")
    # the gate's sigmoid: an ``exp`` of the shared scope's own (the
    # expert's SiLU is one level down, under ``jit(silu)``)
    assert some(r"jvp\(bps\.mlp\)/bps\.moe/bps\.moe\.shared/exp$")
    assert not some(r"bps\.mlp/.*bps\.gdn") and not some(
        r"bps\.gdn.*bps\.moe")


def test_what_a_configuration_refuses():
    with pytest.raises(ValueError, match="needs `gdn`"):
        decoder.afmoe_config(**dict(
            vocab_size=128, hidden=64, heads=4, kv_heads=2, head_dim=16,
            mlp_dim=96, moe_dim=32, window=8, top_k=2, router_outputs=8,
            held=(0, 1), layer_kinds=("gdn_moe",)))
    with pytest.raises(ValueError, match="rotary lanes"):
        _config(dict(SIZES, rotary_dim=5))
    with pytest.raises(ValueError, match="rotary lanes"):
        _config(dict(SIZES, rotary_dim=32))
    with pytest.raises(ValueError, match="value heads over"):
        _config(dict(SIZES, gdn_key_heads=3))
    with pytest.raises(ValueError, match="none of .*gdn_moe.*gattn_moe"):
        _config(dict(SIZES, layer_kinds=["gdn", "gattn_moe"]))
    assert set(decoder.GATED) == {"gdn_moe", "gattn_moe"} <= set(
        decoder.KINDS)

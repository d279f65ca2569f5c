"""Latent attention (``models/mla.py``, the ``deepseek_v3`` kinds of
``models/decoder.py``) against its plain reference
(``benchmark/reference/deepseek_v3_share.py``) on seeded random weights:
the loss and EVERY leaf's gradient; the flash kernels in the interpreter
with v at a width of its own (q·k 48 over 32 and 192 over 128: forward,
dq, dk, dv against ``local_attention``), with and without the shared
rotary head; the rotary pairing against a direct complex rotation; and
THE TEST THAT TIES THE SHARE TO THE MODEL: the eight shares' routed
parts, with the attention and the shared expert counted once, add up to
the uncut reference's layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import decoder, mla
from byteps_tpu.ops import flash_attention as fa
from byteps_tpu.parallel.mesh import make_mesh
from byteps_tpu.training import DistributedTrainer

from benchmark.reference import deepseek_v3_share as ref

SIZES = dict(vocab_size=128, hidden=64, heads=4, kv_lora_rank=32,
             qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, mlp_dim=96,
             moe_dim=24, top_k=2, router_outputs=8, held=[0, 1, 2, 3],
             shared_experts=2, route_scale=2.5, max_seq=64, rope_theta=1e6,
             norm_eps=1e-6, layer_kinds=["mla_dense", "mla_moe", "mla_moe"])


def _tokens(rows=2, seq=32, seed=0):
    return np.random.default_rng(seed).integers(1, 128, size=(rows, seq),
                                                dtype=np.int32)


def _reference(params, tokens, sizes, precision="float32"):
    toks, targets = ref.targets_of(tokens, "lm")
    shape = (tokens.shape[0], 1, tokens.shape[1])
    return ref.loss_and_grads(params, toks.reshape(shape),
                              targets.reshape(shape),
                              dict(ref._static(sizes)), precision)


# ------------------------------------------- program against reference

@pytest.mark.parametrize("change", [
    {}, {"held": [1, 6], "top_k": 4},
    {"qk_rope_dim": 16, "v_head_dim": 8, "kv_lora_rank": 24},
    {"shared_experts": 0,
     "routed_kw": {"impl": "gmm_interpret", "row_tile": 128}},
    {"balanced": True},
], ids=["four_of_eight_held", "two_of_eight_held", "other_widths",
        "kernels_no_shared", "balanced_choice"])
def test_loss_and_every_leaf_gradient_match_the_reference(change):
    sizes = {**SIZES, **change}
    routed_kw = sizes.pop("routed_kw", {"row_tile": 8})
    params = ref.make_params(3, sizes)
    cfg = decoder.deepseek_v3_config(**sizes, dtype="float32",
                                     lm_head_chunk=16, routed_kw=routed_kw)
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
    cfg = decoder.deepseek_v3_config(**SIZES)
    mine = decoder.init_params(jax.random.PRNGKey(0), cfg)
    theirs = ref.make_params(0, SIZES)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(
        theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32
    tiny = decoder.deepseek_v3_tiny()
    assert (tiny.mla.qk_dim, tiny.mla.v_dim, tiny.head_dim) == (24, 16, 24)
    assert not tiny.scale_embedding and tiny.kv_heads == tiny.heads


def test_lower_precisions_of_the_reference_differ_and_keep_the_choice():
    """bfloat16 and float8 move the loss (the scores, the latent's two
    projections and the experts are among the products they round); the
    router's scores stay float32 in each."""
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
    cfg = decoder.deepseek_v3_config(**SIZES, dtype="float32", remat=True,
                                     routed_kw={"row_tile": 8})
    batches = [_tokens(4, 32, seed=s) for s in range(3)]
    want = ref.train_steps(params, batches, SIZES, optimizer, "lm", 2)
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


def test_the_latent_halfs_scopes_nest_inside_the_attention_half():
    """``bps.attn.mla`` holds the half inside ``bps.attn``, forward and
    backward; ``bps.attn.mla.latent`` all of it but the q and o
    projections and the attention itself (``bps_attn_xla`` on the CPU);
    the routed half lies in ``bps.mlp`` as afmoe's does."""
    import re
    cfg = decoder.deepseek_v3_tiny(remat=True)
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

    assert some(r"bps\.model/jvp\(bps\.attn\)/bps\.attn\.mla/"
                r"bps\.attn\.mla\.latent/")
    assert some(r"bps\.model/transpose\(.*bps\.attn/bps\.attn\.mla/"
                r"bps\.attn\.mla\.latent/")
    assert some(r"bps\.attn\.mla/bps_attn_xla") and not some(
        r"bps\.attn\.mla\.latent/.*bps_attn_xla")
    assert some(r"jvp\(bps\.mlp\)/bps\.moe/bps\.moe\.experts")
    assert not some(r"bps\.mlp/.*bps\.attn\.mla") and not some(
        r"bps\.attn\.mla.*bps\.moe")


def test_what_a_configuration_refuses():
    with pytest.raises(ValueError, match="needs `mla`"):
        decoder.afmoe_config(
            vocab_size=8, hidden=8, heads=2, kv_heads=2, head_dim=4,
            mlp_dim=8, moe_dim=8, layer_kinds=["mla_dense"], window=4,
            top_k=1, router_outputs=2, held=[0])
    with pytest.raises(ValueError, match="do not pair"):
        decoder.deepseek_v3_config(**{**SIZES, "qk_rope_dim": 7})
    with pytest.raises(ValueError, match="none of"):
        decoder.deepseek_v3_config(**{**SIZES, "layer_kinds": ["mla"]})


# ------------------------------------- the kernels, v at its own width

def _qkv(widths, shared, seq, heads=2, kv_heads=2, seed=0):
    """q, k, v and a cotangent; with ``shared`` the last third of k's
    lanes is ONE head repeated to every head (the rotary key), returned
    apart so that its gradient can be asked for."""
    d, vd = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (1, seq, heads, d))
    k = jax.random.normal(ks[1], (1, seq, kv_heads, d))
    v = jax.random.normal(ks[2], (1, seq, kv_heads, vd))
    g = jax.random.normal(ks[3], (1, seq, heads, vd))
    one = jax.random.normal(ks[4], (1, seq, 1, d // 3)) if shared else None
    return q, k, v, g, one


def _assemble(k, one):
    if one is None:
        return k
    nope = k.shape[-1] - one.shape[-1]
    return jnp.concatenate([k[..., :nope], jnp.broadcast_to(
        one, (*k.shape[:3], one.shape[-1]))], -1)


@pytest.mark.parametrize("shared", [False, True],
                         ids=["a_key_a_head", "shared_rotary_head"])
@pytest.mark.parametrize("widths", [(48, 32), (192, 128)],
                         ids=["48_over_32", "192_over_128"])
@pytest.mark.parametrize("seq,blocks", [(256, 128), (128, None)],
                         ids=["online_and_split", "one_block_pair"])
def test_flash_kernels_with_a_value_width_of_its_own(widths, shared, seq,
                                                     blocks):
    """Forward, dq, dk and dv of the interpreted kernels against
    ``local_attention`` under the causal triangle: the online forward
    with the split backward (blocks of 128 over 256 positions) and the
    one-block forward with the fused backward."""
    q, k, v, g, one = _qkv(widths, shared, seq)

    def loss(attn, q, k, v, one):
        return jnp.sum(attn(q, _assemble(k, one), v) * g)

    flash = functools.partial(fa.flash_attention, causal=True,
                              interpret=True, block_q=blocks, block_k=blocks)
    plain = functools.partial(fa.local_attention, causal=True)
    out = flash(q, _assemble(k, one), v)
    assert out.shape == (1, seq, 2, widths[1])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(plain(q, _assemble(k, one), v)),
        atol=2e-5, rtol=1e-4)
    wrt = (0, 1, 2, 3) if shared else (0, 1, 2)
    got = jax.grad(functools.partial(loss, flash), wrt)(q, k, v, one)
    want = jax.grad(functools.partial(loss, plain), wrt)(q, k, v, one)
    assert [x.shape for x in got[:3]] == [q.shape, k.shape, v.shape]
    for name, a, b in zip(("dq", "dk", "dv", "d_shared"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=name)


def test_grouped_kv_heads_and_a_window_take_a_value_width_too():
    q, _, _, g, _ = _qkv((48, 32), False, 256, heads=4)
    _, k, v, _, _ = _qkv((48, 32), False, 256, kv_heads=2, seed=1)

    def loss(attn, q, k, v):
        return jnp.sum(attn(q, k, v, causal=True, window=96) * g)

    flash = functools.partial(fa.flash_attention, interpret=True,
                              block_q=64, block_k=64)
    got = jax.grad(functools.partial(loss, flash), (0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, fa.local_attention),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


def test_supported_and_the_dispatcher_tell_the_truth_about_two_widths():
    assert fa.supported((2, 8192, 32, 192), (2, 8192, 32, 192),
                        (2, 8192, 32, 128))
    assert not fa.supported((2, 256, 2, 128), (2, 256, 2, 128),
                            (2, 256, 2, 384))
    assert not fa.supported((2, 8192, 32, 320), (2, 8192, 32, 320),
                            (2, 8192, 32, 128))
    q, k, v, _, _ = _qkv((48, 32), False, 128)
    out = fa.attention(q, k, v, causal=True)        # the CPU's path
    assert out.shape == (1, 128, 2, 32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fa.attention(q, k, v, causal=True,
                                                 impl="naive")), atol=1e-6)
    with pytest.raises(ValueError, match="share their last width"):
        fa.flash_attention(q, k[..., :32], v, interpret=True)
    with pytest.raises(ValueError, match="all but it"):
        fa.flash_attention(q, k, v[:, :64], interpret=True)


def test_a_call_with_two_widths_takes_blocks_of_1024_by_default():
    """Measured on the chip at 192 over 128 (``_default_block``), at any
    length; an equal-width call takes them past ``_WHOLE_KV`` keys alone
    (PR 45), and a named block is kept as named."""
    assert fa._default_block(1024, 128, 128) == 512
    assert fa._default_block(1024, 64, 64) == 512
    assert fa._default_block(1024, 192, 128) == 1024
    assert fa._default_block(8192, 192, 128) == fa._default_block(
        8192, 128, 128) == 1024
    q = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16)
    assert fa._resolve(q, q, None, None, None, block=1024) == (
        192 ** -0.5, 1024, 1024)
    assert fa._resolve(q, q, None, 256, None, block=1024)[1:] == (256, 1024)
    assert fa._resolve(q, q, None, None, None)[1:] == (512, 512)


# ----------------------------------------------------- rotary pairing

def test_rotary_pairing_is_a_complex_rotation_of_the_pairs():
    """``rope_interleave``: lanes (2i, 2i + 1) are one complex number,
    turned by ``pos * theta^(-2i/r)``. The program permutes the WEIGHTS'
    rotary columns to paired halves and rotates the halves; q·k is what
    the direct rotation of the pairs gives."""
    r, seq, theta = 8, 16, 1e6
    rng = np.random.RandomState(0)
    a = rng.randn(1, seq, 12)
    wq, wk = rng.randn(12, 2, 4 + r), rng.randn(12, 1, 4 + r)
    q = np.einsum("bsh,hnd->bsnd", a, wq)[..., 4:]          # [1, s, 2, r]
    k = np.einsum("bsh,hnd->bsnd", a, wk)[..., 4:]          # [1, s, 1, r]
    turn = np.exp(1j * np.arange(seq)[:, None]
                  * theta ** (-2.0 * np.arange(r // 2) / r))  # [s, r/2]

    def direct(x):
        z = (x[..., 0::2] + 1j * x[..., 1::2]) * turn[None, :, None, :]
        return z

    want = np.einsum("bqnc,bknc->bnqk", direct(q),
                     np.conj(np.broadcast_to(direct(k), direct(q).shape))).real

    def program(w):
        x = jnp.einsum("bsh,hnd->bsnd", jnp.asarray(a, jnp.float32),
                       mla.paired_halves(jnp.asarray(w, jnp.float32), r))
        np.testing.assert_allclose(       # the lanes before: as they were
            np.asarray(x[..., :4]),
            np.einsum("bsh,hnd->bsnd", a, w)[..., :4], rtol=1e-5, atol=1e-5)
        return decoder.rope(x[..., 4:], theta)

    got = jnp.einsum("bqnc,bknc->bnqk", program(wq),
                     jnp.broadcast_to(program(wk), (1, seq, 2, r)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    # and the reference rotates the pairs where they lie
    np.testing.assert_allclose(
        np.asarray(ref._rope_pairs(jnp.asarray(q, jnp.float32), theta)),
        np.stack([direct(q).real, direct(q).imag], -1).reshape(q.shape),
        rtol=1e-4, atol=1e-4)


# ------------------------------------------------------ the share test

@pytest.mark.parametrize("balanced", [False, True],
                         ids=["by_score", "balanced"])
def test_the_eight_shares_add_up_to_the_uncut_layer(balanced):
    """8 chips hold 1 of 8 experts each (the cell: 8 chips, 16 of 128).
    Each computes the latent attention, the shared experts and its own
    expert's part; the routed parts of all shares with the attention and
    the shared experts ONCE are the uncut reference's layer (the
    reference of the benchmark, given all 8)."""
    experts = 8
    sizes = dict(SIZES, held=list(range(experts)), top_k=3,
                 balanced=balanced, layer_kinds=["mla_moe"])
    whole = ref.make_params(11, sizes)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, sizes["hidden"]))
    dot = functools.partial(ref._dot, "float32")
    uncut = ref.layer(x, whole, dict(ref._static(sizes)), "mla_moe", dot)

    def share(held, experts_of):
        cfg = decoder.deepseek_v3_config(
            **dict(sizes, held=held), dtype="float32",
            routed_kw={"row_tile": 8})
        blk = dict(whole, ffn=dict(whole["ffn"], experts=experts_of))
        return decoder._layer(x, blk, cfg, "mla_moe"), blk

    # the attention and the shared experts alone: a share of experts that
    # add nothing
    once, _ = share([0], jax.tree_util.tree_map(
        lambda w: jnp.zeros_like(w[:1]), whole["ffn"]["experts"]))
    total = once
    for chip in range(experts):
        mine, blk = share([chip], jax.tree_util.tree_map(
            lambda w: w[chip:chip + 1], whole["ffn"]["experts"]))
        # the reference is given the same share and gives the same part
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(ref.layer(
                x, blk, dict(ref._static(dict(sizes, held=[chip]))),
                "mla_moe", dot)), rtol=1e-4, atol=2e-6)
        total = total + (mine - once)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-4, atol=5e-6)

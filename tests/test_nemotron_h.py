"""The ``nemotron_h`` family on the CPU at a small size, seeded weights:
the chunked state-space scan (``ops/ssd.py``) against the one-step
recurrence; the decoder of one-mixer layers (``models/decoder.py``,
``models/mamba2.py``) against the benchmark's plain reference
(``benchmark/reference/nemotron_h_share.py``); the routed layer whose
experts are ``relu(.)^2`` against a loop over experts; the grouped
products and the rows' movement in the interpreter at an expert width of
1.5 lane tiles; and THE TEST THAT TIES THE SHARE TO THE MODEL: the shares
of the routed experts, with the shared expert counted once, add up to the
uncut reference's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import decoder, moe
from byteps_tpu.ops import grouped_matmul as gm
from byteps_tpu.ops import routed_rows as rr
from byteps_tpu.ops.ssd import ssd, ssd_steps

from benchmark.reference import nemotron_h_share as ref

# ------------------------------------------------------------ the scan

CHUNK = 16


def _scan_inputs(seed, s, dtype=jnp.float32, bsz=2, heads=4, p=8, groups=2,
                 n=16):
    """Sizes of the seeded model's kind: steps of 0.02 to 0.7, ``A`` in
    [-16, -1], so that a chunk forgets some heads' state and keeps
    others'."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (bsz, s, heads, p)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (bsz, s, heads)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (heads,), maxval=2.77)),
            (0.3 * jax.random.normal(k[3], (bsz, s, groups, n))).astype(dtype),
            (0.3 * jax.random.normal(k[4], (bsz, s, groups, n))).astype(dtype),
            jnp.ones((heads,)))


# float32: the two forms differ by the order of their sums. bfloat16: the
# chunked form rounds the operands of its four products to 8 bits of
# mantissa (2^-8 = 0.4 % an operand), a sum of up to ``CHUNK`` such terms
# against a float32 recurrence over the same rounded inputs
@pytest.mark.parametrize("chunks", [1, 2, 5])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_chunked_scan_is_the_one_step_recurrence(chunks, dtype, tol):
    args = _scan_inputs(chunks, CHUNK * chunks, dtype)
    weight = jax.random.normal(jax.random.PRNGKey(9),
                               args[0].shape, jnp.float32)

    def loss(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()

    def chunked(*a):
        return ssd(*a, chunk=CHUNK)

    got, want = chunked(*args), ssd_steps(*args)
    assert got.dtype == dtype and got.shape == args[0].shape
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=tol * scale)
    every = tuple(range(6))
    for g, w in zip(jax.grad(loss(chunked), every)(*args),
                    jax.grad(loss(ssd_steps), every)(*args)):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=tol * float(jnp.abs(w.astype(jnp.float32)).max()))


def test_the_scan_takes_whole_chunks_and_is_safe_to_rematerialise():
    args = _scan_inputs(3, 2 * CHUNK)
    with pytest.raises(ValueError, match="chunks of"):
        ssd(*(a[:, :CHUNK + 1] if a.ndim > 1 else a for a in args),
            chunk=CHUNK)
    plain = jax.grad(lambda x: ssd(x, *args[1:], chunk=CHUNK).sum())(args[0])
    again = jax.grad(lambda x: jax.checkpoint(
        lambda x: ssd(x, *args[1:], chunk=CHUNK))(x).sum())(args[0])
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(again))


def test_a_long_forgetting_chunk_overflows_nothing():
    """Steps of 1 under A = -16: a chunk's total is -256 and exp of its
    negation is infinite in float32; the masked exponents never see it."""
    x, dt, a, b, c, d = _scan_inputs(4, 3 * CHUNK)
    dt, a = jnp.ones_like(dt), jnp.full_like(a, -16.0)
    grads = jax.grad(lambda *t: ssd(*t, chunk=CHUNK).sum(), (0, 1, 2, 3, 4))(
        x, dt, a, b, c, d)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    np.testing.assert_allclose(np.asarray(ssd(x, dt, a, b, c, d, chunk=CHUNK)),
                               np.asarray(ssd_steps(x, dt, a, b, c, d)),
                               atol=1e-5)


# --------------------------------------------------------- the decoder

def _sizes(cfg):
    """What the reference is given of a program's configuration."""
    return dict(
        vocab_size=cfg.vocab_size, hidden=cfg.hidden, heads=cfg.heads,
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim, moe_dim=cfg.moe_dim,
        shared_dim=cfg.routed.shared_dim, top_k=cfg.routed.top_k,
        router_outputs=cfg.routed.num_experts, held=list(cfg.routed.held),
        route_scale=cfg.routed.route_scale, balanced=cfg.routed.balanced,
        ssm_heads=cfg.ssm.heads, ssm_head_dim=cfg.ssm.head_dim,
        ssm_groups=cfg.ssm.groups, ssm_state=cfg.ssm.state,
        conv_kernel=cfg.ssm.conv_kernel, norm_eps=cfg.norm_eps,
        layer_kinds=list(cfg.layer_kinds))


OPT = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder.nemotron_h_tiny(balanced=True, lm_head_chunk=16)
    sizes = _sizes(cfg)
    rng = np.random.RandomState(5)
    batches = [rng.randint(1, cfg.vocab_size, (2, 32)).astype(np.int32)
               for _ in range(3)]
    return cfg, sizes, ref.make_params(11, sizes), batches


def test_the_seeded_tree_is_the_programs(tiny):
    cfg, sizes, params, _ = tiny
    mine = decoder.init_params(jax.random.PRNGKey(0), cfg)
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(params))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32
    ssm = params["layers"][0]
    step = jax.nn.softplus(ssm["dt_bias"])      # the published rule
    assert float(step.min()) >= 1e-3 * 0.999 and float(step.max()) <= 0.1001
    assert float(jnp.exp(ssm["A_log"]).min()) >= 1.0
    assert float(jnp.exp(ssm["A_log"]).max()) <= 16.0
    assert float(jnp.abs(ssm["conv_w"]).max()) <= 0.5
    np.testing.assert_array_equal(np.asarray(ssm["D"]), 1.0)
    step = jax.nn.softplus(mine["layers"][0]["dt_bias"])    # the program's
    assert float(step.min()) >= 1e-3 * 0.999 and float(step.max()) <= 0.1001


@pytest.mark.parametrize("kind", ["ssm", "attn", "moe"])
def test_each_kind_of_layer_is_the_references(tiny, kind):
    cfg, sizes, params, _ = tiny
    blk = params["layers"][cfg.layer_kinds.index(kind)]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.hidden))
    got = decoder._layer(x, blk, cfg, kind)
    want = ref.layer(x, blk, sizes, kind)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


def test_loss_gradients_and_three_steps_are_the_references(tiny):
    cfg, sizes, params, batches = tiny
    want = ref.train_steps(params, batches, sizes, OPT, "lm", 1)
    tx = optax.adamw(**OPT)
    state = tx.init(params)
    p, losses = params, []
    for i, batch in enumerate(batches):
        loss, g = jax.value_and_grad(decoder.causal_lm_loss)(
            p, cfg, jnp.asarray(batch))
        if i == 0:
            np.testing.assert_allclose(
                np.asarray(ref.leaf_norms(g)), want["grad_norm"], rtol=2e-4)
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, want["loss"], rtol=1e-5)
    change = ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, params))
    np.testing.assert_allclose(np.asarray(change), want["change_norm"],
                               rtol=2e-3)
    assert len(want["leaf_names"]) == len(want["grad_norm"])
    assert "layers.0.A_log" in want["leaf_names"]


def test_the_kinds_are_named_and_what_each_needs_is_asked_for():
    with pytest.raises(ValueError, match="one mixer one of"):
        decoder.nemotron_h_tiny(layer_kinds=("ssm", "mamba"))
    with pytest.raises(ValueError, match="needs `ssm`"):
        decoder.afmoe_tiny(layer_kinds=("ssm",))
    with pytest.raises(ValueError, match="experts' function"):
        moe.RoutedConfig(8, (0,), 2, act="gelu")
    cfg = decoder.nemotron_h_tiny()
    assert cfg.scale_embedding is False and cfg.routed.act == "relu2"
    assert decoder.afmoe_tiny().scale_embedding is True
    assert decoder.afmoe_tiny().routed.act == "gated_silu"


# ------------------------------------------------- relu2 routed layer

T, H, M, MS, E, K = 96, 256, 192, 320, 16, 4


def _relu2_layer(seed, held, shared=True):
    rng = np.random.RandomState(seed)
    normal = lambda *s: jnp.asarray(rng.randn(*s) * 0.05, jnp.float32)  # noqa: E731
    blk = {"router": normal(H, E),
           "experts": {"up": normal(len(held), H, M),
                       "down": normal(len(held), M, H)}}
    if shared:
        blk["shared"] = {"up": normal(H, MS), "down": normal(MS, H)}
    return blk, jnp.asarray(rng.randn(T, H), jnp.float32)


def _loop(f, blk, cfg):
    """Every held expert over every row, masked by the router's choice."""
    w, chosen = moe.route(f, blk["router"], cfg)
    out = moe.relu2(f @ blk["shared"]["up"]) @ blk["shared"]["down"]
    for g, e in enumerate(cfg.held):
        mine = jnp.where(chosen == e, w, 0.0).sum(-1)
        out = out + mine[:, None] * (
            moe.relu2(f @ blk["experts"]["up"][g]) @ blk["experts"]["down"][g])
    return out


@pytest.mark.parametrize("impl", ["ragged", "gmm_interpret"])
@pytest.mark.parametrize("held", [(0, 1, 2, 3), (5, 9)], ids=str)
def test_relu2_experts_off_the_lane_tile_match_a_loop_over_experts(held,
                                                                   impl):
    """An expert width of 1.5 lane tiles (192), a shared width of 2.5
    (320), a hidden size of 2 (a row of the take's source is padded to a
    whole (8, 128) tile): the kernels in the interpreter and XLA's path
    give the loop's value and every gradient."""
    cfg = moe.RoutedConfig(E, held, K, 2.5, row_tile=128, impl=impl,
                           act="relu2", shared_dim=MS)
    assert rr.resolve(impl, T, H, M, len(held), 128) == impl
    blk, f = _relu2_layer(0, held)

    def loss(fn):
        return lambda f, blk: jnp.sum(jnp.sin(fn(f, blk, cfg)))

    (a, ga), (b, gb) = (jax.value_and_grad(loss(fn), (0, 1))(f, blk)
                        for fn in (moe.routed_ffn, _loop))
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-3,
                                   atol=1e-5)


@pytest.mark.parametrize("k,n", [(256, 192), (192, 256), (192, 320)],
                         ids=str)
def test_grouped_products_at_a_width_of_one_and_a_half_lane_tiles(k, n):
    """The three kernels in the interpreter against ``lax.ragged_dot``
    where a block hangs over the edge of ``k`` or ``n``, the buffer
    poisoned past the live tiles: NaNs there do no harm, and what a
    hanging block reads past the edge reaches nothing that is written."""
    tile = 8
    padded = np.asarray([8, 24, 8, 16])
    used, tiles = int(padded.sum()), int(padded.sum()) // tile + 3
    rng = np.random.RandomState(4)
    lhs = np.full((tiles * tile, k), np.nan, np.float32)
    lhs[:used] = rng.randn(used, k)
    w = jnp.asarray(rng.randn(len(padded), k, n), jnp.float32)
    group = np.repeat(np.arange(len(padded)), padded // tile)
    group = np.concatenate([group, np.full(tiles - len(group), group[-1])])
    args = (jnp.asarray(group, jnp.int32),
            jnp.asarray([used // tile], jnp.int32))
    sizes = jnp.asarray(padded, jnp.int32)
    assert gm.supported((tiles * 128, k), w.shape, 128)
    assert not gm.supported((tiles * 128, k + 32), (4, k + 32, n), 128)

    def kernels(lhs, w):
        return gm.grouped_matmul(lhs, w, *args, sizes, tile, "gmm_interpret")

    def ragged(lhs, w):
        return gm.grouped_matmul(lhs, w, *args, sizes, tile, "ragged")

    lhs = jnp.asarray(lhs)
    got = kernels(lhs, w)
    assert got.shape == (tiles * tile, n)
    want = ragged(jnp.nan_to_num(lhs), w)[:used]
    np.testing.assert_allclose(np.asarray(got[:used]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    ct = jnp.asarray(rng.randn(tiles * tile, n), jnp.float32)
    loss = lambda fn: lambda a, b: jnp.sum(fn(a, b)[:used] * ct[:used])  # noqa: E731
    (da, dw), (ea, ew) = (jax.grad(loss(fn), (0, 1))(jnp.nan_to_num(lhs), w)
                          for fn in (kernels, ragged))
    np.testing.assert_allclose(np.asarray(da[:used]), np.asarray(ea[:used]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ew), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("width,want,cols", [
    (2048, 512, 512), (1024, 1024, 1024), (2688, 512, 384),
    (2688, 1024, 896), (1856, 512, 384), (1856, 1024, 640), (192, 512, 128),
    (64, 512, 64), (256, 512, 256), (320, 1024, 320)], ids=str)
def test_a_widths_blocks(width, want, cols):
    """Widths of whole 256s keep the power-of-two blocks they had; the
    others take the multiple of 128 that hangs least over the edge."""
    assert gm._cols(width, want) == cols


def test_rows_move_at_a_hidden_size_that_is_no_whole_tile_a_row():
    """Hidden 384 in bfloat16 is 3 rows of 128 lanes where a tile holds
    16: the take pads a row of its source to a whole tile, copies that,
    and writes the 384 lanes; the buffer behind the live tiles is
    poisoned for the combine and left alone by the take."""
    tile, h, tokens = 128, 384, 128
    experts = np.full((tokens, 2), 5, np.int32)
    experts[:, 1] = 6
    experts[:40, 0], experts[40:60, 0], experts[60:122, 1] = 0, 1, 2
    cfg = moe.RoutedConfig(8, (0, 1, 2), 2, row_tile=tile)
    plan = moe.plan_rows(jnp.asarray(experts), cfg)
    live = int(plan["num_tiles"][0]) * tile
    rng = np.random.RandomState(7)
    src = jnp.asarray(rng.randn(tokens, h), jnp.bfloat16)
    index = np.asarray(plan["row_token"]).copy()
    assert live < index.size
    index[live:] = 2 ** 30
    scale = jnp.asarray(rng.rand(index.size), jnp.float32)
    for factor in (None, scale):
        got, want = (np.asarray(rr.take_rows(
            src, jnp.asarray(index), plan["num_tiles"], tile, scale=factor,
            impl=impl), np.float32) for impl in ("gmm_interpret", "ragged"))
        np.testing.assert_allclose(got[:live], want[:live], rtol=1e-2)
        assert got.shape == (index.size, h)
        assert not got[:live][index[:live] == tokens].any()     # pad rows
        assert np.isnan(got[live:]).all()
    y = np.full((index.size, h), np.nan, np.float32)
    y[:live] = rng.randn(live, h)
    w = jnp.asarray(rng.rand(tokens, 2), jnp.float32)
    got, want = (rr.combine_rows(buf, plan["dest"], w, plan, impl=impl)
                 for buf, impl in ((jnp.asarray(y), "gmm_interpret"),
                                   (jnp.nan_to_num(y), "ragged")))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


# ------------------------------------------------------ the share test

@pytest.mark.parametrize("balanced", [False, True],
                         ids=["by_score", "balanced"])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(balanced):
    """4 chips hold 2 of 8 experts each (the cell: 16 chips, 8 of 128).
    Each computes the shared expert and its own experts' part; the routed
    parts of all shares and the shared expert ONCE are the uncut
    reference's layer (the reference of the benchmark, given all 8)."""
    experts, top_k = 8, 3
    rng = np.random.RandomState(3)
    normal = lambda *s: jnp.asarray(rng.randn(*s) * 0.05, jnp.float32)  # noqa: E731
    whole = {"router": normal(H, experts),
             "experts": {"up": normal(experts, H, M),
                         "down": normal(experts, M, H)},
             "shared": {"up": normal(H, MS), "down": normal(MS, H)}}
    f = jnp.asarray(rng.randn(T, H), jnp.float32)
    z = {"top_k": top_k, "route_scale": 2.5, "held": tuple(range(experts)),
         "balanced": balanced}
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision="highest")  # noqa: E731
    uncut = ref._routed(f, whole, z, dot)
    shared = moe.relu2(f @ whole["shared"]["up"]) @ whole["shared"]["down"]
    total = shared
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        cfg = moe.RoutedConfig(experts, held, top_k, 2.5, row_tile=8,
                               balanced=balanced, act="relu2", shared_dim=MS)
        share = dict(whole, experts=jax.tree_util.tree_map(
            lambda w: w[2 * chip:2 * chip + 2], whole["experts"]))
        mine = moe.routed_ffn(f, share, cfg)
        # the reference is given the same share and gives the same part
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(ref._routed(
                f, share, dict(z, held=held), dot)), rtol=1e-4, atol=1e-6)
        total = total + (mine - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-4, atol=2e-6)

"""The routed feed-forward layer of one chip's share (``moe.routed_ffn``),
its grouped products (``ops/grouped_matmul.py``), the experts' function
between them (``ops/routed_act.py``) and the kernels that move
its rows (``ops/routed_rows.py``): against a dense
masked product over the held experts; no row dropped under an imbalance
forced by a biased router; and THE TEST THAT TIES THE SHARE TO THE MODEL:
the shares of the experts, with the shared expert counted once, add up to
the uncut reference's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import moe
from byteps_tpu.common import setup_record
from byteps_tpu.ops import grouped_matmul as gm
from byteps_tpu.ops import routed_act as ra
from byteps_tpu.ops import routed_rows as rr

from benchmark.reference import afmoe_share as ref

T, H, M, E, K = 96, 128, 128, 16, 4


def _layer(seed, held, shared=True, act="gated_silu"):
    rng = np.random.RandomState(seed)
    normal = lambda *s: jnp.asarray(rng.randn(*s) * 0.05, jnp.float32)  # noqa: E731
    first, wide = moe.ACTS[act][1], M * ra.FORMS[act][1]
    blk = {"router": normal(H, E),
           "experts": {first: normal(len(held), H, wide),
                       "down": normal(len(held), M, H)}}
    if shared:
        blk["shared"] = {first: normal(H, wide), "down": normal(M, H)}
    return blk, jnp.asarray(rng.randn(T, H), jnp.float32)


def _dense(f, blk, cfg):
    """Every held expert over every row, masked by the router's choice."""
    act, first = moe.ACTS[cfg.act]
    w, chosen = moe.route(f, blk["router"], cfg)
    out = (act(f @ blk["shared"][first])
           @ blk["shared"]["down"]) if "shared" in blk else 0.0
    for g, e in enumerate(cfg.held):
        mine = jnp.where(chosen == e, w, 0.0).sum(-1)
        out = out + mine[:, None] * (
            act(f @ blk["experts"][first][g]) @ blk["experts"]["down"][g])
    return out


@pytest.mark.parametrize("impl", ["ragged", "gmm_interpret"])
@pytest.mark.parametrize("held", [(0, 1, 2, 3), (5, 9), tuple(range(16)),
                                  (15,)], ids=str)
def test_routed_layer_and_every_gradient_match_the_dense_product(held, impl):
    cfg = moe.RoutedConfig(E, held, K, 2.8, row_tile=128, impl=impl)
    blk, f = _layer(0, held)

    def loss(fn):
        return lambda f, blk: jnp.sum(jnp.sin(fn(f, blk, cfg)))

    (a, ga), (b, gb) = (jax.value_and_grad(loss(fn), (0, 1))(f, blk)
                        for fn in (moe.routed_ffn, _dense))
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-6)


@pytest.mark.parametrize("impl", ["ragged", "gmm_interpret"])
def test_no_row_is_dropped_when_the_router_favours_two_experts(impl):
    """A router biased toward experts 1 and 2 sends them nearly every row
    (far beyond any capacity factor); each row's result still holds both."""
    held = (0, 1, 2, 3)
    bias = np.zeros((H, E), np.float32)
    cfg = moe.RoutedConfig(E, held, 2, 1.0, row_tile=128, impl=impl)
    blk, f = _layer(1, held, shared=False)
    f = jnp.abs(f)                      # so that a positive column wins
    bias[:, 1:3] = 0.5
    blk["router"] = blk["router"] + bias
    _, chosen = moe.route(f, blk["router"], cfg)
    plan = moe.plan_rows(chosen, cfg)
    counts = np.asarray(plan["counts"])
    assert counts[1] == T and counts[2] == T and counts.sum() == 2 * T
    # every chosen pair has a row of its own, and every such row its pair
    dest = np.asarray(plan["dest"]).reshape(-1)
    assert len(set(dest)) == 2 * T and dest.max() < plan["row_pair"].shape[0]
    np.testing.assert_array_equal(np.asarray(plan["row_pair"])[dest],
                                  np.arange(2 * T))
    np.testing.assert_allclose(np.asarray(moe.routed_ffn(f, blk, cfg)),
                               np.asarray(_dense(f, blk, cfg)), rtol=1e-4,
                               atol=1e-6)


def test_the_plan_puts_every_expert_on_whole_tiles():
    held = (3, 7, 8, 12)
    cfg = moe.RoutedConfig(E, held, K, row_tile=8)
    blk, f = _layer(2, held)
    _, chosen = moe.route(f, blk["router"], cfg)
    plan = {k: np.asarray(v) for k, v in moe.plan_rows(chosen, cfg).items()}
    chosen = np.asarray(chosen)
    for g, e in enumerate(held):
        assert plan["counts"][g] == (chosen == e).sum()
    assert (plan["group_rows"] % 8 == 0).all() and (
        plan["group_rows"] >= np.maximum(plan["counts"], 1)).all()
    assert plan["num_tiles"][0] * 8 == plan["group_rows"].sum()
    # a tile holds one expert's rows: the rows' pairs chose that expert
    pairs = chosen.reshape(-1)
    for r, p in enumerate(plan["row_pair"]):
        if p < pairs.size:
            assert held[plan["tile_group"][r // 8]] == pairs[p]
    # the buffer is sized for the worst routing: all k choices held
    assert plan["row_pair"].shape[0] >= T * K


def _plan_by_hand(experts, weights, held, tile, token_tile):
    """``plan_rows``'s arrays by a loop over the held experts, in NumPy:
    expert g's pairs in the pairs' order, its run on the next tile border,
    padded to whole tiles and to at least one; then, a tile of tokens and
    an expert, where that tile's pairs lie in the expert's run."""
    t, k = experts.shape
    pairs, flat = t * k, experts.reshape(-1)
    tiles = -(-t * min(k, len(held)) // tile) + len(held)
    dest = np.full(pairs, tiles * tile, np.int32)
    row_pair = np.full(tiles * tile, pairs, np.int32)
    row_weight = np.zeros(tiles * tile, np.float32)
    tile_group = np.full(tiles, len(held) - 1, np.int32)
    lo = np.zeros((t // token_tile, len(held)), np.int32)
    hi = np.zeros_like(lo)
    counts, group_rows, row = [], [], 0
    for g, e in enumerate(held):
        mine = np.flatnonzero(flat == e)
        padded = max(-(-mine.size // tile), 1) * tile
        dest[mine] = row + np.arange(mine.size)
        row_pair[row:row + mine.size] = mine
        row_weight[row:row + mine.size] = weights.reshape(-1)[mine]
        tile_group[row // tile:(row + padded) // tile] = g
        for i in range(t // token_tile):
            before = (mine < i * token_tile * k).sum()
            lo[i, g] = row + before
            hi[i, g] = row + (mine < (i + 1) * token_tile * k).sum()
        counts.append(mine.size)
        group_rows.append(padded)
        row += padded
    return {"dest": dest.reshape(t, k), "row_pair": row_pair,
            "row_token": np.where(row_pair < pairs, row_pair // k, t),
            "tile_group": tile_group, "num_tiles": [row // tile],
            "group_rows": group_rows, "counts": counts,
            "lo": lo.reshape(-1), "hi": hi.reshape(-1),
            "lanes": np.repeat(np.stack([lo, hi], 1), rr._WINDOW, axis=2),
            "live": [row], "row_weight": row_weight}


def _routing(kind, rng, tokens, k, held, tile):
    """[tokens, k] distinct outputs of 128 a token, by the state's name."""
    others = np.setdiff1d(np.arange(128), held)
    if kind == "uniform":
        return np.stack([rng.permutation(128)[:k] for _ in range(tokens)])
    if kind == "worst":                 # every pair's expert is held
        return np.stack([rng.permutation(held)[:k] for _ in range(tokens)])
    chosen = np.stack([rng.permutation(others)[:k] for _ in range(tokens)])
    if kind == "one_expert":            # it takes every token, the rest none
        chosen[:, rng.randint(k)] = held[3]
    if kind == "tile_border":           # runs of exactly one and two tiles
        chosen[:tile, 0], chosen[tile:3 * tile, 1] = held[1], held[-1]
    return chosen                       # "none": no pair's expert is held


@pytest.mark.parametrize("routing", ["uniform", "worst", "none", "one_expert",
                                     "tile_border"])
@pytest.mark.parametrize("held", [tuple(range(8)), tuple(range(5, 128, 8))],
                         ids=["first_8", "every_8th_16"])
@pytest.mark.parametrize("k", [6, 8])
def test_the_plan_is_the_plan_made_by_hand_over_the_whole_buffer(
        monkeypatch, k, held, routing):
    """Every array of ``plan_rows``, pad rows and dead tiles too, and the
    rows' weights beside them, element for element against a loop over
    the experts: 6 and 8 choices of 128 outputs, 8 held in a range and 16
    spread, under uniform routing, the worst (every pair held), none, one
    expert taking every token, and runs that end on a tile's border."""
    monkeypatch.setattr(rr, "_TOKEN_TILE", 32)      # three tiles of tokens
    tile = 16
    rng = np.random.RandomState(k * len(held))
    chosen = _routing(routing, rng, T, k, np.asarray(held), tile)
    weights = rng.rand(T, k).astype(np.float32)
    cfg = moe.RoutedConfig(128, held, k, row_tile=tile)
    got = moe.plan_rows(jnp.asarray(chosen, jnp.int32), cfg,
                        jnp.asarray(weights))
    want = _plan_by_hand(chosen, weights, held, tile, 32)
    assert set(got) == set(want)
    for name, array in want.items():
        np.testing.assert_array_equal(np.asarray(got[name]), array, name)
        assert got[name].dtype == (jnp.float32 if name == "row_weight"
                                   else jnp.int32), name
    held_pairs = np.isin(chosen, held).sum()
    assert int(np.sum(want["counts"])) == held_pairs == {
        "worst": T * k, "none": 0, "one_expert": T}.get(routing, held_pairs)
    if routing == "tile_border":
        assert sorted(want["counts"])[-2:] == [tile, 2 * tile]
    # without the weights: the same int32 arrays and nothing else
    bare = moe.plan_rows(jnp.asarray(chosen, jnp.int32), cfg)
    assert set(bare) == set(want) - {"row_weight"}
    for name, array in bare.items():
        np.testing.assert_array_equal(np.asarray(array), want[name], name)


@pytest.mark.parametrize("balanced", [False, True],
                         ids=["by_score", "balanced"])
def test_the_chosen_scores_are_the_gathers_to_the_bit(balanced):
    """``route`` reads the chosen experts' scores by a compare against
    every output and a sum with one term that is not zero: the weights and
    their gradient to the router's weights and to the tokens are those of
    ``take_along_axis`` (whose transpose is a scatter-add), bit for bit,
    op by op (no jit: XLA's CPU fusions round a sigmoid they fuse
    otherwise)."""
    held = (0, 1, 2, 3)
    cfg = moe.RoutedConfig(E, held, K, 2.8, balanced=balanced)
    blk, f = _layer(14, held)
    ct = jnp.asarray(np.random.RandomState(14).randn(T, K), jnp.float32)
    experts = moe.route(f, blk["router"], cfg, 2)[1]

    def by_gather(f, w):
        scores = jax.nn.sigmoid(jnp.dot(f, w, precision="highest"))
        top = jnp.take_along_axis(scores, experts, axis=-1)
        return cfg.route_scale * top / top.sum(-1, keepdims=True)

    def ours(f, w):
        return moe.route(f, w, cfg, 2)[0]

    (a, pull_a), (b, pull_b) = (jax.vjp(fn, f, blk["router"])
                                for fn in (ours, by_gather))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for x, y in zip(pull_a(ct), pull_b(ct)):
        assert np.asarray(x).any()
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("balanced", [False, True],
                         ids=["by_score", "balanced"])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(balanced):
    """8 chips hold 2 of 16 experts each. Each computes the shared expert
    and its own experts' part; the routed parts of all shares and the
    shared expert ONCE are the uncut reference's layer (the reference of
    the benchmark, given all 16 experts)."""
    rng = np.random.RandomState(3)
    normal = lambda *s: jnp.asarray(rng.randn(*s) * 0.05, jnp.float32)  # noqa: E731
    whole = {"router": normal(H, E),
             "experts": {"gate_up": normal(E, H, 2 * M),
                         "down": normal(E, M, H)},
             "shared": {"gate_up": normal(H, 2 * M), "down": normal(M, H)}}
    f = jnp.asarray(rng.randn(T, H), jnp.float32)
    z = {"top_k": K, "route_scale": 2.8, "held": tuple(range(E)),
         "balanced": balanced}
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision="highest")  # noqa: E731
    uncut = ref._routed(f, whole, z, dot)
    shared = moe.gated_silu(f @ whole["shared"]["gate_up"]) @ whole[
        "shared"]["down"]
    total = shared
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        cfg = moe.RoutedConfig(E, held, K, 2.8, row_tile=8,
                               balanced=balanced)
        share = dict(whole, experts=jax.tree_util.tree_map(
            lambda w: w[2 * chip:2 * chip + 2], whole["experts"]))
        # the reference is given the same share and gives the same part
        mine = moe.routed_ffn(f, share, cfg)
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(ref._routed(
                f, share, dict(z, held=held), dot)), rtol=1e-4, atol=1e-6)
        total = total + (mine - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-4, atol=2e-6)


def _ragged_setup(k, n, tile=8, rows_in_groups=(8, 20, 0), spare=2, seed=7,
                  whole_numbers=False):
    """Rows sorted by group on whole tiles, ``spare`` tiles behind them."""
    padded = np.maximum(-(-np.asarray(rows_in_groups) // tile), 1) * tile
    used, tiles = int(padded.sum()), int(padded.sum()) // tile + spare
    rng = np.random.RandomState(seed)
    draw = ((lambda *s: rng.randint(-3, 4, s).astype(np.float32))
            if whole_numbers else (lambda *s: rng.randn(*s) * 0.25))
    lhs = np.full((tiles * tile, k), np.nan, np.float32)
    lhs[:used] = draw(used, k)
    dout = np.full((tiles * tile, n), np.nan, np.float32)
    dout[:used] = draw(used, n)
    w = jnp.asarray(draw(len(padded), k, n), jnp.float32)
    group = np.repeat(np.arange(len(padded)), padded // tile)
    group = np.concatenate([group, np.full(tiles - len(group), group[-1])])
    return (jnp.asarray(lhs), jnp.asarray(dout), w,
            jnp.asarray(group, jnp.int32),
            jnp.asarray([used // tile], jnp.int32),
            jnp.asarray(padded, jnp.int32), used)


@pytest.mark.parametrize("rows_in_groups", [(8, 24, 0, 16), (0, 0, 0, 8)],
                         ids=str)
def test_grouped_products_skip_what_lies_behind_the_rows(rows_in_groups):
    """The three kernels in the interpreter against ``lax.ragged_dot``,
    on a buffer longer than its rows: tiles past ``num_tiles`` are not
    read (NaNs there do no harm) and not written."""
    tile, k, n = 8, 128, 256
    lhs, ct, w, *args, sizes, used = _ragged_setup(
        k, n, tile, rows_in_groups, spare=3, seed=4)

    def kernels(lhs, w):
        return gm.grouped_matmul(lhs, w, *args, sizes, tile, "gmm_interpret")

    def ragged(lhs, w):
        return gm.grouped_matmul(lhs, w, *args, sizes, tile, "ragged")

    got = kernels(lhs, w)[:used]
    want = ragged(jnp.nan_to_num(lhs), w)[:used]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    ct = jnp.nan_to_num(ct)
    loss = lambda fn: lambda a, b: jnp.sum(fn(a, b)[:used] * ct[:used])  # noqa: E731
    (da, dw), (ea, ew) = (jax.grad(loss(fn), (0, 1))(jnp.nan_to_num(lhs), w)
                          for fn in (kernels, ragged))
    np.testing.assert_allclose(np.asarray(da[:used]), np.asarray(ea[:used]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ew), rtol=1e-4,
                               atol=1e-4)


def test_grouped_products_carry_their_names():
    import re
    tile = 128
    args = (jnp.zeros((2,), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.asarray([128], jnp.int32))
    loss = lambda a, w: gm.grouped_matmul(a, w, *args, tile, "gmm").sum()  # noqa: E731
    jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(
        jnp.zeros((256, 128)), jnp.zeros((1, 128, 128))))
    assert set(re.findall(r"name=(bps_gmm\w*)", jaxpr)) == {
        "bps_gmm", "bps_gmm_dx", "bps_gmm_dw"}


# ---- the grouped products' blocks (PR 53): each operand across HBM once

# the three routed cells' experts: the two weights [k, n] a layer (the first
# product's, the second's), at a row tile of 512 in bf16
CELL_WEIGHTS = {"trinity": ((2048, 2048), (1024, 2048)),
                "nemotron": ((2688, 1856), (1856, 2688)),
                "kanana": ((2048, 1536), (768, 2048))}


@pytest.mark.parametrize("cell", sorted(CELL_WEIGHTS))
@pytest.mark.parametrize("kernel", gm.KERNELS)
def test_at_the_cells_shapes_every_operand_crosses_hbm_once(kernel, cell):
    """The nine kernel shapes of the three routed cells: the rule takes
    the whole width, so no operand is read twice; what a step holds by
    count is under the limit the call asks for, and the limit is what the
    step needs, under the budget."""
    for k, n in CELL_WEIGHTS[cell]:
        blocks, limit = gm._blocks(kernel, k, n, 512, 2)
        assert blocks == ((k, n) if kernel == "bps_gmm_dw" else
                          (n if kernel == "bps_gmm" else k,))
        assert set(gm.operand_passes(kernel, k, n, 512, 2).values()) == {1}
        held = gm._step_bytes(kernel, k, n, 512, 2, blocks)
        assert held < limit <= gm._VMEM_BUDGET
        assert limit <= max(held + (4 << 20), 16 << 20)


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688), (2048, 1536),
                                 (768, 2048)])
def test_whole_width_blocks_match_ragged_dot_and_its_gradients(k, n):
    """The three kernels at ONE block a width (1856 and 1536 are no power
    of two of lane tiles, 1856 not even whole ones: a block equal to the
    array's dimension hangs over nothing) against ``lax.ragged_dot`` and
    its gradients, a few rows and experts; the tiles past ``num_tiles``
    hold NaNs in both operands and are neither read nor written."""
    tile = 8
    lhs, dout, w, group, num, sizes, used = _ragged_setup(k, n, tile)
    assert gm._blocks("bps_gmm", k, n, tile, 4)[0] == (n,)
    assert gm._blocks("bps_gmm_dx", k, n, tile, 4)[0] == (k,)
    assert gm._blocks("bps_gmm_dw", k, n, tile, 4)[0] == (k, n)

    def product(impl):
        return lambda a, b: gm.grouped_matmul(a, b, group, num, sizes, tile,
                                              impl)

    got, pull = jax.vjp(product("gmm_interpret"), lhs, w)
    want, pull_ragged = jax.vjp(product("ragged"), jnp.nan_to_num(lhs), w)
    np.testing.assert_allclose(np.asarray(got[:used]),
                               np.asarray(want[:used]), rtol=1e-4, atol=1e-3)
    (da, dw), (ea, ew) = pull(dout), pull_ragged(jnp.nan_to_num(dout))
    np.testing.assert_allclose(np.asarray(da[:used]), np.asarray(ea[:used]),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ew), rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("kernel", gm.KERNELS)
def test_the_narrowest_and_the_widest_block_give_the_same_bits(monkeypatch,
                                                               kernel):
    """A contraction is never cut and ``bps_gmm_dw`` sums the row tiles in
    the same order whatever its column blocks: blocks of one lane tile
    (the last hanging over the edge of 1856 = 14.5) and the whole width
    give equal results. In whole numbers, so that the CPU's own order of
    a float32 product's sums (it follows the block's width) is out of the
    comparison; the MXU's is asserted on the chip (PERF.md, PR 53)."""
    k, n, tile = 384, 1856, 8
    lhs, dout, w, group, num, _, used = _ragged_setup(
        k, n, tile, whole_numbers=True)

    def run(blocks):
        monkeypatch.setattr(gm, "_blocks", lambda *a: (blocks, 16 << 20))
        if kernel == "bps_gmm_dw":
            return gm._gmm_dw.__wrapped__(lhs, dout, group, num, 3, tile,
                                          True)
        rows = dout if kernel == "bps_gmm_dx" else lhs
        return gm._gmm.__wrapped__(rows, w, group, num, tile,
                                   kernel == "bps_gmm_dx", True)[:used]

    narrow, wide = (((128, 128), (k, n)) if kernel == "bps_gmm_dw" else
                    ((128,), (k if kernel == "bps_gmm_dx" else n,)))
    a, b = np.asarray(run(narrow)), np.asarray(run(wide))
    assert np.isfinite(a).all() and np.abs(a).max() > 0
    np.testing.assert_array_equal(a, b)


def test_a_width_that_cannot_fit_whole_is_cut_and_the_passes_say_so():
    """An expert of 8192 x 8192 in bf16: a whole width is 128 MiB a
    buffer. ``bps_gmm`` / ``_dx`` take the widest cut that fits the budget
    (1024: eight passes over the rows where the default limit's 512 made
    sixteen); ``bps_gmm_dw`` keeps ``n`` whole (the rows' transposed
    block serves all of it) and cuts ``k``. Past every cut that fits, the
    blocks the default limit got."""
    for kernel in ("bps_gmm", "bps_gmm_dx"):
        blocks, limit = gm._blocks(kernel, 8192, 8192, 512, 2)
        assert blocks == (1024,) and limit <= gm._VMEM_BUDGET
        assert gm.operand_passes(kernel, 8192, 8192, 512, 2) == {
            "lhs": 8, "w": 1, "out": 1}
    blocks, limit = gm._blocks("bps_gmm_dw", 8192, 8192, 512, 2)
    assert blocks == (512, 8192) and limit <= gm._VMEM_BUDGET
    assert gm.operand_passes("bps_gmm_dw", 8192, 8192, 512, 2) == {
        "lhs": 1, "dout": 16, "out": 1}
    assert gm._blocks("bps_gmm", 1 << 17, 4096, 512, 4)[0] == (512,)
    # bps_gmm_dw holds no whole dimension, so a cut of it always fits a
    # row tile of 512; under one of 8192 nothing does
    assert gm._blocks("bps_gmm_dw", 1 << 17, 1 << 17, 512, 4)[0] == (
        512, 4096)
    assert gm._blocks("bps_gmm_dw", 4096, 4096, 8192, 4)[0] == (512, 1024)
    # a width under the floor is one block whatever the budget
    assert gm._blocks("bps_gmm", 128, 64, 8, 4)[0] == (64,)


def test_under_a_small_budget_the_cut_blocks_still_match_ragged_dot(
        monkeypatch):
    """The same rule with less to spend: the blocks narrow (1856 -> 640
    and 384, the last block over the edge) and the results do not move."""
    k, n, tile = 1856, 768, 8
    lhs, dout, w, group, num, sizes, used = _ragged_setup(k, n, tile)
    monkeypatch.setattr(gm, "_VMEM_BUDGET", 6 << 20)
    monkeypatch.setattr(gm, "_MOSAIC_ROOM", 0)
    assert gm._blocks("bps_gmm_dx", k, n, tile, 4)[0] == (640,)
    assert gm._blocks("bps_gmm_dw", k, n, tile, 4)[0] == (640, 768)
    assert gm.operand_passes("bps_gmm_dx", k, n, tile, 4)["lhs"] == 3
    want, pull = jax.vjp(lambda a, b: gm.grouped_matmul(
        a, b, group, num, sizes, tile, "ragged"), jnp.nan_to_num(lhs), w)
    ea, ew = pull(jnp.nan_to_num(dout))
    got = gm._gmm.__wrapped__(lhs, w, group, num, tile, False, True)
    da = gm._gmm.__wrapped__(dout, w, group, num, tile, True, True)
    dw = gm._gmm_dw.__wrapped__(lhs, dout, group, num, 3, tile, True)
    for a, b in ((got[:used], want[:used]), (da[:used], ea[:used]),
                 (dw, ew)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-3)


@pytest.mark.parametrize("tokens,vocab,hidden,cols", [
    (16384, 25024, 2048, 1024), (16384, 16384, 2688, 896),
    (16384, 16032, 2048, 1024), (32768, 30522, 1024, 1024),
    (8192, 50257, 1024, 1024)],
    ids=["trinity_mini", "nemotron3_nano", "kanana2_30b", "bert_large",
         "gpt2_medium"])
def test_the_embeddings_backward_keeps_its_blocks(tokens, vocab, hidden,
                                                  cols):
    """``bps_embed_dw`` is not the experts': its grid and blocks at the
    configurations' shapes are PR 42's (256 rows of the vocabulary, 256
    token rows, ``_cols(hidden, 1024)`` columns) and it asks for no VMEM
    beyond Mosaic's default."""
    jaxpr = jax.make_jaxpr(lambda ids, d: gm.embed_dw(
        ids, d, vocab, None, jnp.float32, False))(
            jax.ShapeDtypeStruct((tokens,), jnp.int32),
            jax.ShapeDtypeStruct((tokens, hidden), jnp.bfloat16))
    (call,) = [eqn for eqn in _equations(jaxpr.jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert call.params["name"] == "bps_embed_dw"
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (hidden // cols, tokens // 256 + -(-vocab // 256))
    assert [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
            for bm in mapping.block_mappings] == [
                (1, 1, 256), (256, cols), (256, cols)]
    params = call.params["compiler_params"]["mosaic_tpu"]
    assert params.vmem_limit_bytes is None
    assert params.dimension_semantics == ("arbitrary", "arbitrary")


def test_the_lowered_grouped_products_ask_for_their_vmem():
    """Lowered for the TPU, each ``bps_gmm*`` custom call carries the
    scoped VMEM ``_blocks`` counted for it (Mosaic's default of 16 MiB
    holds no whole weight of a cell)."""
    import re
    k, n, tile, tiles, held = 2688, 1856, 512, 4, 2
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((tiles * tile, k), jnp.bfloat16), ((held, k, n), jnp.bfloat16),
        ((tiles,), jnp.int32), ((1,), jnp.int32))]

    def grads(lhs, w, group, num):
        out, pull = jax.vjp(lambda a, b: gm.grouped_matmul(
            a, b, group, num, None, tile, "gmm"), lhs, w)
        return out, pull(out)

    text = jax.jit(grads).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    asked = {name: int(size) for size, name in re.findall(
        r'scoped_memory_configs[^\n]*?size\\22: (\d+)[^\n]*?'
        r'kernel_name = "(bps_gmm\w*)"', text)}
    assert asked == {kernel: gm._blocks(kernel, k, n, tile, 2)[1]
                     for kernel in gm.KERNELS}
    assert min(asked.values()) > 16 << 20


# ---- the experts' function (ops/routed_act.py): bps_moe_act_fwd, _bwd

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("act,m", [("gated_silu", 1024), ("relu2", 1856)])
def test_the_experts_function_skips_what_lies_behind_the_rows(act, m, dtype):
    """Both kernels in the interpreter at the two cells' widths (2048 ->
    1024, and 1856 = 14.5 lane tiles) against ``jax.vjp`` of the XLA
    function in float32, on the live rows; the tiles past ``num_tiles``
    hold NaN before the call: none reaches a live row, and nothing is
    written behind the rows (the interpreter hands out NaN there)."""
    tile, tiles, live = 128, 5, 3
    used = live * tile
    fn, per = ra.FORMS[act]
    assert ra.supported((tiles * tile, per * m), tile, act)
    rng = np.random.RandomState(11)
    h = np.full((tiles * tile, per * m), np.nan, np.float32)
    da = np.full((tiles * tile, m), np.nan, np.float32)
    h[:used], da[:used] = rng.randn(used, per * m), rng.randn(used, m)
    h, da = jnp.asarray(h, dtype), jnp.asarray(da, dtype)
    num = jnp.asarray([live], jnp.int32)
    got, pull = jax.vjp(
        lambda h: ra.routed_act(h, num, tile, act, "gmm_interpret"), h)
    d_got, = pull(da)
    want, pull = jax.vjp(fn, h[:used].astype(jnp.float32))
    d_want, = pull(da[:used].astype(jnp.float32))
    assert got.dtype == dtype and d_got.dtype == dtype
    assert got.shape == da.shape and d_got.shape == h.shape
    # one rounding to the operands' dtype on the way out
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    for ours, theirs in ((got, want), (d_got, d_want)):
        ours = np.asarray(ours, np.float32)
        np.testing.assert_allclose(ours[:used], np.asarray(theirs), rtol=tol,
                                   atol=tol)
        assert np.isnan(ours[used:]).all()


def test_the_experts_function_carries_its_names():
    """``bps_moe_act_fwd`` and ``bps_moe_act_bwd``, not ``bps_gmm*``: the
    benchmark reads every ``bps_gmm*`` event as a grouped product."""
    import re
    num = jnp.ones((1,), jnp.int32)
    for act, width in (("gated_silu", 256), ("relu2", 192)):
        jaxpr = str(jax.make_jaxpr(jax.grad(lambda h: ra.routed_act(
            h, num, 128, act, "gmm").sum()))(jnp.zeros((256, width))))
        names = set(re.findall(r"name=(bps_\w+)", jaxpr))
        assert names == {"bps_moe_act_fwd", "bps_moe_act_bwd"}
        assert not any(n.startswith("bps_gmm") for n in names)


@pytest.mark.parametrize("m,took,fallbacks", [(128, "kernels", 0),
                                              (192, "xla", 1)])
def test_the_layers_choice_of_function_is_recorded(monkeypatch, m, took,
                                                   fallbacks):
    """On a TPU the layer's trace notes ``routed_act`` once: the kernels
    where gate and up split on a lane tile's border, and at a width of
    1.5 lane tiles XLA's function, which reads as ONE fall-back (the
    grouped products and the rows' movement take that width)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(setup_record, "_warned", set())
    held = (0, 1, 2, 3)
    cfg = moe.RoutedConfig(E, held, K, 2.8, row_tile=128)
    blk, f = _layer(12, held, shared=False)
    blk["experts"] = {"gate_up": jnp.zeros((len(held), H, 2 * m)),
                      "down": jnp.zeros((len(held), m, H))}
    rec = setup_record.open_record()
    try:
        jax.eval_shape(lambda f, blk: moe.routed_ffn(f, blk, cfg), f, blk)
    finally:
        setup_record.close(rec)
    assert rec["choices"]["routed_act", took] == 1
    assert rec["choices"]["grouped_matmul", "gmm"] == 2
    assert [key[:2] for key in rec["fallbacks"]] == [
        ("routed_act", "xla")] * fallbacks


@pytest.mark.parametrize("impl", ["ragged", "gmm_interpret"])
def test_a_balanced_choice_gives_every_expert_its_share_of_the_rows(impl):
    """A router whose outputs sit far apart (experts 1 and 2 chosen by
    every token, others by none) chooses, on its outputs standardised an
    expert, every expert about ``T k / E`` times; the weights stay the
    chosen experts' own scores, normalised; and the layer and its
    gradients are the reference's, told the same."""
    held = (0, 1, 2, 3)
    blk, f = _layer(5, held)
    f = jnp.abs(f)
    blk["router"] = blk["router"].at[:, 1:3].add(0.5).at[:, 5:9].add(-0.5)
    plain = moe.RoutedConfig(E, held, K, 2.8, row_tile=128, impl=impl)
    cfg = moe.RoutedConfig(E, held, K, 2.8, row_tile=128, impl=impl,
                           balanced=True)
    count = lambda c: np.bincount(  # noqa: E731
        np.asarray(moe.route(f, blk["router"], c)[1]).ravel(), minlength=E)
    assert count(plain)[1] == T and count(plain)[5:9].sum() == 0
    mean = T * K / E
    assert np.abs(count(cfg) - mean).max() <= 0.4 * mean
    weights, chosen = moe.route(f, blk["router"], cfg)
    scores = jax.nn.sigmoid(jnp.dot(f, blk["router"], precision="highest"))
    top = np.take_along_axis(np.asarray(scores), np.asarray(chosen), -1)
    np.testing.assert_allclose(np.asarray(weights),
                               2.8 * top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    z = {"top_k": K, "route_scale": 2.8, "held": held, "balanced": True}
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision="highest")  # noqa: E731
    loss = lambda fn: lambda f, blk: jnp.sum(jnp.sin(fn(f, blk)))  # noqa: E731
    (a, ga), (b, gb) = (jax.value_and_grad(loss(fn), (0, 1))(f, blk)
                        for fn in (lambda f, blk: moe.routed_ffn(f, blk, cfg),
                                   lambda f, blk: ref._routed(f, blk, z, dot)))
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-6)


# ---- the movement of rows (ops/routed_rows.py): bps_moe_take, bps_moe_combine

def _worst_or_no_routing(routing, seed, impl, act="gated_silu"):
    """(cfg, blk, f). WORST: the router has as many outputs as a token
    chooses and all are held, so every token sends all ``k`` rows here.
    NONE: no token chooses a held expert."""
    if routing == "worst":
        e, held = K, tuple(range(K))
    else:
        e, held = E, (3, 12)
    cfg = moe.RoutedConfig(e, held, K, 2.8, row_tile=128, impl=impl, act=act)
    blk, f = _layer(seed, held, act=act)
    blk["router"] = blk["router"][:, :e]
    if routing == "none":       # the held outputs are every token's lowest
        f = jnp.abs(f)
        blk["router"] = blk["router"].at[:, jnp.asarray(held)].add(-2.0)
    plan = moe.plan_rows(moe.route(f, blk["router"], cfg)[1], cfg)
    assert int(plan["counts"].sum()) == (T * K if routing == "worst" else 0)
    # there are tiles behind the rows in both states
    assert int(plan["num_tiles"][0]) * 128 < plan["row_pair"].shape[0]
    return cfg, blk, f


def _finite_and_the_dense_products(cfg, blk, f):
    """Loss and every gradient of the layer: finite, and those of the
    dense masked product. Returns the layer's gradients."""
    def loss(fn):
        return lambda f, blk: jnp.sum(jnp.sin(fn(f, blk, cfg)))

    (a, ga), (b, gb) = (jax.value_and_grad(loss(fn), (0, 1))(f, blk)
                        for fn in (moe.routed_ffn, _dense))
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        assert np.isfinite(np.asarray(x)).all()
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-6)
    return ga


@pytest.mark.parametrize("impl", ["ragged", "gmm_interpret"])
@pytest.mark.parametrize("routing", ["worst", "none"])
def test_layer_and_gradients_under_worst_and_under_no_routing(routing, impl):
    """Under no routing the layer is its shared expert, the experts'
    gradients are zero, nothing is NaN."""
    cfg, blk, f = _worst_or_no_routing(routing, 6, impl)
    ga = _finite_and_the_dense_products(cfg, blk, f)
    if routing == "none":
        shared = moe.gated_silu(f @ blk["shared"]["gate_up"]) @ blk[
            "shared"]["down"]
        np.testing.assert_allclose(
            np.asarray(moe.routed_ffn(f, blk, cfg)), np.asarray(shared),
            rtol=1e-6, atol=1e-7)
        for leaf in jax.tree_util.tree_leaves(ga[1]["experts"]):
            assert not np.asarray(leaf).any()


@pytest.mark.parametrize("act", sorted(moe.ACTS))
@pytest.mark.parametrize("routing", ["worst", "none"])
def test_nan_behind_the_rows_reaches_neither_loss_nor_gradient(
        monkeypatch, routing, act):
    """The same two states with every kernel of the layer in the
    interpreter and the buffer POISONED behind the live tiles (PR 30:
    worst routing is a real state and stale rows may hold NaN): the
    first product's dead rows are NaN when the experts' function gets
    them, its own are NaN when the down product does, and the loss and
    every gradient are the dense product's, under both functions."""
    def poisoned(fn):
        def call(*args):
            out = fn(*args)
            live = args[3][0] * args[4]     # num_tiles * tile
            return jnp.where(jnp.arange(out.shape[0])[:, None] < live, out,
                             jnp.nan)
        return call

    monkeypatch.setattr(gm, "_gmm", poisoned(gm._gmm))
    _finite_and_the_dense_products(
        *_worst_or_no_routing(routing, 13, "gmm_interpret", act))


def _plan_with_a_run_that_ends_on_a_tile(tile):
    """128 tokens of 2 choices, experts 0, 1, 2 of 8 held; expert 2 gets
    62 rows of its 64-row tile, the last live one: a window over its last
    rows that ran on would read the buffer behind the rows."""
    experts = np.full((128, 2), 5, np.int32)
    experts[:, 1] = 6
    experts[:40, 0], experts[40:60, 0], experts[60:122, 1] = 0, 1, 2
    cfg = moe.RoutedConfig(8, (0, 1, 2), 2, row_tile=tile)
    return moe.plan_rows(jnp.asarray(experts), cfg)


def test_the_take_leaves_what_lies_behind_the_rows_and_zeroes_pad_rows():
    tile, h = 64, 128
    plan = _plan_with_a_run_that_ends_on_a_tile(tile)
    live = int(plan["num_tiles"][0]) * tile
    rng = np.random.RandomState(7)
    src = jnp.asarray(rng.randn(128, h), jnp.float32)
    index = np.asarray(plan["row_token"]).copy()
    assert live < index.size and (index[:live] == 128).any()
    index[live:] = 2 ** 30          # behind the rows: never looked at
    scale = jnp.asarray(rng.rand(index.size), jnp.float32)
    for factor in (None, scale):
        got = np.asarray(rr.take_rows(src, jnp.asarray(index),
                                      plan["num_tiles"], tile, scale=factor,
                                      impl="gmm_interpret"))
        want = np.asarray(rr.take_rows(src, jnp.asarray(index),
                                       plan["num_tiles"], tile, scale=factor,
                                       impl="ragged"))
        np.testing.assert_allclose(got[:live], want[:live], rtol=1e-6)
        assert not got[:live][index[:live] == 128].any()    # pad rows
        # the interpreter hands out NaN for what a kernel never wrote
        assert np.isnan(got[live:]).all()


def test_the_combine_reads_nothing_behind_the_rows(monkeypatch):
    monkeypatch.setattr(rr, "_TOKEN_TILE", 32)      # four tiles of tokens
    tile, h = 64, 128
    plan = _plan_with_a_run_that_ends_on_a_tile(tile)
    live = int(plan["num_tiles"][0]) * tile
    rng = np.random.RandomState(8)
    y = np.full((plan["row_pair"].shape[0], h), np.nan, np.float32)
    y[:live] = rng.randn(live, h)
    w = jnp.asarray(rng.rand(128, 2), jnp.float32)
    d_out = jnp.asarray(rng.randn(128, h), jnp.float32)
    for weights, ct in ((w, None), (None, None), (None, d_out)):
        got, want = (rr.combine_rows(buf, plan["dest"], weights,
                                     plan, d_out=ct, impl=impl)
                     for buf, impl in ((jnp.asarray(y), "gmm_interpret"),
                                       (jnp.nan_to_num(y), "ragged")))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["ragged", "gmm_interpret"])
def test_combine_gradients_match_autodiff_of_the_plain_gathers(impl):
    """``d_w`` of the weights and ``d_y`` of the rows, by the hand-written
    rule, against autodiff through ``jnp.take`` and an einsum."""
    held = (0, 1, 2, 3)
    cfg = moe.RoutedConfig(E, held, K, 2.8, row_tile=128)
    blk, f = _layer(9, held)
    weights, chosen = moe.route(f, blk["router"], cfg)
    plan = moe.plan_rows(chosen, cfg, weights)
    live = int(plan["num_tiles"][0]) * 128
    rng = np.random.RandomState(9)
    y = jnp.zeros((plan["row_pair"].shape[0], H)).at[:live].set(
        rng.randn(live, H))
    ct = jnp.asarray(rng.randn(T, H), jnp.float32)

    def plain(y, w):
        rows = jnp.take(y, plan["dest"].reshape(-1), axis=0, mode="fill",
                        fill_value=0).reshape(T, K, H)
        return jnp.sum(jnp.einsum("tkh,tk->th", rows, w) * ct)

    def ours(y, w):
        return jnp.sum(moe._combine(y, w, plan, 128, impl) * ct)

    (dy, dw), (ey, ew) = (jax.grad(fn, (0, 1))(y, weights)
                          for fn in (ours, plain))
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ew), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(dy[:live]), np.asarray(ey[:live]),
                               rtol=1e-5, atol=1e-6)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_with_the_kernels_no_gather_walks_the_buffer_or_every_pair():
    """The layer and its gradients traced with the kernels: no XLA gather
    reads ``hidden``-wide rows by all ``T * top_k`` chosen pairs or by the
    buffer's rows (with ``ragged`` there are five); and the kernels that
    move the rows carry names of their own, not ``bps_gmm*`` (which the
    benchmark adds into ``kernels.gmm_ms``)."""
    import re
    tokens, hidden, held = 256, 1024, (0, 1, 2, 3)
    rng = np.random.RandomState(10)
    normal = lambda *s: jnp.asarray(rng.randn(*s) * 0.05, jnp.float32)  # noqa: E731
    blk = {"router": normal(hidden, E),
           "experts": {"gate_up": normal(len(held), hidden, 2 * M),
                       "down": normal(len(held), M, hidden)}}
    f = normal(tokens, hidden)

    def wide_gathers(impl):
        cfg = moe.RoutedConfig(E, held, K, 2.8, row_tile=128, impl=impl)
        buffer = moe.plan_rows(jnp.zeros((tokens, K), jnp.int32),
                               cfg)["row_pair"].shape[0]
        traced = jax.make_jaxpr(jax.grad(
            lambda f, blk: moe.routed_ffn(f, blk, cfg).sum(), (0, 1)))(f, blk)
        found = [eqn for eqn in _equations(traced.jaxpr)
                 if eqn.primitive.name == "gather"
                 and eqn.invars[0].aval.shape[1:] == (hidden,)
                 and eqn.invars[1].aval.shape[0] in (tokens * K, buffer)]
        return found, str(traced)

    found, text = wide_gathers("gmm")
    assert not found, found
    names = set(re.findall(r"name=(bps_\w+)", text))
    assert {"bps_moe_take", "bps_moe_combine"} <= names
    assert {n for n in names if n.startswith("bps_gmm")} == {
        "bps_gmm", "bps_gmm_dx", "bps_gmm_dw"}
    # the test sees them: two forward and three backward (none recomputed)
    assert len(wide_gathers("ragged")[0]) == 5


def test_with_the_kernels_nothing_elementwise_walks_the_buffer():
    """The layer and its gradients traced with the kernels, under both
    functions: no equation outside a kernel makes a floating
    ``[buffer, .]`` array (with ``ragged`` the function, its derivative
    and the cotangents' pad-and-add do: the test sees them)."""
    tokens, hidden, held = 256, 1024, (0, 1, 2, 3)
    inside = ("pallas_call", "pjit", "jit", "custom_vjp_call",
              "custom_vjp_call_jaxpr", "custom_jvp_call")

    def walkers(impl, act):
        cfg = moe.RoutedConfig(E, held, K, 2.8, row_tile=128, impl=impl,
                               act=act)
        first, wide = moe.ACTS[act][1], M * ra.FORMS[act][1]
        blk = {"router": jnp.zeros((hidden, E)),
               "experts": {first: jnp.zeros((len(held), hidden, wide)),
                           "down": jnp.zeros((len(held), M, hidden))}}
        f = jnp.zeros((tokens, hidden))
        buffer = moe.plan_rows(jnp.zeros((tokens, K), jnp.int32),
                               cfg)["row_pair"].shape[0]
        traced = jax.make_jaxpr(jax.grad(
            lambda f, blk: moe.routed_ffn(f, blk, cfg).sum(), (0, 1)))(f, blk)
        return [eqn for eqn in _equations(traced.jaxpr)
                if eqn.primitive.name not in inside
                for out in eqn.outvars
                if getattr(out.aval, "ndim", 0) == 2
                and out.aval.shape[0] == buffer
                and jnp.issubdtype(out.aval.dtype, jnp.floating)]

    for act in sorted(moe.ACTS):
        found = walkers("gmm", act)
        assert not found, found
        assert len(walkers("ragged", act)) >= 4


def _single_element_moves(lowered):
    """(op, operand shape, index count, location) of every gather and
    scatter of a lowered program that takes ONE element an index: a gather
    whose slice sizes are all one, a scatter whose updates have no window."""
    import math
    import re
    from jaxlib.mlir import ir
    found = []

    def visit(op):
        if op.name in ("stablehlo.gather", "stablehlo.scatter"):
            numbers = str(op.attributes[
                "dimension_numbers" if op.name.endswith("gather")
                else "scatter_dimension_numbers"])
            if ("offset_dims" not in numbers
                    and "update_window_dims" not in numbers):
                shape = list(ir.RankedTensorType(op.operands[1].type).shape)
                shape.pop(int(re.search(r"index_vector_dim = (\d+)",
                                        numbers).group(1)))
                found.append((op.name, str(op.operands[0].type),
                              math.prod(shape), str(op.location)))
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir().operation.walk(visit)
    return found


@pytest.mark.parametrize("impl", ["ragged", "gmm_interpret"])
def test_nothing_takes_one_element_an_index_over_the_buffer_or_the_pairs(
        impl):
    """The lowered gradient of the layer: no gather or scatter whose slice
    is one element runs over ``buffer`` or ``T * top_k`` indices (the
    plan's int32 arrays, the chosen scores and the rows' weights are made
    by sorts, compares and a slice a row tile). The rows' movement under
    "ragged" (``take_xla``) is no such op: its slice is a whole row. And
    the plan is still a scope of its own inside the route's."""
    tokens, hidden, held = 256, 128, (0, 1, 2, 3)
    cfg = moe.RoutedConfig(E, held, K, 2.8, row_tile=128, impl=impl)
    blk = {"router": jnp.zeros((hidden, E)),
           "experts": {"gate_up": jnp.zeros((len(held), hidden, 2 * M)),
                       "down": jnp.zeros((len(held), M, hidden))}}
    f = jnp.zeros((tokens, hidden))
    buffer = moe.plan_rows(jnp.zeros((tokens, K), jnp.int32),
                           cfg)["row_pair"].shape[0]
    lowered = jax.jit(jax.grad(
        lambda f, blk: moe.routed_ffn(f, blk, cfg).sum(), (0, 1))).lower(
            f, blk)
    moves = _single_element_moves(lowered)
    assert not [m for m in moves if m[2] in (buffer, tokens * K)], moves
    # what is left reads a table of ``held`` entries by row tile
    assert all(m[2] == buffer // 128 for m in moves), moves
    assert "bps.moe.route/bps.moe.route.plan/" in lowered.as_text(
        debug_info=True)


def test_the_single_element_check_sees_the_gathers_it_is_there_for():
    """The same walk over a program that has them: a gather of single
    int32s by ``buffer`` indices and the scatter that inverts a
    permutation of the pairs (what ``plan_rows`` did before)."""
    def before(order, table):
        rank = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        return table[rank], jnp.take_along_axis(
            table.reshape(8, -1), rank.reshape(8, -1) % 4, axis=-1)

    moves = _single_element_moves(jax.jit(before).lower(
        jnp.arange(32, dtype=jnp.int32), jnp.arange(32, dtype=jnp.int32)))
    assert sorted((m[0], m[2]) for m in moves) == [
        ("stablehlo.gather", 32), ("stablehlo.gather", 32),
        ("stablehlo.scatter", 32)]

"""What a skipped grid step of the causal flash kernels costs (the online
forward, the split dq and dk/dv): its index maps name the block beside it,
so nothing is fetched for it. The index maps are read from the jaxpr; the
numbers, in Pallas' interpret mode, are the naive product's at blocks
wider than tall and taller than wide, under a band whose width is no
multiple of a block and over a fold of four query heads a kv head."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu.ops.flash_attention as fa
from byteps_tpu.ops.flash_attention import flash_attention, local_attention
from test_flash_attention import equations

SEQ = 768
# (query heads, kv heads, window): the triangle, a band whose width is no
# multiple of a block, the fold (four query heads a kv head), both
KINDS = {"triangle": (2, 2, None), "band": (2, 2, 320),
         "fold": (4, 1, None), "fold_band": (4, 1, 320)}
BLOCKS = [(128, 128), (256, 128), (128, 256)]


def _qkv(seed, s, heads, kv_heads, d=64):
    rng = np.random.RandomState(seed)
    make = lambda h: jnp.asarray(  # noqa: E731
        rng.randn(1, s, h, d).astype(np.float32))
    return make(heads), make(kv_heads), make(kv_heads)


def _out_and_grads(q, k, v, attend):
    out, vjp = jax.vjp(attend, q, k, v)
    return (out, *vjp(jnp.cos(out)))


def _assert_close(got, want):
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                                   atol=3e-4, err_msg=name)


@pytest.mark.parametrize("bq,bk", BLOCKS, ids=lambda b: str(b))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_held_blocks_are_the_right_ones(kind, bq, bk):
    """Forward, dq, dk and dv with the held index maps are the naive
    product's: a step that runs reads its own block."""
    heads, kv_heads, window = KINDS[kind]
    q, k, v = _qkv(3, SEQ, heads, kv_heads)
    _assert_close(
        _out_and_grads(q, k, v, lambda q, k, v: flash_attention(
            q, k, v, True, None, bq, bk, True, window=window)),
        _out_and_grads(q, k, v, lambda q, k, v: local_attention(
            q, k, v, causal=True, window=window)))


def test_the_lane_dense_split_backward_holds_its_blocks_too():
    """GPT-2's geometry: one forward block, the split backward over a
    2 x 2 grid of 512 x 512 (one step of the four skipped), heads as
    64-lane slices of [b, s, heads*d] blocks: the held index maps go
    through the lane-dense block form as through the head-major one."""
    q, k, v = _qkv(5, 1024, 2, 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, None, None, None, True)

    jaxpr = jax.make_jaxpr(lambda *a: _out_and_grads(*a, flash))(q, k, v)
    calls = {str(e.params["name"]): e for e in equations(jaxpr,
                                                         "pallas_call")}
    dkv = calls["bps_flash_bwd_dkv"]
    assert dkv.invars[0].aval.shape == (1, 1024, 128)
    assert dkv.params["grid_mapping"].grid[2:] == (2, 2)
    # q's block (batch, rows, lanes) at kv block 1: held at q block 1
    q_blocks = _block_indices(dkv, 0)
    assert [q_blocks[0, 0, 1, iq][1] for iq in (0, 1)] == [1, 1]
    assert [q_blocks[0, 0, 0, iq][1] for iq in (0, 1)] == [0, 1]
    _assert_close(_out_and_grads(q, k, v, flash),
                  _out_and_grads(q, k, v, lambda q, k, v: local_attention(
                      q, k, v, causal=True)))


# ---- a skipped step fetches nothing: the index maps, from the jaxpr

def _block_indices(eqn, operand):
    """{grid point: block index} of one operand of a pallas_call."""
    mapping = eqn.params["grid_mapping"]
    index_map = mapping.block_mappings[operand].index_map_jaxpr
    return {point: tuple(int(i) for i in jax.core.eval_jaxpr(
        index_map.jaxpr, index_map.consts, *map(np.int32, point)))
        for point in itertools.product(*map(range, mapping.grid))}


def _fetches(indices):
    """Block indices in the order the grid walks them (the last dimension
    fastest), a run of equal ones once: what the pipeline fetches."""
    return [index for index, _ in itertools.groupby(indices)]


def _runs(kernel, point, bq, bk, nq, nk, window):
    """Whether the kernel's body runs at a grid point: its block holds a
    visible pair (the kernels' own walk, on the host)."""
    _, _, outer, inner = point
    if kernel == "bps_flash_bwd_dkv":
        kb, steps = outer, (nq if window is None else fa._band_steps_q(
            nq, nk, bq, bk, window))
        qb = inner % steps + (0 if window is None else kb * bk // bq)
    else:
        qb = outer % nq
        kb = inner + (0 if window is None else int(
            fa._band_lo_k(qb, bq, bk, window)))
    if qb >= nq or kb >= nk:
        return False
    rows = np.arange(qb * bq, (qb + 1) * bq)[:, None]
    cols = np.arange(kb * bk, (kb + 1) * bk)[None]
    return bool(np.asarray(fa._visible(rows, cols, window)).any())


@pytest.mark.parametrize("bq,bk", BLOCKS, ids=lambda b: str(b))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_skipped_step_names_the_block_beside_it(kind, bq, bk):
    """Forward and dq: k and v are held at the q block's diagonal block
    once the steps have passed it; dk/dv: q, do, lse and delta at the kv
    block's first visible q block until the steps reach it, and at the
    band's last once past it. So the blocks fetched over the whole grid
    are those the steps that run fetch, and some step IS skipped."""
    heads, kv_heads, window = KINDS[kind]
    q, k, v = (jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
               for x in _qkv(0, SEQ, heads, kv_heads))

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, bq, bk, False,
                               window=window).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    calls = {str(e.params["name"]): e for e in equations(jaxpr,
                                                         "pallas_call")}
    # the operands a skipped step would fetch: k, v | q, do, lse, delta
    held = {"bps_flash_fwd": (1, 2), "bps_flash_bwd_dq": (1, 2),
            "bps_flash_bwd_dkv": (0, 3, 4, 5)}
    assert set(calls) == set(held)
    nq, nk = SEQ // bq, SEQ // bk
    skipped = 0
    for kernel, operands in held.items():
        for operand in operands:
            indices = _block_indices(calls[kernel], operand)
            running = [index for point, index in indices.items()
                       if _runs(kernel, point, bq, bk, nq, nk, window)]
            skipped += len(indices) - len(running)
            assert _fetches(indices.values()) == _fetches(running), (
                kernel, operand)
    assert skipped

"""Grouped matrix products as Pallas TPU kernels: the experts' products
of a routed feed-forward layer (``models/moe.py::routed_ffn``).

The rows of ``lhs`` [rows, k] come sorted by group (expert), each group's
rows padded to whole row tiles of ``tile`` rows, so that a row tile
belongs to ONE group: ``tile_group[t]`` names it. Only the first
``num_tiles`` tiles hold rows; the buffer behind them is sized for the
worst routing (no row is ever dropped) and is never touched: a grid step
past ``num_tiles`` skips its body, and its index maps stay on the last
tile that ran, so it moves nothing either. Device time of these kernels
follows the rows that are there, not the buffer, and so do the movement
of rows to and from the buffer (``ops/routed_rows.py``) and the experts'
function between the products (``ops/routed_act.py``); of what surrounds
them in ``routed_ffn`` only the plan still walks the whole buffer.

  - ``bps_gmm``     out[r] = lhs[r] @ w[group(r)]          [rows, n]
  - ``bps_gmm_dx``  out[r] = lhs[r] @ w[group(r)]^T        [rows, k]
  - ``bps_gmm_dw``  out[g] = lhs[rows of g]^T @ dout[rows of g]   [g, k, n]
  - ``bps_embed_dw``  the same sum where ``lhs`` is a one-hot of ids: the
    gradient of an embedding table (``embed_dw``, below)      [vocab, n]

bf16 (or the inputs' dtype) in, fp32 accumulation on the MXU. The
contraction of the first two is one block (k, n <= a few thousand: an
expert's widths); ``bps_gmm_dw`` carries an fp32 accumulator over a
group's row tiles. Rows of tiles that did not run hold whatever the
buffer held: callers read only the rows they routed.

Which widths run the kernels (``supported``): ``k`` and ``n`` in whole
lane tiles of 128 OR ending in a half one (1856 = 14.5 tiles, an
expert's width in nemotron_h). A contraction is never cut, so it takes
the whole width, half tile and all.

How the columns are cut (``_blocks``, PR 53): the column blocks are the
grid's OUTER dimensions, so the whole buffer of rows crosses HBM once for
every column block, and in ``bps_gmm_dw`` every block of the rows is
transposed for the MXU once for every column block it meets. So a kernel
takes the widest blocks whose step fits ``_VMEM_BUDGET`` (64 of the v5e's
128 MiB) by ``_step_bytes``' count, and asks Mosaic for that step's bytes
(``vmem_limit_bytes``; its default of 16 MiB holds no expert's weight of a
cell twice, and blocks cut to fit it read the rows two to seven times):
the whole width wherever it fits, which it does at every shape a cell
runs (one block a width, nothing over an edge whatever the width:
``operand_passes`` is 1 for every operand); else ``_cols``' cuts, widest
first: a width that a power-of-two block of whole lane tiles divides is
cut into such blocks, any other into blocks of whole lane tiles that
leave the least over (2688 -> 3 x 896 or 7 x 384, 1856 -> 3 x 640 or
4 x 384 + 320), and the last block then hangs over the array's edge: what
it reads there is garbage that only reaches results past the edge, which
are not written. The results do not depend on the cut, to the bit: no
contraction is cut, and ``bps_gmm_dw`` sums a group's row tiles in the
same order whatever its column blocks.

``grouped_matmul`` is the differentiable entry: the kernels on the TPU,
``lax.ragged_dot`` elsewhere (CPU tests), like ``ops.flash_attention
.attention``.

``embed_dw`` is the grouped product's second user, and no part of a
routed layer (its kernel's name is outside ``bps_gmm*`` for that: what
reads a trace by that prefix counts the experts' products alone). A
group is a block of ``EMBED_BLOCK`` rows of the vocabulary, and the
token ids come SORTED, so a block's tokens are one run of rows. The
runs are not padded: a row tile that holds the end of one run and the
start of the next is read once for each, and a row of another block's
matches no column of this block's one-hot, which the kernel makes in
VMEM from the tile's ids. ``tile_group`` and its companion ``tile_row``
name, for each of a static ``rows // tile + blocks`` grid steps, the
block and the row tile (every block has a step, so every block of the
gradient is written, an empty one as zeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.setup_record import note_choice
from .flash_attention import _pick_block as _pick

HALF_LANES = 64     # a width is whole lane tiles, or ends in half a one
# embed_dw: rows of the vocabulary a group, token rows a grid step. The
# work is tokens x EMBED_BLOCK x hidden, the grid steps tokens / EMBED_TILE
# + vocab / EMBED_BLOCK. Read at the four cells' shapes on the v5e (PERF.md,
# PR 42): 256 / 256 is the fastest in all four, by 0.02-0.05 ms of 0.5-1.2
# over 512 / 256 and 512 / 512; 1024 rows a block do not fit VMEM
EMBED_BLOCK = 256
EMBED_TILE = 256

# a step past the rows revisits the last tile's blocks: nothing may be
# reordered around it, so the tile dimension is never "parallel"
_GMM_DIMENSIONS = ("arbitrary", "arbitrary")
_DW_DIMENSIONS = ("parallel", "parallel", "arbitrary")

_LANES = 128
# VMEM a step of a grouped product may count on, of the v5e's 128 MiB: half.
# The widest step a cell runs (``bps_gmm_dw`` on a whole [2688, 1856] of
# nemotron_h: the block twice in bf16 and once in float32) counts 51 MiB;
# Mosaic's default of 16 holds none of the cells' weights whole, and the
# blocks cut to fit it re-read the rows once a column block (PERF.md, PR 53)
_VMEM_BUDGET = 64 << 20
# what Mosaic keeps beside the blocks counted in ``_step_bytes``: chunks of
# a float32 product on their way to the result. Its own count for the
# cells' eighteen calls (``used_scoped_memory_configs`` of a compile for the
# v5e) is 0.1-0.4 MiB over ``_step_bytes``
_MOSAIC_ROOM = 2 << 20
_DEFAULT_LIMIT = 16 << 20   # Mosaic's own, where a call asks for nothing
KERNELS = ("bps_gmm", "bps_gmm_dx", "bps_gmm_dw")


def _cols(width: int, want: int) -> int:
    """Columns a block of a dimension ``width`` long where it is CUT, into
    blocks of at most ``want`` (``_blocks`` asks only where the whole
    width does not fit its budget; ``embed_dw`` always). ``_pick``'s where
    that is a power-of-two number of lane tiles, at least two (every
    width of whole 256s: 2048 -> 512 under a ``want`` of 512). Else the
    multiple of 128 up to ``want`` (and the width) whose blocks hang
    least over the edge, the larger of equals (1856 -> 384 under 512:
    4 x 384 + 320, 64 columns over; 640 under 1024); a width under a
    lane tile is one block."""
    b = _pick(width, want)
    if 256 <= b <= want or width < 128:
        return b
    return min(range(128, min(want, width) + 1, 128),
               key=lambda c: (-(-width // c) * c - width, -c))


def _widths(width: int, floor: int) -> list[int]:
    """The column blocks to try for a dimension, widest first: the whole
    width (one block, nothing over the edge, whatever the width), then
    ``_cols``' cuts at every power of two down to ``floor``."""
    out, want = [width], 1 << (width - 1).bit_length()
    while want > floor:
        want //= 2
        cut = _cols(width, want)
        if cut < out[-1]:
            out.append(cut)
    return out


def _step_bytes(kernel: str, k: int, n: int, tile: int, itemsize: int,
                blocks: tuple[int, ...]) -> int:
    """VMEM bytes a grid step of ``kernel`` holds at ``blocks``, by count:
    the pipeline's two buffers of each operand's block and of the
    result's, each padded to whole lane tiles, and what the body keeps: in
    ``bps_gmm`` / ``_dx`` a third copy of the rows' block (relaid for the
    MXU), in ``bps_gmm_dw`` the float32 accumulator and the rows' block
    transposed."""
    def lanes(c):
        return -(-c // _LANES) * _LANES

    if kernel == "bps_gmm_dw":
        tk, tn = blocks
        return ((3 * tile * lanes(tk) + 2 * tile * lanes(tn)
                 + 2 * tk * lanes(tn)) * itemsize + tk * lanes(tn) * 4)
    (tn,) = blocks
    if kernel == "bps_gmm":
        rows, weight = tile * lanes(k), k * lanes(tn)
    else:
        rows, weight = tile * lanes(n), tn * lanes(n)
    return (3 * rows + 2 * weight + 2 * tile * lanes(tn)) * itemsize


def _blocks(kernel: str, k: int, n: int, tile: int,
            itemsize: int) -> tuple[tuple[int, ...], int]:
    """The column blocks of ``kernel`` against a weight [k, n] and the
    ``vmem_limit_bytes`` its call asks for: the widest blocks whose step
    fits ``_VMEM_BUDGET`` by ``_step_bytes``, so that every operand
    crosses HBM once where a whole width fits (``operand_passes``), and
    for the limit what that step needs, never the budget: what a kernel
    reserves XLA cannot use to keep its neighbours' operands in VMEM.
    ``bps_gmm`` / ``_dx``: (tn,), the block of the result's columns.
    ``bps_gmm_dw``: (tk, tn), the widest ``tn`` first (a transposed block
    of the rows then serves all of ``n``), ``tk`` by what is left. Where
    nothing fits, the blocks the default limit got: 512, and 512 x 1024."""
    if kernel == "bps_gmm_dw":
        tries = [(tk, tn) for tn in _widths(n, 1024)
                 for tk in _widths(k, 512)]
    else:
        tries = [(tn,) for tn in _widths(n if kernel == "bps_gmm" else k,
                                         512)]
    need = [_step_bytes(kernel, k, n, tile, itemsize, b) + _MOSAIC_ROOM
            for b in tries]
    best = next((i for i, b in enumerate(need) if b <= _VMEM_BUDGET),
                len(tries) - 1)
    return tries[best], max(need[best], _DEFAULT_LIMIT)


def operand_passes(kernel: str, k: int, n: int, tile: int,
                   itemsize: int) -> dict[str, int]:
    """How often each operand of ``kernel`` crosses HBM under the blocks
    ``_blocks`` chooses, the result included. The column blocks are the
    grid's outer dimensions, so the rows go by once for every column block
    of the OTHER operand; a weight's block stays put over a group's
    consecutive row tiles, and a block of the result is written once."""
    blocks, _ = _blocks(kernel, k, n, tile, itemsize)
    if kernel == "bps_gmm_dw":
        tk, tn = blocks
        return {"lhs": -(-n // tn), "dout": -(-k // tk), "out": 1}
    width = n if kernel == "bps_gmm" else k
    return {"lhs": -(-width // blocks[0]), "w": 1, "out": 1}


def _gmm_kernel(group_ref, num_ref, lhs_ref, rhs_ref, out_ref, *, transpose):
    del group_ref
    t = pl.program_id(1)

    @pl.when(t < num_ref[0])
    def _tile():
        dims = (((1,), (1,)), ((), ())) if transpose else (
            ((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


# jitted, like the kernels of ops/routed_rows.py: a step calls each some
# dozen times, and one traced and lowered function serves every call of a
# shape (a cell's set-up pays the lowering on every run, cached or not)
@functools.partial(jax.jit, static_argnames=("tile", "transpose", "interpret"))
def _gmm(lhs, w, tile_group, num_tiles, tile, transpose, interpret):
    """``lhs`` [rows, c] against ``w`` [g, k, n]: c = k and out [rows, n],
    or with ``transpose`` c = n and out [rows, k]."""
    rows, c = lhs.shape
    g, k, n = w.shape
    width = k if transpose else n
    name = "bps_gmm_dx" if transpose else "bps_gmm"
    (tn,), vmem = _blocks(name, k, n, tile, lhs.dtype.itemsize)

    def last(t, num):           # a step past the rows stays on the last tile
        return jnp.minimum(t, num[0] - 1)

    if transpose:
        rhs_spec = pl.BlockSpec(
            (1, tn, n), lambda j, t, grp, num: (grp[last(t, num)], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (1, k, tn), lambda j, t, grp, num: (grp[last(t, num)], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose=transpose),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(width, tn), rows // tile),
            in_specs=[
                pl.BlockSpec((tile, c),
                             lambda j, t, grp, num: (last(t, num), 0)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tile, tn), lambda j, t, grp, num: (last(t, num), j))),
        out_shape=jax.ShapeDtypeStruct((rows, width), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_GMM_DIMENSIONS, vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(tile_group, num_tiles, lhs, w)


def _gmm_dw_kernel(group_ref, num_ref, lhs_ref, dout_ref, out_ref, acc):
    t = pl.program_id(2)
    num = num_ref[0]
    here = group_ref[t]
    first = jnp.logical_or(t == 0, group_ref[jnp.maximum(t - 1, 0)] != here)
    final = jnp.logical_or(
        t == num - 1,
        group_ref[jnp.minimum(t + 1, pl.num_programs(2) - 1)] != here)

    @pl.when(t < num)
    def _tile():
        @pl.when(first)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(final)
        def _write():
            out_ref[0] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "tile", "interpret"))
def _gmm_dw(lhs, dout, tile_group, num_tiles, groups, tile, interpret):
    """[groups, k, n]: each group's ``lhs^T @ dout`` over its own rows.
    Every group has a tile (``moe.py`` pads an empty one to a tile of
    zero rows), so every block of the result is written."""
    rows, k = lhs.shape
    n = dout.shape[1]
    (tk, tn), vmem = _blocks("bps_gmm_dw", k, n, tile, lhs.dtype.itemsize)

    def last(t, num):
        return jnp.minimum(t, num[0] - 1)

    return pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(k, tk), pl.cdiv(n, tn), rows // tile),
            in_specs=[
                pl.BlockSpec((tile, tk),
                             lambda i, j, t, grp, num: (last(t, num), i)),
                pl.BlockSpec((tile, tn),
                             lambda i, j, t, grp, num: (last(t, num), j))],
            out_specs=pl.BlockSpec(
                (1, tk, tn),
                lambda i, j, t, grp, num: (grp[last(t, num)], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_DW_DIMENSIONS, vmem_limit_bytes=vmem),
        interpret=interpret,
        name="bps_gmm_dw",
    )(tile_group, num_tiles, lhs, dout)


def _embed_dw_kernel(group_ref, row_ref, num_ref, ids_ref, dout_ref, out_ref,
                     acc, *, scale, precision):
    del row_ref
    t = pl.program_id(1)
    num = num_ref[0]
    here = group_ref[t]
    first = jnp.logical_or(t == 0, group_ref[jnp.maximum(t - 1, 0)] != here)
    final = jnp.logical_or(
        t == num - 1,
        group_ref[jnp.minimum(t + 1, pl.num_programs(1) - 1)] != here)

    @pl.when(t < num)
    def _tile():
        block, tile = out_ref.shape[0], dout_ref.shape[0]
        # [block, tile]: the tile's row r is a token of this block's row
        # v where ids[r] == block * here + v; a row of another block (or
        # of no token: an id outside the vocabulary) matches none
        hit = (jax.lax.broadcasted_iota(jnp.int32, (block, tile), 0)
               == ids_ref[0] - here * block)
        part = jnp.dot(jnp.where(hit, 1.0, 0.0).astype(dout_ref.dtype),
                       dout_ref[...], precision=precision,
                       preferred_element_type=jnp.float32)

        @pl.when(first)
        def _start():
            acc[...] = part

        @pl.when(jnp.logical_not(first))
        def _add():
            acc[...] += part

        @pl.when(final)
        def _write():
            total = acc[...] if scale is None else acc[...] * scale
            out_ref[...] = total.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "vocab", "scale", "out_dtype", "interpret"))
def embed_dw(ids, dout, vocab, scale, out_dtype, interpret):
    """[vocab, n] in ``out_dtype``: ``scale * sum of dout[r] over the rows
    whose id is v``, summed in float32, for ``ids`` [rows] int32 in
    ascending order and ``dout`` [rows, n] in the same. ``rows`` in whole
    tiles of ``EMBED_TILE``; an id outside ``0 .. vocab - 1`` (a pad
    row's) is in no sum, and its row of ``dout`` must still be finite.
    bf16 rows are summed exactly; float32 rows by a product at fp32
    contract precision, since a float32 is no bf16."""
    rows, n = dout.shape
    block, tile = EMBED_BLOCK, EMBED_TILE
    groups, tiles = pl.cdiv(vocab, block), rows // tile
    steps = tiles + groups
    # block g's run of rows, the row tiles it lies in (an empty run: one),
    # and the grid step at which g's tiles start
    edge = jnp.searchsorted(ids, jnp.arange(groups + 1, dtype=jnp.int32)
                            * block, side="left").astype(jnp.int32)
    lo, hi = edge[:-1], edge[1:]
    first = jnp.minimum(lo // tile, tiles - 1)
    count = jnp.where(hi > lo, (hi - 1) // tile - first + 1, 1)
    start = jnp.cumsum(count) - count
    num_tiles = count.sum().astype(jnp.int32)[None]
    # steps past the last stay on its tile and its block: nothing moves
    step = jnp.minimum(jnp.arange(steps, dtype=jnp.int32), num_tiles - 1)
    tile_group = (jnp.searchsorted(start, step, side="right") - 1).astype(
        jnp.int32)
    tile_row = first[tile_group] + step - start[tile_group]
    tn = _cols(n, 1024)
    precision = (jax.lax.Precision.HIGHEST if dout.dtype == jnp.float32
                 else None)
    return pl.pallas_call(
        functools.partial(_embed_dw_kernel, scale=scale, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), steps),
            in_specs=[
                pl.BlockSpec((1, 1, tile),
                             lambda j, t, grp, row, num: (row[t], 0, 0)),
                pl.BlockSpec((tile, tn),
                             lambda j, t, grp, row, num: (row[t], j))],
            out_specs=pl.BlockSpec(
                (block, tn), lambda j, t, grp, row, num: (grp[t], j)),
            scratch_shapes=[pltpu.VMEM((block, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((vocab, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_GMM_DIMENSIONS),
        interpret=interpret,
        name="bps_embed_dw",
    )(tile_group, tile_row, num_tiles, ids.reshape(tiles, 1, tile), dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm_vjp(lhs, w, tile_group, num_tiles, tile, interpret):
    return _gmm(lhs, w, tile_group, num_tiles, tile, False, interpret)


def _gmm_vjp_fwd(lhs, w, tile_group, num_tiles, tile, interpret):
    out = _gmm(lhs, w, tile_group, num_tiles, tile, False, interpret)
    return out, (lhs, w, tile_group, num_tiles)


def _gmm_vjp_bwd(tile, interpret, res, dout):
    lhs, w, tile_group, num_tiles = res
    dlhs = _gmm(dout, w, tile_group, num_tiles, tile, True, interpret)
    dw = _gmm_dw(lhs, dout, tile_group, num_tiles, w.shape[0], tile,
                 interpret)
    return dlhs, dw, None, None


_gmm_vjp.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def supported(lhs_shape, w_shape, tile: int) -> bool:
    """Shapes the kernels take: widths in whole 128-lane tiles or ending
    in a half one, rows in whole row tiles of a multiple of 128."""
    rows, _ = lhs_shape
    _, k, n = w_shape
    return (tile % 128 == 0 and rows % tile == 0 and k % HALF_LANES == 0
            and n % HALF_LANES == 0)


def grouped_matmul(lhs, w, tile_group, num_tiles, group_rows, tile: int,
                   impl: str = "auto"):
    """``out[r] = lhs[r] @ w[group of r]`` for the rows of the first
    ``num_tiles`` row tiles; ``tile_group`` [rows // tile] int32 names
    each tile's group (tiles past ``num_tiles`` repeat the last one's),
    ``num_tiles`` is [1] int32, ``group_rows`` [groups] int32 the padded
    rows of each group (what ``lax.ragged_dot`` is given off the TPU).

    impl: "auto" (the kernels on the TPU where ``supported``), "gmm",
    "gmm_interpret" (the kernels in Pallas' interpreter: tests) or
    "ragged" (``lax.ragged_dot``)."""
    if impl not in ("auto", "gmm", "gmm_interpret", "ragged"):
        raise ValueError(f"grouped_matmul impl {impl!r}")
    asked = impl
    if impl == "auto":
        impl = ("gmm" if jax.default_backend() == "tpu"
                and supported(lhs.shape, w.shape, tile) else "ragged")
    note_choice("grouped_matmul", "ragged" if impl == "ragged" else "gmm",
                (tuple(lhs.shape), tuple(w.shape), tile),
                "lax.ragged_dot: the kernels need widths in whole or half "
                "lane tiles and rows in whole row tiles of a multiple of 128",
                asked=asked)
    if impl == "ragged":
        return jax.lax.ragged_dot(lhs, w, group_rows,
                                  preferred_element_type=lhs.dtype)
    return _gmm_vjp(lhs, w, tile_group, num_tiles, tile,
                    impl == "gmm_interpret")

"""The row movement of a routed feed-forward layer as Pallas TPU kernels
(``models/moe.py::routed_ffn``): rows to the experts' buffer and results
back to their tokens, in time that follows the rows routed this step.

The buffer of rows is sized for the worst routing and only its first
``num_tiles`` row tiles hold rows (``ops/grouped_matmul.py``). XLA's
gathers cannot know that: by row they walk the whole buffer, by pair all
``tokens * top_k`` chosen pairs. These two read the plan instead:

  - ``bps_moe_take``     out[r] = src[index[r]] (* scale[r]), zeros in a
    pad row (index past ``src``), for the rows of the first
    ``num_tiles`` tiles; tiles behind them are neither read nor written.
    A grid step a row tile, skipped past the rows like ``bps_gmm``'s. A
    row is copied by a DMA of its own from HBM. A DMA moves whole
    (8, 128) tiles (16 rows of bf16), so ``src`` is first laid out a row
    a tile (``[n * h / 128, 128]``: a reshape of ``tokens`` rows, not of
    the buffer), and the copied tile is put back as ``[tile, h]`` in VMEM.
    A row that is no whole number of such tiles (hidden 2688 in bf16: 21
    rows of 128 lanes, a tile holds 16) is padded to the next whole one
    in that copy of ``src`` (32: 4096 lanes), and the pad stays in VMEM.
  - ``bps_moe_combine``  out[t] = sum_j weights[t, j] * y[dest[t, j]]
    over the pairs that have a row here, or with ``d_out`` the products
    ``y[dest[t, j]] . d_out[t]``. A grid step a tile of tokens. An
    expert's rows come sorted by token, so the rows a token tile has in
    one expert's run are CONTIGUOUS: ``lo .. hi`` (``tile_bounds``). The
    step copies a window of rows from each expert's run (``_WINDOW``
    rows, further passes where a run has more) and sums them into their
    tokens by one product on the MXU, ``a @ rows`` with ``a[t, r]`` the
    weight of the pair of token ``t`` whose row is ``r`` (the transpose of
    ``bps_gmm_dw``'s shape). fp32 accumulation, weights in ``y``'s dtype
    as the einsum it replaces had them.

Pad rows INSIDE a live tile are written as zeros: ``bps_gmm_dw`` sums
over every row of a live tile. Off the TPU both are ``jnp.take`` with
fill (``impl`` "ragged"), as ``grouped_matmul`` is ``lax.ragged_dot``.

Which widths run the kernels (``resolve``): the hidden size in whole
128-lane tiles; the experts' width as ``grouped_matmul.supported`` takes
it (whole lane tiles or ending in a half one), since these kernels leave
unwritten what only the grouped kernels know not to read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.setup_record import note_choice
from .grouped_matmul import HALF_LANES

_LANES = 128
_TOKEN_TILE = 512       # tokens a grid step of bps_moe_combine
_WINDOW = 64            # rows a pass copies of one expert's run
_UNROLL = 8             # row copies issued a loop step of bps_moe_take
_MAX_SPAN = 4096        # held * _WINDOW: the rows a pass stages and sums
# of the chip's 128 MiB: a step holds about 20, and what a kernel reserves
# XLA cannot use to keep its neighbours' operands in VMEM
_VMEM_LIMIT = 32 << 20


def _row_align(dtype) -> int:
    """Rows of a (8, 128) tile of 32-bit words."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _token_tile(tokens: int) -> int:
    """Tokens a grid step of ``bps_moe_combine``: all, where they do not
    come in whole tiles (``resolve`` then refuses more than a tile)."""
    return _TOKEN_TILE if tokens % _TOKEN_TILE == 0 else tokens


def resolve(impl: str, tokens: int, hidden: int, width: int, held: int,
            tile: int) -> str:
    """How the rows of a layer travel under ``grouped_matmul``'s ``impl``:
    "gmm" or "gmm_interpret" (the two kernels; "auto": on the TPU) where
    they take the shapes, else "ragged" (XLA's gathers). Never the kernels
    around ``lax.ragged_dot``, which may read what they leave unwritten:
    so ``width``, the experts', as the grouped kernels take it (whole lane
    tiles or ending in a half one) and ``tile`` in whole 128s. The
    compiled kernels move a row as whole (8, 128) tiles (``_take`` pads a
    row that is none); the interpreter does not care."""
    asked = impl
    if impl == "auto":
        impl = "gmm" if jax.default_backend() == "tpu" else "ragged"
    tt = _token_tile(tokens)
    fits = (hidden % _LANES == 0 and width % HALF_LANES == 0
            and tile % _LANES == 0 and tt <= _TOKEN_TILE and tt % 8 == 0
            and held * _WINDOW <= _MAX_SPAN)
    impl = impl if fits else "ragged"
    note_choice("routed_rows", "ragged" if impl == "ragged" else "gmm",
                (tokens, hidden, width, held, tile),
                "XLA's gathers: the kernels need a hidden size in whole lane "
                "tiles, the experts' width as the grouped kernels take it and "
                "tokens in whole tiles of 512 or one tile of whole 8s",
                asked=asked)
    return impl


def take_xla(x, index):
    """Rows of ``x`` by ``index``; zeros where the index is past the end."""
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


# ------------------------------------------------------------- bps_moe_take

def _take_kernel(num_ref, idx_ref, *rest, sub, n_src, scaled):
    scale_ref = rest[0] if scaled else None
    src_ref, out_ref, stage, sem = rest[scaled:]
    tile, h = out_ref.shape

    @pl.when(pl.program_id(0) < num_ref[0])
    def _tile():
        def start(r, issued):
            i = idx_ref[0, 0, r]
            there = jnp.logical_and(i >= 0, i < n_src)
            dst = pl.ds(pl.multiple_of(r * sub, sub), sub)

            @pl.when(there)
            def _copy():
                pltpu.make_async_copy(
                    src_ref.at[pl.ds(pl.multiple_of(i * sub, sub), sub)],
                    stage.at[dst], sem).start()

            @pl.when(jnp.logical_not(there))
            def _pad():
                stage[dst, :] = jnp.zeros((sub, _LANES), stage.dtype)

            return issued + there.astype(jnp.int32)

        def start_some(q, issued):      # unrolled by hand: Mosaic's loops
            for u in range(_UNROLL):    # unroll wholly or not at all
                issued = start(q * _UNROLL + u, issued)
            return issued

        issued = jax.lax.fori_loop(0, tile // _UNROLL, start_some,
                                   jnp.int32(0))

        def wait(_, carry):     # every copy signals as many bytes
            pltpu.make_async_copy(src_ref.at[pl.ds(0, sub)],
                                  stage.at[pl.ds(0, sub)], sem).wait()
            return carry

        jax.lax.fori_loop(0, issued, wait, 0)
        rows = stage[...].reshape(tile, sub * _LANES)
        if sub * _LANES != h:   # a row padded to whole tiles: drop the pad
            rows = rows[:, :h]
        if scaled:              # [1, tile] along lanes -> a row's own lanes
            by_row = jnp.broadcast_to(scale_ref[0], (_LANES, tile)).T
            for c in range(h // _LANES):
                lanes = slice(c * _LANES, (c + 1) * _LANES)
                out_ref[:, lanes] = (rows[:, lanes].astype(jnp.float32)
                                     * by_row).astype(out_ref.dtype)
        else:
            out_ref[...] = rows


# jitted: a step calls each kernel a dozen times, and one traced and lowered
# function serves every call of its kind (20 s of a cell's set-up otherwise)
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _take(src, index, num_tiles, tile, scale, interpret):
    rows = index.shape[0]
    n_src, h = src.shape
    align = _row_align(src.dtype)
    sub = -(-h // (_LANES * align)) * align     # whole (8, 128) tiles a row
    if sub * _LANES != h:
        src = jnp.pad(src, ((0, 0), (0, sub * _LANES - h)))
    scaled = scale is not None

    def last(t, num):           # a step past the rows stays on the last tile
        return jnp.minimum(t, num[0] - 1)

    def a_tile(t, num):
        return last(t, num), 0, 0

    by_tile = (rows // tile, 1, tile)
    in_specs = [pl.BlockSpec((1, 1, tile), a_tile, memory_space=pltpu.SMEM)]
    args = [index.reshape(by_tile)]
    if scaled:
        in_specs.append(pl.BlockSpec((1, 1, tile), a_tile))
        args.append(scale.astype(src.dtype).astype(jnp.float32).reshape(
            by_tile))           # rounded as the product's operand
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    args.append(src.reshape(n_src * sub, _LANES))       # a row a tile
    return pl.pallas_call(
        functools.partial(_take_kernel, sub=sub, n_src=n_src, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // tile,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, h),
                                   lambda t, num: (last(t, num), 0)),
            scratch_shapes=[pltpu.VMEM((tile * sub, _LANES), src.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((rows, h), src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="bps_moe_take",
    )(num_tiles, *args)


def take_rows(src, index, num_tiles, tile: int, scale=None,
              impl: str = "ragged"):
    """[rows, h]: ``src[index[r]]``, times ``scale[r]`` (fp32 [rows])
    where given, zeros where ``index[r]`` is no row of ``src``; with the
    kernel (``impl`` "gmm" / "gmm_interpret") only for the rows of the
    first ``num_tiles`` row tiles, what lies behind them is not written."""
    if impl == "ragged":
        out = take_xla(src, index)
        return out if scale is None else out * scale[:, None].astype(
            out.dtype)
    return _take(src, index, num_tiles, tile=tile, scale=scale,
                 interpret=impl == "gmm_interpret")


# ---------------------------------------------------------- bps_moe_combine

def tile_bounds(slot, group_rows):
    """What ``bps_moe_combine`` reads of the plan, a dict of int32 arrays.
    ``lo`` and ``hi`` [token tiles * held]: the rows that the pairs of a
    token tile have in held expert g's run are ``lo .. hi``, one after
    another, because a run is sorted by pair; ``lanes`` [token tiles, 2,
    held * _WINDOW]: the same two, each expert's repeated over the lanes
    of its window; ``live`` [1]: the rows of the live tiles (behind them
    nothing is read). ``slot`` [T, k]: the pair's held expert, or
    ``held`` for none; ``group_rows`` [held]: the padded rows of each
    run, as ``grouped_matmul`` takes them."""
    t, k = slot.shape
    held = group_rows.shape[0]
    tt = _token_tile(t)
    count = (slot.reshape(t // tt, tt * k, 1)
             == jnp.arange(held, dtype=jnp.int32)).sum(1, dtype=jnp.int32)
    run = jnp.cumsum(group_rows) - group_rows
    lo = run + jnp.cumsum(count, 0) - count
    hi = lo + count
    return {"lo": lo.reshape(-1), "hi": hi.reshape(-1),
            "lanes": jnp.repeat(jnp.stack([lo, hi], 1), _WINDOW, axis=2),
            "live": group_rows.sum()[None]}


def _combine_kernel(lo_ref, hi_ref, live_ref, lanes_ref, dest_ref, *rest,
                    held, weighted, products):
    w_ref = rest[0] if weighted else None
    y_ref = rest[weighted]
    rest = rest[weighted + 1:]
    if products:
        dout_ref, out_ref, stage, sem = rest
    else:
        out_ref, stage, acc, sem = rest
    i = pl.program_id(0)
    tt, k = dest_ref.shape
    window, align = _WINDOW, _row_align(stage.dtype)
    span = held * window

    @pl.when(i == 0)
    def _clean():       # a window's stale rows are multiplied by zero
        stage[...] = jnp.zeros_like(stage)

    def first_of(lo, hi):
        """A run's first window starts whole tiles down from its first
        row; an empty run has no window."""
        return jnp.where(hi > lo, lo // align * align, hi)

    def window_of(g, p):
        """(whether expert g has a p-th window, the row it starts on)."""
        lo, hi = lo_ref[i * held + g], hi_ref[i * held + g]
        begin = first_of(lo, hi) + p * window
        # a window ends inside the live tiles: what lies behind them was
        # never written, and 0 * whatever it holds is not 0
        return begin < hi, pl.multiple_of(
            jnp.minimum(begin, live_ref[0] - window), align)

    def windows(g, most):
        lo, hi = lo_ref[i * held + g], hi_ref[i * held + g]
        return jnp.maximum(most, (hi - first_of(lo, hi) + window - 1)
                           // window)

    passes = jax.lax.fori_loop(0, held, windows, jnp.int32(0))
    lo_lane, hi_lane = lanes_ref[0, 0:1, :], lanes_ref[0, 1:2, :]
    first_lane = first_of(lo_lane, hi_lane)
    within = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1) % window
    dest = dest_ref[...]
    if products:
        out_ref[...] = jnp.zeros_like(out_ref)

    def one_pass(p, carry):
        def fetch(g, _):
            there, base = window_of(g, p)

            @pl.when(there)
            def _copy():
                pltpu.make_async_copy(
                    y_ref.at[pl.ds(base, window)],
                    stage.at[pl.ds(pl.multiple_of(g * window, window),
                                   window)], sem.at[g]).start()
            return _

        def wait(g, _):
            @pl.when(window_of(g, p)[0])
            def _done():
                pltpu.make_async_copy(
                    y_ref.at[pl.ds(0, window)],
                    stage.at[pl.ds(pl.multiple_of(g * window, window),
                                   window)], sem.at[g]).wait()
            return _

        jax.lax.fori_loop(0, held, fetch, 0)
        # lane -> the row of y it holds this pass, or none: as window_of
        begin = first_lane + p * window
        mine = jnp.minimum(begin, live_ref[0] - window) + within
        row = jnp.where(
            (begin < hi_lane) & (mine >= jnp.maximum(lo_lane, begin))
            & (mine < jnp.minimum(hi_lane, begin + window)), mine, -1)
        if not products:        # while the copies fly
            a = jnp.zeros((tt, span), jnp.float32)
            for j in range(k):
                a += jnp.where(dest[:, j:j + 1] == row,
                               w_ref[:, j:j + 1] if weighted else 1.0, 0.0)
            a = a.astype(stage.dtype)
        jax.lax.fori_loop(0, held, wait, 0)
        if products:
            dots = jax.lax.dot_general(
                dout_ref[...], stage[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [tt, span]
            col = jax.lax.broadcasted_iota(jnp.int32, (tt, k), 1)
            found = jnp.zeros((tt, k), jnp.float32)
            for j in range(k):
                found = jnp.where(col == j, jnp.sum(
                    jnp.where(dest[:, j:j + 1] == row, dots, 0.0), axis=1,
                    keepdims=True), found)
            out_ref[...] += found
        else:
            part = jnp.dot(a, stage[...], preferred_element_type=jnp.float32)

            @pl.when(passes == 1)       # nearly always: no accumulator
            def _only():
                out_ref[...] = part.astype(out_ref.dtype)

            @pl.when((passes > 1) & (p == 0))
            def _first():
                acc[...] = part

            @pl.when(p > 0)
            def _further():
                acc[...] += part
        return carry

    jax.lax.fori_loop(0, passes, one_pass, 0)
    if not products:
        @pl.when(passes == 0)
        def _none():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(passes > 1)
        def _sum():
            out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tt", "interpret"))
def _combine(y, dest, weights, lo, hi, live, lanes, d_out, tt, interpret):
    t, k = dest.shape
    h = y.shape[1]
    held = lo.shape[0] // (t // tt)
    weighted, products = weights is not None, d_out is not None
    narrow = pl.BlockSpec((tt, k), lambda i, *_: (i, 0))
    wide = pl.BlockSpec((tt, h), lambda i, *_: (i, 0))
    in_specs = [pl.BlockSpec((1,) + lanes.shape[1:], lambda i, *_: (i, 0, 0)),
                narrow]
    args = [lanes, dest]
    if weighted:        # rounded as the product's operand, as the einsum's
        in_specs.append(narrow)
        args.append(weights.astype(y.dtype).astype(jnp.float32))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    args.append(y)
    scratch = [pltpu.VMEM((held * _WINDOW, h), y.dtype)]
    if products:
        in_specs.append(wide)
        args.append(d_out)
        out_spec, out_shape = narrow, jax.ShapeDtypeStruct((t, k),
                                                           jnp.float32)
    else:
        out_spec, out_shape = wide, jax.ShapeDtypeStruct((t, h), y.dtype)
        scratch.append(pltpu.VMEM((tt, h), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((held,)))
    return pl.pallas_call(
        functools.partial(_combine_kernel, held=held, weighted=weighted,
                          products=products),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(t // tt,), in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),   # _clean runs first
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="bps_moe_combine",
    )(lo, hi, live, *args)


def combine_rows(y, dest, weights=None, bounds=None, d_out=None,
                 impl: str = "ragged"):
    """[T, h]: ``sum_j weights[t, j] * y[dest[t, j]]`` (weights of one
    where none are given) over the pairs whose ``dest`` is a row of
    ``y``; with ``d_out`` [T, h] instead [T, k] fp32: the pair's row of
    ``y`` times the token's row of ``d_out``, zero where it has no row.
    ``bounds``: ``tile_bounds`` (or the plan that holds them): the kernel
    reads them."""
    if impl == "ragged":
        rows = take_xla(y, dest.reshape(-1)).reshape(dest.shape + y.shape[1:])
        if d_out is not None:
            return jnp.einsum("tkh,th->tk", rows, d_out,
                              preferred_element_type=jnp.float32)
        if weights is None:
            return rows.sum(1)
        return jnp.einsum("tkh,tk->th", rows, weights.astype(y.dtype))
    return _combine(y, dest, weights, bounds["lo"], bounds["hi"],
                    bounds["live"], bounds["lanes"], d_out,
                    tt=_token_tile(dest.shape[0]),
                    interpret=impl == "gmm_interpret")

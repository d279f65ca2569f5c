"""Flash attention as Pallas TPU kernels (forward + backward).

The reference delegates all model math to torch/tf/mxnet (SURVEY §5
"Long-context: entirely absent"); here attention is the FLOPs/HBM hot
spot of the flagship BERT/GPT benchmarks, so it gets a hand-written
kernel pair:

  - forward: blockwise online-softmax attention — the [s, s] score
    matrix never leaves VMEM; O(s·block) HBM traffic instead of O(s²);
    a plain softmax with no carried state where one kv block holds the
    row's keys (up to 1024 by default: BERT's and GPT-2's shapes)
  - backward: two kernels (dq; dk+dv) recomputing probabilities from the
    saved log-sum-exp, the standard flash-attention-2 scheme; one fused
    kernel where the sequence is one block pair
  - fp32 accumulation on the MXU (`preferred_element_type`), bf16 inputs
  - causal masking by block skipping + an iota mask on diagonal blocks
  - the rows' statistics (lse, delta) cross HBM with the sequence on
    the lane axis, never as [.., s, 1] columns (padded 128x there)
  - one additive score term, T5's relative-position table, made inside
    the kernels from block offsets (``rel_table``)

The five kernel bodies (the online and the single-block forward; the dq,
the dk/dv and the fused backward) get a block's scores from ONE function
and its mask from ONE (``_scores``, ``_mask``), and the backward bodies
their probabilities and dL/dS from two more (``_probs``, ``_dscores``):
a mechanism that changes how scores or the mask are made is an edit
there, not five.

Layout contract matches the rest of the stack: [batch, seq, heads,
head_dim] in, same out. Kernels run per (batch, head tile) over a grid
of sequence blocks; the kv-block loop is the innermost grid dimension so
the accumulator scratch lives in VMEM across it. Across HBM the operands
travel head-major, [b, heads, s, d], or where a head's width divides a
128-lane tile lane-dense, [b, s, heads*d]: ``_lane_dense`` chooses from
the call's shapes (the note on narrow heads, below).

`attention()` is the dispatcher the models call: Pallas on TPU when
shapes allow, pure-JAX blockwise otherwise (CPU tests, odd shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.setup_record import note_choice

_NEG_INF = -1e30
# what the kernels' backward reads of their forward, by the names a
# ``jax.checkpoint`` policy keeps them under (``_fwd_rule``): the output
# and the rows' log-sum-exp. The models' checkpoints save them by default.
SAVED_NAMES = ("flash_out", "flash_lse")

# every kernel's grid is (outer..., carried): only the innermost dim
# carries scratch state across iterations; the rest are independent
# programs the pipeliner may reorder/overlap
_DIM_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _pick_block(s: int, want: int) -> int:
    for b in (want, 512, 256, 128):
        if b <= want and s % b == 0:
            return b
    return s


# ---- a causal band (``window``) and grouped kv heads ----
# ``window`` = w: query i sees key j where 0 <= i - j < w. A q block then
# meets only the kv blocks its band touches, and the grid's kv dimension
# is that many steps long, not sk // bk: the step's kv block is
# ``_band_lo_k(q block) + step``, skipped where it passes the diagonal
# (the index maps clamp it there, so a skipped step moves nothing). The
# dk/dv kernel walks the q blocks of a kv block's band the same way.
# Grouped kv heads (``group`` = query heads a kv head): q, out, do and
# the statistics are FOLDED, [b, hq, s, ..] -> [b, hkv, group * s, ..],
# a free reshape, so that a kv head's queries are consecutive q blocks
# of one kernel row: block ``i`` of the fold is position block
# ``i % nq``; k and v are read as they are, never repeated, and the
# dk/dv kernel's carried loop over the fold's q blocks IS the sum over
# the group. With no window and group 1 every kernel traces as before.

def _band_lo_k(qb, bq, bk, window):
    """First kv block that q block ``qb``'s band touches."""
    return jnp.maximum(qb * bq - (window - 1), 0) // bk


def _band_steps_k(nq, bq, bk, window):
    """kv steps a q block needs at most (static)."""
    return max((i * bq + bq - 1) // bk - max(i * bq - (window - 1), 0) // bk
               + 1 for i in range(nq))


def _band_steps_q(nq, nk, bq, bk, window):
    """q steps a kv block needs at most (static): from its own diagonal
    block to the last q block whose band still reaches it."""
    return max(min(nq - 1, (i * bk + bk + window - 2) // bq)
               - (i * bk) // bq + 1 for i in range(nk))


def _visible(rows, cols, window):
    """The causal mask, cut to a band of ``window`` keys where given."""
    if window is None:
        return rows >= cols
    return jnp.logical_and(rows >= cols, rows - cols < window)


def _positions(shape, axis, first=None):
    """Positions in the sequence along ``axis`` of a [rows, keys] block
    that starts at ``first``: (block index, block size), or for the
    single block's row chunks (block index, block size, rows into the
    block); None where the block starts at 0 and the body adds nothing
    (adding a zero is an instruction)."""
    start = None
    if first is not None:
        block, size, *into = first
        start = block * size
        if into:
            start = start + into[0]
    at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return at if start is None else start + at


def _mask(shape, window, row0=None, col0=None):
    """What a causal call lets a [rows, keys] block see (``_visible``):
    the ONE place the kernels make their mask. ``row0`` and ``col0`` say
    where the block's first row and first key stand (``_positions``)."""
    return _visible(_positions(shape, 0, row0), _positions(shape, 1, col0),
                    window)


def _fold(x, group):
    """[b, hq, s, ...] -> [b, hq // group, group * s, ...]."""
    if group == 1:
        return x
    b, h, s = x.shape[:3]
    return x.reshape((b, h // group, group * s) + x.shape[3:])


def _unfold(x, group):
    if group == 1:
        return x
    b, hk, gs = x.shape[:3]
    return x.reshape((b, hk * group, gs // group) + x.shape[3:])


# ---- heads narrower than a lane tile: [b, s, heads*d] across HBM ----
# An HBM tile is 128 lanes wide. Head-major [b, h, s, d] puts d on the
# lanes: at d = 128 every tile is full, at BERT's and GPT-2's 64 every
# tile of q, k, v, out and their cotangents is half empty, and the
# swapaxes to and from the projections' [b, s, heads, d] are copies
# between two padded layouts (PR 32: 138 of a 712 ms BERT-large step).
# So where d divides 128 those eight cross HBM as the projections write
# and read them, [b, s, heads*d], a reshape of the public layout: a
# block is (1, rows, ht*d) with ht*d whole lane tiles, the head tile on
# the lane axis. Inside a kernel a head is the lanes t*d to (t+1)*d of
# its block, and no lane moves: an operand is the whole 128-lane tile
# the head lies in, its neighbours beside it (``_take``), a product
# over the tile's lanes costs the MXU what one over d of them does
# (either half-fills a 128 x 128 pass), and a result's own d lanes are
# stored where they already sit (``_put``). Handing a product a head's
# [rows, d] slice instead has Mosaic rotate every odd head to lane 0
# and back (measured, PR 33, the kernels alone: BERT-large's fused
# backward 2.25 ms a call against 2.07, seq 128's forward 0.90 against
# 0.66). The rows' statistics keep their own layout (below). The bodies
# are the head-major ones: mask, softmax and the five products do not
# know which layout handed them a head. ``_lane_dense`` says which
# calls take it; every other call, d = 128 first, traces as it did
# before the layout existed.

def _take(ref, t, lanes=None, rows=None, alone=False):
    """Head ``t`` of a block of q, k, v or do as a product's operand.
    Head-major, block (1, ht, rows, d): its [rows, d]. Lane-dense
    (``lanes`` = d), block (1, rows, ht*d): the [rows, 128] lane tile
    the head lies in. ``alone`` zeroes the neighbours' lanes there: an
    operand contracted over its lanes with the neighbours still in the
    other one (q in q k', do in do v') must be; a product that keeps the
    lanes (p v, ds k, ds' q, p' do) gives the neighbours' columns beside
    the head's own, which ``_put`` leaves behind. ``rows``: a slice of
    the block's rows, default all."""
    if lanes is None:
        return ref[(0, t) if rows is None else (0, t, rows)]
    lo = t * lanes // 128 * 128
    x = ref[0, slice(None) if rows is None else rows, lo:lo + 128]
    if alone:
        lane = lo + jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        own = jnp.logical_and(lane >= t * lanes, lane < (t + 1) * lanes)
        x = jnp.where(own, x, jnp.zeros_like(x))
    return x


def _put(ref, t, value, lanes=None, rows=None):
    """Head ``t``'s result into its place in a block of out, dq, dk or
    dv (laid out as in ``_take``), cast to the block's dtype; lane-dense,
    ``value`` is [rows, 128] and its lanes outside the head's are not
    stored."""
    if lanes is None:
        ref[(0, t) if rows is None else (0, t, rows)] = value.astype(
            ref.dtype)
        return
    lo = t * lanes % 128
    ref[0, slice(None) if rows is None else rows,
        t * lanes:(t + 1) * lanes] = value[:, lo:lo + lanes].astype(ref.dtype)


def _rows_spec(ht, rows, d, dense, at):
    """BlockSpec of ``ht`` heads' ``rows`` rows of such an operand;
    ``at`` maps the grid's indices to (batch, head tile, row block)."""
    if dense:
        def lane_block(*grid):
            ib, ih, ir = at(*grid)
            return ib, ir, ih
        return pl.BlockSpec((1, rows, ht * d), lane_block)
    return pl.BlockSpec((1, ht, rows, d), lambda *grid: (*at(*grid), 0))


def _dims(q, k, heads):
    """(b, query heads, kv heads, sq, sk, d) of head-major q and k, or
    with ``heads`` given of lane-dense ones."""
    if heads is None:
        b, hq, sq, d = q.shape
        return b, hq, k.shape[1], sq, k.shape[2], d
    b, sq, lanes = q.shape
    return b, heads, heads, sq, k.shape[1], lanes // heads


# ---- row statistics (lse, delta): lane-dense across HBM ----
# Outside the kernel bodies a row statistic is [b, h, s] fp32. Across a
# pallas_call boundary it is [b, h, 1, s] in blocks of (1, ht, 1, bq):
# the sequence on the lane axis, 4 bytes a row of attention in HBM, and
# a block form that is legal for every head tile (the unit dim is the
# whole second-minor axis). A [b, h, s, 1] column would be tiled
# (8, 128) like any fp32 array: its unit dim pads to 128 lanes, 512
# bytes a row (BERT-large 64 x 512: 268 MB a buffer; measured, PR 27:
# 0.5 GB of the step's peak memory, 0.45 ms of a seq-128 backward call,
# 0.15 ms of a split one; the forward hid the padded write behind its
# compute). Inside a body the statistic meets the [bq, bk] scores as a
# [bq, 1] column, so each kernel converts once per block it loads
# (_load_stat) or stores (_store_stat).

def _diagonal(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _chunk(n):
    """Rows converted at a time: one 128-lane row wherever the block is
    whole ones (always on the TPU: ``supported``), else the block."""
    return 128 if n % 128 == 0 else n


def _store_stat(ref, t, col, start=0):
    """Head ``t``'s [n, 1] column into its [1, n] row of a statistic's
    block, from row ``start`` of the block on. Per chunk: spread the
    column over the lanes, keep the diagonal of the square, sum over the
    sublanes. Exact: every sum adds one value to zeros."""
    n, c = col.shape[0], _chunk(col.shape[0])
    eye = _diagonal(c)
    for i in range(0, n, c):
        ref[0, t, :, start + i:start + i + c] = jnp.sum(
            jnp.where(eye, col[i:i + c], 0.0), axis=0, keepdims=True)


def _load_stat(ref, t):
    """Head ``t``'s [1, n] row of a statistic's block as an [n, 1]
    column: the inverse of ``_store_stat`` (spread the row over the
    sublanes, keep the diagonal, sum over the lanes)."""
    n = ref.shape[-1]
    c = _chunk(n)
    eye = _diagonal(c)
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, ref[0, t, :, i:i + c], 0.0), axis=1,
                 keepdims=True) for i in range(0, n, c)], axis=0)


# ---- in-kernel T5 relative-position bias (see ops/relpos.py) ----
# The bucket index depends only on (col - row), so each (qb, kb) block
# derives its [bq, bk] bucket map from iotas and folds the small
# [heads, num_buckets] table into the scores — NO [h, sq, sk] bias in
# HBM, which is what keeps relative-bias self-attention O(s) memory at
# long sequence lengths.

def _bucket_block(qb, kb, bq, bk, bidirectional, nb, maxd):
    from .relpos import relative_position_bucket
    rows = _positions((bq, bk), 0, (qb, bq))
    cols = _positions((bq, bk), 1, (kb, bk))
    return relative_position_bucket(cols - rows, bidirectional, nb, maxd)


def _table_bias(table_vec, bucket, nb):
    """[nb] table row + [bq, bk] bucket map → [bq, bk] bias. An
    unrolled select-sum (nb is 32): cheap VPU work next to the block's
    two MXU matmuls; a gather would not vectorize on TPU."""
    bias = jnp.zeros(bucket.shape, jnp.float32)
    for b in range(nb):
        bias = bias + jnp.where(bucket == b, table_vec[b], 0.0)
    return bias


def _rel_row(rel_ref, ih, ht, t):
    """Head (ih·ht + t)'s [nb] table row. The table rides as ONE
    full-array block (TPU block rules reject a (ht, nb) tile when
    ht < 8 — and the whole table is ~1 KB anyway). The row index is
    dynamic in the grid's head coordinate and Pallas TPU cannot lower
    dynamic_slice on values, so the row is selected by a masked
    reduction over the (tiny) head dim."""
    tab = rel_ref[...]                                   # [h, nb]
    idx = ih * ht + t
    mask = (jax.lax.broadcasted_iota(jnp.int32, tab.shape, 0)
            == idx)
    return jnp.sum(jnp.where(mask, tab, 0.0), axis=0)    # [nb]


# dtable output tile: padded to the minimum legal TPU block (8
# sublanes × 128 lanes); rows ≥ ht and lanes ≥ nb are zero
_DT_PAD = (8, 128)


def _clamp_ht(ht: int, h: int) -> int:
    """Clamp a head tile to the dtable row bound (_DT_PAD[0]) while
    keeping h % ht == 0. A plain min() can break divisibility — e.g. a
    tile of 12 with h=12 clamps to 8, the grid covers only heads 0-7,
    and the kernel silently emits garbage for the rest — so fall back
    to the largest divisor of h that fits the bound."""
    clamped = min(ht, _DT_PAD[0])
    while clamped > 1 and h % clamped != 0:
        clamped -= 1
    if clamped != ht:
        from ..common.logging import get_logger
        get_logger().warning(
            "rel_table head tile clamped %d -> %d (dtable rows are "
            "hard-sized to %d and h=%d must divide)", ht, clamped,
            _DT_PAD[0], h)
    return clamped


def _table_grad(ds32, bucket, nb):
    """dL/d(table row), padded to the _DT_PAD lane count: sum of dS
    over positions in each bucket."""
    g = jnp.stack([jnp.sum(jnp.where(bucket == b, ds32, 0.0))
                   for b in range(nb)])
    return jnp.pad(g, (0, _DT_PAD[1] - nb))


# ---- a block's scores, and what every backward body makes of them ----
# Written once for the five kernel bodies below, with ``_mask`` above:
# what stays in a body is what makes it that body (the online softmax's
# state, the single block's plain softmax over its row chunks, which of
# dq / dk / dv it accumulates and where it puts them). The backward's
# share is two functions and not one because the dk/dv and the fused
# bodies put dv's product between p and dp.

def _scores(q, k, scale, rel_ref=None, bucket=None, head=None):
    """A block's scores, [rows, keys] float32: q k' * scale and, with a
    ``rel_table``, T5's relative-position term on top, S = q k' * scale
    + B, folded in BEFORE the softmax. ``rel_ref`` is the table's ref,
    ``bucket`` the block's bucket map (``_bucket_block``: shared by the
    heads) and ``head`` = (ih, ht, t) the head's place (``_rel_row``)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if rel_ref is not None:
        row = _rel_row(rel_ref, *head)
        s = s + _table_bias(row.astype(jnp.float32), bucket,
                            rel_ref.shape[1])
    return s


def _probs(s, lse, mask=None):
    """A backward body's probabilities, recomputed from the scores and the
    saved log-sum-exp ([rows, 1]); ``mask`` is ``_mask``'s arguments in a
    causal call, and what it hides is zero."""
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(_mask(*mask), p, 0.0)
    return p


def _dscores(p, do, v, delta=None, delta_at=None):
    """dL/dS of a block, float32: p * (dp - delta) with dp = do v'. The
    rows' correction ``delta`` ([rows, 1]) as the split bodies load it
    beside lse; or loaded here, after dp, from a statistic's block
    ``delta_at`` = (ref, head) (the fused body under a ring caller's
    GLOBAL delta); or with neither made in place, sum_j p_ij dp_ij, which
    is right only where the block holds the row's every key (the fused
    body: see ``_dqkv_fused_kernel``)."""
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [rows, keys]
    if delta_at is not None:
        delta = _load_stat(*delta_at)
    elif delta is None:
        delta = jnp.sum(p * dp, -1, keepdims=True)
    return p * (dp - delta)


# scoped-VMEM budget for the tile chooser (heuristic: real usage exceeds
# the estimate by the io double-buffers; 10M of estimate keeps Mosaic's
# 16M limit safe). 11M admits ht=8 for the d64 fwd — measured NEUTRAL
# (80.22 vs 80.2 sps), so the validated value stands
_HT_VMEM_BUDGET = 10 << 20


def _head_tile(h: int, nq: int, nk: int, bq: int, bk: int, d: int,
               interpret: bool, mats: int = 1) -> int:
    """Heads per kernel program. Short sequences (one block pair per
    (b, h)) leave each program ~0.2 GFLOP, too little to amortize a
    grid step over a 1024-program grid. Longer sequences get
    enough work per program from the block loops, and head-tiling would
    multiply the VMEM footprint, so keep 1. ``mats`` = number of
    [bq, bk] fp32 temporaries live per unrolled head (1 fwd; 3 bwd —
    the Mosaic stack allocator keeps each unrolled iteration's
    temporaries live, and the scoped-vmem limit is 16M)."""
    if interpret or nq != 1 or nk != 1:
        return 1
    for cand in (8, 4, 2):
        if (h % cand == 0
                and _tile_vmem(cand, mats, bq, bk, d) < _HT_VMEM_BUDGET):
            return cand
    return 1


def _tile_vmem(ht: int, mats: int, bq: int, bk: int, d: int) -> int:
    return ht * (mats * bq * bk * 4 + 8 * max(bq, bk) * d)


def _dense_tile(h: int, nq: int, nk: int, bq: int, bk: int, d: int,
                interpret: bool, mats: int):
    """Heads per program of a lane-dense call (see the note on narrow
    heads), or None where there is none: ``_head_tile``'s choice if its
    ht*d lanes are whole tiles, else the least tile whose are (2 at
    width 64: the split backward and the interpreter, where
    ``_head_tile`` keeps 1), if it divides the heads and fits the same
    VMEM count. Only widths that divide a lane tile: a head of 96 would
    lie across two."""
    if d >= 128 or 128 % d:
        return None
    least = 128 // d
    ht = _head_tile(h, nq, nk, bq, bk, d, interpret, mats)
    if ht % least:
        ht = least
    fits = interpret or _tile_vmem(ht, mats, bq, bk, d) < _HT_VMEM_BUDGET
    return ht if h % ht == 0 and fits else None


# --------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, bq, bk, nk,
                ht, rel=None, window=None, group=1, nqh=0):
    """``nk`` is the kv dimension's length in grid steps; ``nqh`` the q
    blocks a head has (read only where the q axis is folded)."""
    rel_ref = None
    if rel is not None:
        rel_ref, o_ref, lse_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, lse_ref, acc, m_scr, l_scr = rest
    ik = kb = pl.program_id(3)
    qb = pl.program_id(2)
    ih = pl.program_id(1)     # evaluated OUTSIDE pl.when: the traced
                              # cond body can't introduce program_id
    if group > 1:
        qb = qb % nqh
    if window is not None:
        kb = _band_lo_k(qb, bq, bk, window) + ik

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    # causal: skip kv blocks strictly above the diagonal
    run = True if not causal else (kb * bk <= qb * bq + bq - 1)

    @pl.when(run)
    def _block():
        bucket = (None if rel is None else       # shared by the heads
                  _bucket_block(qb, kb, bq, bk, *rel))
        # ``ht`` heads per program (unrolled): amortizes grid/dispatch
        # overhead — at seq 512 the per-(b,h) program is only ~0.2 GFLOP
        for t in range(ht):
            q = q_ref[0, t]                  # [bq, d]
            k = k_ref[0, t]                  # [bk, d]
            v = v_ref[0, t]
            s = _scores(q, k, scale, rel_ref, bucket, (ih, ht, t))  # [bq, bk]
            if causal:
                visible = _mask((bq, bk), window, (qb, bq), (kb, bk))
                s = jnp.where(visible, s, _NEG_INF)
            r = slice(t * bq, (t + 1) * bq)
            m_prev = m_scr[r, :1]                             # [bq, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new)
            if window is not None:
                # a row past the band's lower edge sees nothing of the
                # band's first block: m_new is still the mask's value
                # there and exp(0) is not a probability
                p = jnp.where(visible, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[r, :1] * alpha + jnp.sum(p, -1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [bq, d]
            acc[r] = acc[r] * alpha + pv
            m_scr[r] = jnp.broadcast_to(m_new, (bq, 128))
            l_scr[r] = jnp.broadcast_to(l_new, (bq, 128))

    @pl.when(ik == nk - 1)
    def _finish():
        for t in range(ht):
            r = slice(t * bq, (t + 1) * bq)
            l = jnp.maximum(l_scr[r, :1], 1e-30)
            o_ref[0, t] = (acc[r] / l).astype(o_ref.dtype)
            _store_stat(lse_ref, t, m_scr[r, :1] + jnp.log(l))


def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                       causal, bq, bk, nq, ht, window=None, group=1,
                       lanes=None):
    """Forward when ONE kv block holds every key of the row (nk == 1;
    caller guarantees no rel_table): a plain softmax a q row, no
    state carried from grid step to grid step. The online form's round
    trip through its scratch (init, rescale, the [bq, 128] stores of m
    and l, finish) is more than the block's own work at these lengths
    (PR 27, BERT-large seq 512 in the step: 2.15 -> 0.93 ms a call);
    with m_prev = -1e30 and l_prev = 0 the online update computes these
    same values, bit for bit.

    Causal with the whole q in the block as well (nq == 1): the rows are
    taken ``rc`` at a time against the keys up to their own diagonal,
    the static triangle (seq 1024: 3 of 4 [512, 512] squares, as block
    skipping did over a grid of 4 steps)."""
    qb = pl.program_id(2)
    if group > 1:             # folded q axis: ``nq`` blocks a head
        qb = qb % nq
    triangle = causal and nq == 1
    rc = _pick_block(bq, 512) if triangle else bq
    for r0 in range(0, bq, rc):
        keys = r0 + rc if triangle else bk
        if causal:                           # shared by the heads
            visible = _mask((rc, keys), window, (qb, bq, r0))
        for t in range(ht):          # heads per program (see _fwd_kernel)
            q = _take(q_ref, t, lanes, slice(r0, r0 + rc), alone=True)
            k = _take(k_ref, t, lanes, slice(keys))         # [keys, d]
            v = _take(v_ref, t, lanes, slice(keys))
            s = _scores(q, k, scale)                        # [rc, keys]
            if causal:
                s = jnp.where(visible, s, _NEG_INF)
            m = jnp.maximum(jnp.max(s, -1, keepdims=True), _NEG_INF)
            p = jnp.exp(s - m)
            l = jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [rc, d]
            _put(o_ref, t, pv / l, lanes, slice(r0, r0 + rc))
            _store_stat(lse_ref, t, m + jnp.log(l), r0)


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret, out_dtype=None,
               rel_table=None, rel=None, window=None, heads=None):
    """q: [b, h, sq, d]; k,v: [b, hkv, sk, d] → (out [b,h,sq,d],
    lse [b,h,sq] fp32). sq and sk may DIFFER (cross-attention: the
    decoder's queries over the encoder's keys) — the kernels only ever
    see (bq, bk) blocks, so the tiling contract is per-axis. ``hkv`` may
    divide ``h`` (grouped kv heads) and ``window`` cut the causal
    triangle to a band (see the note at the top).

    With ``heads`` given (a call ``_lane_dense`` admits) q, k, v and out
    are lane-dense, [b, s, heads*d]; lse as ever.

    out_dtype overrides the output dtype (default q.dtype) — ring
    attention requests fp32 partials so the per-step LSE combine does
    not accumulate one bf16 rounding per ring step."""
    dense = heads is not None
    b, hq, h, sq, sk, d = _dims(q, k, heads)
    vd = d if dense else v.shape[-1]      # the values' width, and out's
    group = hq // h
    q = _fold(q, group)
    nq, nk = sq // bq, sk // bk           # blocks a head
    steps = nk if window is None else _band_steps_k(nq, bq, bk, window)
    ht = (_dense_tile if dense else _head_tile)(
        h, group * nq, nk, bq, bk, d, interpret,
        mats=3 if rel is not None else 1)
    if rel is not None:
        ht = _clamp_ht(ht, h)   # matches the bwd dtable tile bound
    grid = (b, h // ht, group * nq, steps)
    if nk == 1 and rel is None:
        kernel = functools.partial(_fwd_single_kernel, scale=scale,
                                   causal=causal, bq=bq, bk=bk, nq=nq, ht=ht,
                                   lanes=d if dense else None,
                                   **_band_args(window, group))
        scratch = []
    else:
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   bq=bq, bk=bk, nk=steps, ht=ht, rel=rel,
                                   **_band_args(window, group, nqh=nq))
        scratch = [pltpu.VMEM((ht * bq, vd), jnp.float32),
                   pltpu.VMEM((ht * bq, 128), jnp.float32),
                   pltpu.VMEM((ht * bq, 128), jnp.float32)]
    q_spec, k_spec, v_spec, o_spec, stat = _specs(
        ht, bq, bk, d, vd, dense, lambda iq, ik: iq,
        _kv_block(bq, bk, nq, group, window, causal and nk > 1))
    table_spec, table = _table_operand(rel_table)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec] + table_spec,
        out_specs=[o_spec, stat],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape[:-1] + (v.shape[-1],),
                                 out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, group * sq), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="bps_flash_fwd",
    )(q, k, v, *table)
    return _unfold(out, group), _unfold(lse[:, :, 0], group)


def _specs(ht, bq, bk, d, vd, dense, q_at, k_at):
    """The BlockSpecs of a call on a (b, head tiles, i, j) grid whose step
    (i, j) stands at q block ``q_at(i, j)`` and kv block ``k_at(i, j)``:
    those of (q and dq, k and dk, v and dv, out and do, a row statistic).
    The forward carries the kv axis and so does the dq call; the dk/dv
    call carries q."""
    def rows(n, width, at):
        return _rows_spec(ht, n, width, dense,
                          lambda ib, ih, i, j: (ib, ih, at(i, j)))
    stat = pl.BlockSpec((1, ht, 1, bq),
                        lambda ib, ih, i, j: (ib, ih, 0, q_at(i, j)))
    return (rows(bq, d, q_at), rows(bk, d, k_at), rows(bk, vd, k_at),
            rows(bq, vd, q_at), stat)


def _table_operand(rel_table):
    """([its BlockSpec], [the table]) for a call with a ``rel_table``, two
    empty lists for one without: the table rides as ONE full-array block
    (``_rel_row``)."""
    if rel_table is None:
        return [], []
    return [pl.BlockSpec(rel_table.shape,
                         lambda ib, ih, i, j: (0, 0))], [rel_table]


def _kv_block(bq, bk, nq, group, window, causal):
    """The kv block of a step on a (.., q blocks, kv steps) grid
    (``_specs``' ``k_at``): the step's own, under a band the block the
    step stands for, and in a causal call held at the q block's diagonal
    block once the steps have passed it: a repeated block index is not
    fetched again, so a skipped step moves nothing."""
    def at(iq, ik):
        if causal:
            qb = iq % nq if group > 1 else iq
            if window is not None:
                ik = _band_lo_k(qb, bq, bk, window) + ik
            ik = jnp.minimum(ik, (qb * bq + bq - 1) // bk)
        return ik
    return at


def _band_args(window, group, **more):
    """The kernels' static arguments for a band or a fold; none at all
    where there is neither, so that such a call traces as it always did."""
    if window is None and group == 1:
        return {}
    return dict(window=window, group=group, **more)


# -------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, bq, bk, nk, ht, rel=None, nq=0, window=None,
               group=1, nqh=0, lanes=None):
    """``nk``: the kv dimension's grid steps; ``nqh``: q blocks a head
    (folded q axis), as in ``_fwd_kernel``."""
    rel_ref = dt_ref = dt_scr = None
    if rel is not None:
        rel_ref, dq_ref, dt_ref, dq_acc, dt_scr = rest
    else:
        dq_ref, dq_acc = rest
    ik = kb = pl.program_id(3)
    qb = pl.program_id(2)
    ih = pl.program_id(1)     # outside pl.when (see _fwd_kernel)
    if group > 1:
        qb = qb % nqh
    if window is not None:
        kb = _band_lo_k(qb, bq, bk, window) + ik

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if rel is not None:
        # dtable accumulates across BOTH block dims (its output block
        # is per (b, h)); the rel grid runs iq as carried too
        @pl.when(jnp.logical_and(kb == 0, qb == 0))
        def _init_dt():
            dt_scr[...] = jnp.zeros_like(dt_scr)

    run = True if not causal else (kb * bk <= qb * bq + bq - 1)
    mask = ((bq, bk), window, (qb, bq), (kb, bk)) if causal else None

    @pl.when(run)
    def _block():
        bucket = None if rel is None else _bucket_block(qb, kb, bq, bk, *rel)
        for t in range(ht):                  # heads per program (see fwd)
            q = _take(q_ref, t, lanes, alone=True)
            k = _take(k_ref, t, lanes)
            v = _take(v_ref, t, lanes)
            do = _take(do_ref, t, lanes, alone=True)
            lse = _load_stat(lse_ref, t)                    # [bq, 1]
            delta = _load_stat(delta_ref, t)                # [bq, 1]
            p = _probs(_scores(q, k, scale, rel_ref, bucket, (ih, ht, t)),
                       lse, mask)                           # [bq, bk]
            ds32 = _dscores(p, do, v, delta)  # dL/dS, S = qkᵀ·scale + B
            if rel is not None:
                dt_scr[t] += _table_grad(ds32, bucket, rel[1])
            ds = ds32.astype(k.dtype)
            r = slice(t * bq, (t + 1) * bq)
            dq_acc[r] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _finish():
        for t in range(ht):
            _put(dq_ref, t, dq_acc[t * bq:(t + 1) * bq], lanes)

    if rel is not None:
        @pl.when(jnp.logical_and(kb == nk - 1, qb == nq - 1))
        def _finish_dt():
            dt_ref[0, 0] = dt_scr[...]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, bq, bk, nq, ht, rel=None, window=None,
                group=1, nqh=0, steps=0, lanes=None):
    """``nq``: the q dimension's grid steps, ``group * steps`` of them
    where the q axis is folded or banded: ``steps`` a head, over its
    ``nqh`` q blocks or the part of them in the kv block's band."""
    rel_ref = None
    if rel is not None:
        rel_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    iq = qb = pl.program_id(3)
    kb = pl.program_id(2)
    ih = pl.program_id(1)     # outside pl.when (see _fwd_kernel)
    if group > 1:
        qb = qb % steps
    if window is not None:
        qb = (kb * bk) // bq + qb

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True if not causal else (kb * bk <= qb * bq + bq - 1)
    if window is not None:    # inside the head, and the band reaches kb
        run = jnp.logical_and(run, jnp.logical_and(
            qb < nqh, qb * bq - (window - 1) <= kb * bk + bk - 1))
    mask = ((bq, bk), window, (qb, bq), (kb, bk)) if causal else None

    @pl.when(run)
    def _block():
        bucket = None if rel is None else _bucket_block(qb, kb, bq, bk, *rel)
        for t in range(ht):                  # heads per program (see fwd)
            q = _take(q_ref, t, lanes, alone=True)          # [bq, d]
            k = _take(k_ref, t, lanes)                      # [bk, d]
            v = _take(v_ref, t, lanes)
            do = _take(do_ref, t, lanes, alone=True)        # [bq, d]
            lse = _load_stat(lse_ref, t)                    # [bq, 1]
            delta = _load_stat(delta_ref, t)
            p = _probs(_scores(q, k, scale, rel_ref, bucket, (ih, ht, t)),
                       lse, mask)                           # [bq, bk]
            pt = p.astype(do.dtype)
            r = slice(t * bk, (t + 1) * bk)
            dv_acc[r] += jax.lax.dot_general(
                pt, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bk, d]
            ds = _dscores(p, do, v, delta).astype(q.dtype)
            dk_acc[r] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bk, d]

    @pl.when(iq == nq - 1)
    def _finish():
        for t in range(ht):
            r = slice(t * bk, (t + 1) * bk)
            _put(dk_ref, t, dk_acc[r], lanes)
            _put(dv_ref, t, dv_acc[r], lanes)


def _dqkv_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, *rest,
                       scale, causal, bq, bk, ht, has_delta, window=None,
                       group=1, lanes=None):
    """Single-block-pair fused backward: when the whole sequence is one
    (bq, bk) block per (b, head) — the flagship seq-512 geometry — the
    split dq / dkv kernels each recompute s, p and dp just to emit
    their own outputs (7 matmuls + 2 exp sweeps total). One kernel
    computes the shared recompute once and emits all three gradients:
    5 matmuls + 1 exp, and q/k/v/do cross HBM once instead of twice.

    ``has_delta=False`` computes the softmax-gradient correction
    IN-KERNEL via the identity delta_i = sum_j p_ij·dp_ij (equal to
    sum_d do_id·out_id since out = p̂V) — valid because nk == 1 means
    the whole kv row is in this block. That removes ``out`` from the
    backward's inputs entirely, so under remat XLA dead-code-eliminates
    the recompute's p·V matmul (1 of its 2 matmuls) AND the host-level
    delta pass over out/do. Ring callers pass their hoisted GLOBAL
    delta instead (has_delta=True): a local p·dp sum cannot span the
    other kv shards' contributions."""
    dk_acc = dv_acc = None
    if group > 1:
        # grouped kv heads: one query head of the kv head's group a grid
        # step (``ht`` is 1), dk and dv summed over the steps in scratch
        *rest, dk_acc, dv_acc = rest
        ig = pl.program_id(2)

        @pl.when(ig == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    delta_ref = None
    if has_delta:
        delta_ref, dq_ref, dk_ref, dv_ref = rest
    else:
        dq_ref, dk_ref, dv_ref = rest
    mask = ((bq, bk), window) if causal else None   # the one block pair
    for t in range(ht):
        q = _take(q_ref, t, lanes, alone=True)              # [bq, d]
        k = _take(k_ref, t, lanes)                          # [bk, d]
        v = _take(v_ref, t, lanes)
        do = _take(do_ref, t, lanes, alone=True)
        lse = _load_stat(lse_ref, t)                        # [bq, 1]
        p = _probs(_scores(q, k, scale), lse, mask)         # [bq, bk]
        pt = p.astype(do.dtype)
        dv = jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if group == 1:
            _put(dv_ref, t, dv, lanes)
        else:
            dv_acc[...] += dv
        ds = _dscores(p, do, v, delta_at=(delta_ref, t) if has_delta
                      else None).astype(q.dtype)
        _put(dq_ref, t, jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale, lanes)
        dk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if group == 1:
            _put(dk_ref, t, dk, lanes)
        else:
            dk_acc[...] += dk

    if group > 1:
        @pl.when(ig == group - 1)
        def _finish():
            dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_fused(q, k, v, lse, do, delta, causal, scale, bq, bk,
                     interpret, ht, window=None, group=1, heads=None):
    """One pallas_call emitting (dq, dk, dv); caller guarantees
    nq == nk == 1 a head and no rel_table. ``lse`` and ``delta``
    are [b,h,sq]; ``delta=None`` computes it in-kernel (see
    _dqkv_fused_kernel) — the no-``out``-input form. With ``group`` > 1
    q, do and the statistics come folded and the grid gains the group as
    its carried dimension. ``heads`` as in ``_flash_fwd``: q, k, v, do
    and the three gradients lane-dense."""
    dense = heads is not None
    b, _, h, _, _, d = _dims(q, k, heads)
    vd = d if dense else v.shape[-1]      # v's, do's and dv's width
    has_delta = delta is not None
    kernel = functools.partial(_dqkv_fused_kernel, scale=scale,
                               causal=causal, bq=bq, bk=bk, ht=ht,
                               has_delta=has_delta,
                               lanes=d if dense else None,
                               **_band_args(window, group))
    if group == 1:
        grid, semantics, scratch = (b, h // ht), ("parallel", "parallel"), []
        spec_q, spec_do = (_rows_spec(ht, bq, width, dense,
                                      lambda ib, ih: (ib, ih, 0))
                           for width in (d, vd))
        spec_k, spec_v = (_rows_spec(ht, bk, width, dense,
                                     lambda ib, ih: (ib, ih, 0))
                          for width in (d, vd))
        spec_stat = pl.BlockSpec((1, ht, 1, bq),
                                 lambda ib, ih: (ib, ih, 0, 0))
    else:
        grid = (b, h, group)
        semantics = ("parallel", "parallel", "arbitrary")
        scratch = [pltpu.VMEM((bk, width), jnp.float32)
                   for width in (d, vd)]
        spec_q, spec_do = (pl.BlockSpec((1, 1, bq, width),
                                        lambda ib, ih, ig: (ib, ih, ig, 0))
                           for width in (d, vd))
        spec_k, spec_v = (pl.BlockSpec((1, 1, bk, width),
                                       lambda ib, ih, ig: (ib, ih, 0, 0))
                          for width in (d, vd))
        spec_stat = pl.BlockSpec((1, 1, 1, bq),
                                 lambda ib, ih, ig: (ib, ih, 0, ig))
    in_specs = [spec_q, spec_k, spec_v, spec_do, spec_stat]
    inputs = [q, k, v, do, lse[:, :, None]]
    if has_delta:
        in_specs.append(spec_stat)
        inputs.append(delta[:, :, None])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[spec_q, spec_k, spec_v],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="bps_flash_bwd_fused",
    )(*inputs)


def _flash_bwd(q, k, v, out, lse, do, causal, scale, bq, bk, interpret,
               delta=None, rel_table=None, rel=None, window=None, heads=None):
    """(dq, dk, dv, drel). ``lse`` and a caller's ``delta`` are
    [b,h,sq] fp32, like every row statistic outside the kernels. k and v
    may have fewer heads than q (grouped), ``window`` as in _flash_fwd;
    so ``heads``: q, k, v, out, do, dq, dk and dv lane-dense."""
    dense = heads is not None
    b, hq, h, sq, sk, d = _dims(q, k, heads)
    vd = d if dense else v.shape[-1]      # v's, out's, do's and dv's width
    group = hq // h
    nq, nk = sq // bq, sk // bk           # blocks a head
    q, out, lse, do, delta = (None if x is None else _fold(x, group)
                              for x in (q, out, lse, do, delta))
    band = _band_args(window, group)
    lanes = d if dense else None
    tile = _dense_tile if dense else _head_tile

    has_rel = rel is not None
    if not has_rel and nq == 1 and nk == 1:
        # mats=4: p, dp, ds32 and the cast ds are live per unrolled
        # head. delta passes through as given: None lets the kernel
        # compute it in-kernel (dropping `out` from the backward's
        # inputs — under remat the recompute's p·V matmul DCEs away);
        # ring callers' hoisted GLOBAL delta is honored
        ht_f = (1 if group > 1 else
                tile(h, nq, nk, bq, bk, d, interpret, mats=4))
        dq, dk, dv = _flash_bwd_fused(q, k, v, lse, do, delta, causal,
                                      scale, bq, bk, interpret, ht_f,
                                      heads=heads, **band)
        return _unfold(dq, group), dk, dv, None

    if delta is None:      # ring callers hoist this loop-invariant reduction
        delta = do.astype(jnp.float32) * out.astype(jnp.float32)
        if dense:          # a head's lanes summed, then [b,s,h] -> [b,h,s]
            delta = jnp.swapaxes(
                jnp.sum(delta.reshape(b, sq, h, d), axis=-1), 1, 2)
        else:
            delta = jnp.sum(delta, axis=-1)                 # [b,h,s]
    lse, delta = lse[:, :, None], delta[:, :, None]         # [b,h,1,s]
    ht = tile(h, group * nq, nk, bq, bk, d, interpret,
              mats=5 if has_rel else 3)
    if has_rel:
        # the dtable scratch and output tiles are hard-sized to
        # _DT_PAD rows — a tile above that would write out of bounds
        # and break the drel reshape
        ht = _clamp_ht(ht, h)
    table_spec, table = _table_operand(rel_table)
    acc_lanes = 128 if dense else d     # lane-dense: a head's whole tile
    acc_lanes_v = 128 if dense else vd

    # dq: q block outer, the kv steps the carried dim, as in the forward
    qspec, kspec, vspec, dospec, stat = _specs(
        ht, bq, bk, d, vd, dense, lambda iq, ik: iq,
        _kv_block(bq, bk, nq, group, window, causal))
    steps_k = nk if window is None else _band_steps_k(nq, bq, bk, window)
    out_specs = qspec
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    scratches = [pltpu.VMEM((ht * bq, acc_lanes), jnp.float32)]
    params = _DIM_SEMANTICS
    if has_rel:
        # dtable accumulates in VMEM scratch across BOTH block dims —
        # iq must therefore be CARRIED (arbitrary), not parallel.
        # Output tiles are padded to the minimum legal TPU block
        # (_DT_PAD); real rows/lanes sliced back out below.
        out_specs = [qspec, pl.BlockSpec(
            (1, 1) + _DT_PAD, lambda ib, ih, iq, ik: (ib, ih, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (b, h // ht) + _DT_PAD, jnp.float32)]
        scratches.append(pltpu.VMEM(_DT_PAD, jnp.float32))
        params = pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary"))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=steps_k, ht=ht, rel=rel, nq=nq,
                          lanes=lanes, **(band and dict(band, nqh=nq))),
        grid=(b, h // ht, group * nq, steps_k),
        in_specs=[qspec, kspec, vspec, dospec, stat, stat] + table_spec,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratches,
        compiler_params=params,
        interpret=interpret,
        name="bps_flash_bwd_dq",
    )(q, k, v, do, lse, delta, *table)
    drel = None
    if has_rel:
        dq, dt_b = dq                  # [b, h//ht, 8, 128] padded tiles
        nb = rel_table.shape[1]
        drel = jnp.sum(dt_b[:, :, :ht, :nb], axis=0).reshape(h, nb)

    # dk/dv: kv block is the outer (carried) grid dim, q block inner. A
    # causal call walks a kv block's q blocks from its diagonal block on
    # (the triangle from q block 0, whose steps above the diagonal are
    # skipped; a band to the end of its band, ``steps_q`` steps, the same
    # walk in every head of the fold), and a step outside them is held at
    # the nearest block inside: a skipped step moves nothing here either
    steps_q = nq if window is None else _band_steps_q(nq, nk, bq, bk, window)

    def q_block(ik, iq):
        if not causal:
            return iq
        first, last = (ik * bk) // bq, nq - 1
        qb = iq % steps_q
        if window is not None:
            qb = first + qb
            last = jnp.minimum(last, (ik * bk + bk + window - 2) // bq)
        return iq // steps_q * nq + jnp.clip(qb, first, last)
    qspec, kspec, vspec, dospec, stat = _specs(
        ht, bq, bk, d, vd, dense, q_block, lambda ik, iq: ik)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=group * steps_q, ht=ht, rel=rel,
                          lanes=lanes,
                          **(band and dict(band, nqh=nq, steps=steps_q))),
        grid=(b, h // ht, nk, group * steps_q),
        in_specs=[qspec, kspec, vspec, dospec, stat, stat] + table_spec,
        out_specs=[kspec, vspec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((ht * bk, acc_lanes), jnp.float32),
                        pltpu.VMEM((ht * bk, acc_lanes_v), jnp.float32)],
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="bps_flash_bwd_dkv",
    )(q, k, v, do, lse, delta, *table)
    return _unfold(dq, group), dk, dv, drel


# ------------------------------------------------------------ public API

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 9, 10, 11))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, block_k=None, interpret=False,
                    rel_table=None, rel_bidirectional=True,
                    rel_max_distance=128, window=None):
    """Pallas flash attention. q: [b, sq, heads, d]; k,v: [b, sk, heads,
    d] → [b, sq, heads, d]. sq and sk may differ (cross-attention).

    v may have a width of its own, [b, sk, heads, dv] (latent attention:
    q·k over 192, values of 128): the result, its cotangent and dv are
    then dv wide, dq and dk d wide, ``scale`` defaults to d ** -0.5 with
    d the width of q·k, and the call is head-major (below); the blocks of
    q, k, dq and dk are the whole width d, which need be no multiple of a
    lane tile. With dv = d every kernel traces as it did before v had a
    width.

    Grouped kv heads: k and v may carry ``kv_heads`` heads where
    ``heads % kv_heads == 0``; query head i attends kv head
    ``i // (heads // kv_heads)``, k and v are never repeated in HBM and
    dk / dv come back summed over each group. ``window`` = w (causal
    only) lets query i see key j where ``0 <= i - j < w``; kv blocks
    outside the band are not visited. Neither goes with ``rel_table``.

    Which layout crosses HBM (``_fwd_rule`` chooses, once a call; no
    argument does): q, k, v, out and in the backward do, dq, dk, dv go
    lane-dense, [b, s, heads*d], a free reshape of what is passed and
    returned, where head_dim divides a 128-lane tile (64, 32), a head
    tile of whole lane tiles divides the heads (an even number of heads
    at width 64) within the kernels' VMEM count, k and v have as many
    heads as q, there is no ``rel_table`` or ``window``, and one forward
    block holds a row's keys (up to 1024 by default; explicit blocks
    that split the keys do not). Unequal q and kv lengths are covered
    (T5's cross-attention at width 64). Every other call (width 128,
    T5's self-attention under its table, grouped kv heads, a window, the
    online forward at long rows, three heads of 64) is head-major,
    [b, heads, s, d], behind a swapaxes each way, and so are the ring's
    own calls of ``_flash_fwd`` / ``_flash_bwd``.

    Each seq must be divisible by the (auto-shrunk) block sizes; a
    block size of None is the default (see ``_resolve``): in a plain
    call's forward the whole kv sequence up to 1024 keys, else what
    ``_default_block`` reads off the call: 1024 x 1024 past 1024 keys or
    where v has a width of its own, 512 x 512 in the backward of a call
    of at most 1024 keys and with a ``rel_table``. Differentiable
    via the flash backward kernels. The larger block won at every shape
    measured (512 read ~29% faster than 256 on BERT-large seq-512, 1024
    35-50 % faster than 512 in the online forward at 8k: fewer grid
    steps, fewer rescalings of the accumulator); 1024 x 1024 fits VMEM
    through d=256, 1024 x 2048 does not.

    One additive score term: ``rel_table`` [heads, num_buckets], T5's
    relative-position bias computed IN-KERNEL from block offsets — no
    [h, sq, sk] bias ever materializes (O(s) memory at any length), dtable
    accumulated in VMEM scratch. (A materialized [heads, sq, sk] bias is
    ``local_attention``'s, the reference's, alone.)
    """
    out, _ = _fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret,
                       rel_table, rel_bidirectional, rel_max_distance, window)
    return out


# keys ONE forward program holds whole when the caller names no block:
# up to here a plain call's forward is _fwd_single_kernel
_WHOLE_KV = 1024


def _resolve(q, k, scale, block_q, block_k, whole_kv=False, causal=False,
             block=512):
    """(scale, bq, bk). A block size of None is the default: ``block``
    (``_default_block``), and with ``whole_kv`` (the forward of a call
    without a rel_table) a kv sequence of up to _WHOLE_KV keys is one
    block — with the whole q beside it when causal, so that the visible
    triangle is static."""
    _, sq, _, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if whole_kv and block_k is None and sk <= _WHOLE_KV:
        block_k = sk
        if causal and block_q is None:
            block_q = sq
    bq = _pick_block(sq, min(block_q or block, sq))
    bk = _pick_block(sk, min(block_k or block, sk))
    return scale, bq, bk


def _default_block(sk: int, d: int, vd: int, plain: bool = True) -> int:
    """Rows and keys a block where the caller names none, from what the
    call shows: 1024 where the keys are more than one forward program
    holds whole (``_WHOLE_KV``: the online forward, the split backward)
    or v has a width of its own; 512 for the calls of at most
    ``_WHOLE_KV`` keys (GPT-2's split backward, BERT's one block) and for
    a call with a ``rel_table`` (not ``plain``: its [bq, bk] temporaries
    were never measured at 1024).

    Measured on the chip, the kernels alone, device ms a call, forward |
    dq | dk/dv at 512 x 512 -> 1024 x 1024 (my chip runs, PR 45, calls 1
    and 4; PERF.md section 5 has 512 x 1024 and 1024 x 512 too: neither
    reads under 1024 x 1024 for any kernel of any shape): the causal
    triangle of 32 heads of 128 over 2 kv heads x 8192 keys 20.84 |
    13.48 | 18.33 -> 10.18 | 11.58 | 14.48; 8 heads over 1 kv head 5.15 |
    3.46 | 4.35 -> 2.53 | 2.90 | 3.57, and under ``window`` 2048 (three
    blocks of 1024 a q block for five of 512) 2.60 | 1.75 | 2.20 -> 1.69
    | 1.75 | 2.12; 192 over 128 (32 heads) 24.21 | 20.84 | 23.81 -> 13.45
    | 18.41 | 20.76; the same way at 2,048 and 4,096 keys, at widths 64
    and 256 and with no mask at all (21.66 -> 14.62 the three together).
    A grid step costs about 0.35 us and a block's rescaling of its
    accumulator and its statistics' round trip do not shrink with the
    block. 2048 x 1024 and 1024 x 2048 run out of VMEM."""
    return 1024 if plain and (sk > _WHOLE_KV or vd != d) else 512


def _rel_static(rel_table, bidirectional, max_distance):
    """(bidirectional, num_buckets, max_distance) static tuple the
    kernels close over, or None."""
    if rel_table is None:
        return None
    return (bool(bidirectional), int(rel_table.shape[1]),
            int(max_distance))


def _check_band(q, k, causal, window, has_rel) -> None:
    """What a window or grouped kv heads need of a call."""
    heads, kv_heads = q.shape[2], k.shape[2]
    if kv_heads < 1 or heads % kv_heads:
        raise ValueError(f"{heads} query heads do not divide over "
                         f"{kv_heads} kv heads")
    if window is not None and not (causal and window >= 1):
        raise ValueError("a window is a causal band of at least one key "
                         f"(got window={window!r}, causal={causal})")
    if has_rel and (window is not None or kv_heads != heads):
        raise ValueError("a rel_table goes with neither a window nor "
                         "grouped kv heads")


def _lane_dense(q, k, bq, bk, bwd_blocks, interpret) -> bool:
    """Whether a call with no rel_table or window sends its
    operands across HBM as [b, s, heads*d] (the note on narrow heads):
    a head width that divides a lane tile, as many kv heads as query heads,
    every key of a row in the forward's one block (``_fwd_single_kernel``)
    and a head tile of whole lane tiles for the forward and for the
    backward. q and kv lengths may differ (T5's cross-attention)."""
    _, sq, heads, d = q.shape
    sk = k.shape[1]
    if k.shape[2] != heads or bk != sk:
        return False
    bq_b, bk_b = bwd_blocks
    nq, nk = sq // bq_b, sk // bk_b
    return None not in (
        _dense_tile(heads, sq // bq, 1, bq, bk, d, interpret, mats=1),
        _dense_tile(heads, nq, nk, bq_b, bk_b, d, interpret,
                    mats=4 if nq == 1 and nk == 1 else 3))


def _fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret,
              rel_table=None, rel_bidirectional=True, rel_max_distance=128,
              window=None):
    """The forward and its residuals. The layout in which q, k, v, out
    (and in the backward their cotangents) cross HBM is chosen here,
    once a call, from what the call shows (``_lane_dense``): lane-dense
    [b, s, heads*d], a free reshape of the arguments, or head-major
    [b, heads, s, d] behind a swapaxes each way. The residuals carry the
    layout to ``_vjp_bwd`` in their rank."""
    _check_band(q, k, causal, window, rel_table is not None)
    if rel_table is not None and rel_table.shape[1] > _DT_PAD[1]:
        raise ValueError(
            f"rel_table has {rel_table.shape[1]} buckets; the in-kernel "
            f"path supports at most {_DT_PAD[1]} (one dtable lane tile)")
    if causal and q.shape[1] != k.shape[1]:
        raise ValueError(
            "causal masking requires equal q/kv lengths (got "
            f"{q.shape[1]} vs {k.shape[1]}); cross-attention is "
            "bidirectional")
    if q.shape[3] != k.shape[3] or k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"q {q.shape} and k {k.shape} share their last width and k "
            f"and v {v.shape} all but it")
    rel = _rel_static(rel_table, rel_bidirectional, rel_max_distance)
    plain = rel is None
    block = _default_block(k.shape[1], q.shape[3], v.shape[3], plain)
    scale, bq, bk = _resolve(q, k, scale, block_q, block_k, whole_kv=plain,
                             causal=causal, block=block)
    heads = None
    if (plain and window is None
            and v.shape[3] == q.shape[3] and _lane_dense(
            q, k, bq, bk, _resolve(q, k, scale, block_q, block_k)[1:],
            interpret)):
        heads = q.shape[2]
        qt, kt, vt = (x.reshape(*x.shape[:2], -1) for x in (q, k, v))
    else:
        note_choice("flash_blocks", f"{bq}x{bk}", tuple(q.shape))
        qt = jnp.swapaxes(q, 1, 2)       # [b, h, s, d]
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
    out, lse = _flash_fwd(qt, kt, vt, causal, scale, bq, bk, interpret,
                          rel_table=rel_table, rel=rel, window=window,
                          heads=heads)
    # named so a remat policy can pin the flash residuals while everything
    # around them recomputes (SAVED_NAMES; remat_policy="save_attn")
    out, lse = map(checkpoint_name, (out, lse), SAVED_NAMES)  # lse [b,h,sq]
    res = (qt, kt, vt, out, lse, rel_table)
    if heads is not None:
        return out.reshape(q.shape), res
    return jnp.swapaxes(out, 1, 2), res


def _vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
             rel_table=None, rel_bidirectional=True, rel_max_distance=128,
             window=None):
    out, res = _fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret,
                         rel_table, rel_bidirectional, rel_max_distance,
                         window)
    return out, res


def _vjp_bwd(causal, scale, block_q, block_k, interpret,
             rel_bidirectional, rel_max_distance, window, res, g):
    qt, kt, vt, out, lse, rel_table = res
    if qt.ndim == 3:                 # lane-dense residuals (_fwd_rule)
        heads = lse.shape[1]
        kv = jax.ShapeDtypeStruct((*kt.shape[:2], heads, g.shape[3]),
                                  kt.dtype)
        scale, bq, bk = _resolve(g, kv, scale, block_q, block_k)
        dq, dk, dv, _ = _flash_bwd(
            qt, kt, vt, out, lse, g.reshape(qt.shape), causal, scale, bq, bk,
            interpret, heads=heads)
        return (dq.reshape(g.shape), dk.reshape(kv.shape),
                dv.reshape(kv.shape), None)
    scale, bq, bk = _resolve(
        jnp.swapaxes(qt, 1, 2), jnp.swapaxes(kt, 1, 2), scale, block_q,
        block_k, block=_default_block(kt.shape[2], qt.shape[3], vt.shape[3],
                                      rel_table is None))
    rel = _rel_static(rel_table, rel_bidirectional, rel_max_distance)
    do = jnp.swapaxes(g, 1, 2)
    dq, dk, dv, drel = _flash_bwd(
        qt, kt, vt, out, lse, do, causal, scale, bq, bk,
        interpret, rel_table=rel_table, rel=rel, window=window)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2), drel)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


def supported(q_shape, k_shape=None, v_shape=None) -> bool:
    """Shapes the Pallas kernels handle: each sequence a multiple of
    128, head_dim ≤ 256 (one VMEM tile of lanes per block row), the
    values' own width, where ``v_shape`` shows one, as well. q and kv
    lengths may differ (cross-attention)."""
    _, sq, _, d = q_shape
    sk = sq if k_shape is None else k_shape[1]
    vd = d if v_shape is None else v_shape[3]
    return sq % 128 == 0 and sk % 128 == 0 and max(d, vd) <= 256


def local_attention(q, k, v, causal: bool = False,
                    scale: float | None = None, bias=None, window=None):
    """Single-device reference attention, same layout [b, s, h, d]
    (q and kv lengths may differ; ``bias`` [h, sq, sk] adds to the
    scores — the T5 relative-position contract). k and v may carry
    fewer heads than q (query head i attends kv head i // group) and v a
    width of its own, which is then the result's; ``window`` = w cuts the
    causal triangle to ``0 <= i - j < w``."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    if window is not None and not causal:
        raise ValueError("a window is a causal band")
    sc = jnp.einsum("bqkgd,bckd->bkgqc", q.reshape(b, s, hk, h // hk, d), k,
                    preferred_element_type=jnp.float32) * scale
    sc = sc.reshape(b, h, s, k.shape[1])
    if bias is not None:
        sc = sc + bias[None].astype(jnp.float32)
    if causal:
        if k.shape[1] != s:
            # same contract (and message) as the flash path
            raise ValueError(
                "causal masking requires equal q/kv lengths (got "
                f"{s} vs {k.shape[1]}); cross-attention is "
                "bidirectional")
        mask = jnp.tril(jnp.ones((s, s), bool))
        if window is not None:
            mask = jnp.logical_and(mask, ~jnp.tril(mask, -window))
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgqc,bckd->bqkgd",
                     p.astype(v.dtype).reshape(b, hk, h // hk, s, -1), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, v.shape[3]).astype(q.dtype)


def attention(q, k, v, causal=False, scale=None, impl="auto",
              rel_table=None, rel_bidirectional=True,
              rel_max_distance=128, window=None):
    """Dispatcher: Pallas flash kernels on TPU, blockwise JAX elsewhere.

    impl: "auto" | "flash" | "naive" ("flash" takes the kernels whatever
    the platform and shape, "naive" ``local_attention``).

    ``rel_table`` [heads, num_buckets]: T5 relative-position bias,
    computed in-kernel on the flash path (no materialized [h, sq, sk]
    bias); materialized only on the naive fall-back, into
    ``local_attention``'s ``bias``.

    ``window``, grouped kv heads (k, v with fewer heads than q) and a
    width of v unlike q's and k's as in ``flash_attention``, on every path.
    """
    if impl not in ("auto", "flash", "naive"):
        raise ValueError(
            f"attn impl must be auto|flash|naive, got {impl!r}")

    def _naive():
        b = None
        if rel_table is not None:
            from .relpos import relative_bias
            b = relative_bias(rel_table.T, q.shape[1], k.shape[1],
                              rel_bidirectional, rel_table.shape[1],
                              rel_max_distance)
        # named so that a fall-back from the kernels shows in a trace
        with jax.named_scope("bps_attn_xla"):
            return local_attention(q, k, v, causal=causal, scale=scale,
                                   bias=b, window=window)

    on_tpu = jax.default_backend() == "tpu"
    flash = impl == "flash" or (impl == "auto" and on_tpu
                                and supported(q.shape, k.shape, v.shape))
    # a silent fall-through here once cost 28x at seq 8k (an s-1 shift
    # broke seq % 128): the record makes the downgrade loud, once a shape
    note_choice("attention", "flash" if flash else "xla",
                tuple(q.shape) if v.shape[3] == q.shape[3]
                else (*q.shape, v.shape[3]),
                "naive O(s^2): flash needs seq % 128 == 0 and head_dim <= 256",
                asked=impl)
    if flash:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               rel_table=rel_table,
                               rel_bidirectional=rel_bidirectional,
                               rel_max_distance=rel_max_distance,
                               window=window)
    return _naive()

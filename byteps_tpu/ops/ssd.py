"""The state-space scan of a Mamba-2 layer in its chunked form (the
"state-space duality" of Dao and Gu, 2024): ``models/mamba2.py``'s mixer.

A head ``h`` carries a state ``H`` in R^{P x N} (P its width, N the
state size) through the positions of a sequence::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T        y_t = H_t C_t + D x_t

with ``A`` (negative) and ``D`` a scalar a head, ``dt_t`` a positive step a
head and position, and ``B_t``, ``C_t`` in R^N shared by the heads of a
GROUP (head h reads group ``h // (heads / groups)``). One step at a time
that is ``s`` dependent updates of a [P, N] state. In chunks of ``Q``
positions it is products: with ``a_t = dt_t A`` and ``L`` its running sum
inside a chunk,

    Y_in  = ((C B^T) o exp(L_i - L_j) o [i >= j]) (dt x)       inside a chunk
    S_c   = sum_t exp(L_end - L_t) dt_t x_t B_t^T              the chunk's state
    H_c   = exp(sum a) H_{c-1} + S_c                           across chunks
    Y_out = exp(L_t) C_t H_{c-1}                               what came before
    y     = Y_in + Y_out + D x

``ssd`` is the entry and the dispatcher, as ``ops.flash_attention
.attention`` and ``ops.grouped_matmul.grouped_matmul`` are: on the TPU,
for the shapes ``supported`` takes, the Pallas kernels ``bps_ssd_fwd``
and ``bps_ssd_bwd`` (``ssd_kernels``, a ``jax.custom_vjp``); elsewhere
(the CPU, odd shapes) ``ssd_xla``, the same form as XLA products
differentiated by JAX, under the scope ``bps_ssd_xla``. ``ssd_packed``
is ``ssd`` for the caller that holds ``x``, ``B`` and ``C`` side by side
in one array, as the mixer's convolution writes them: the kernels then
read their blocks out of that array and no slice of it is made
(``ssd_kernels_packed``). Both forms are pure
functions (safe under ``jax.checkpoint``) and both keep the same
precision: the decays, their running sums and the carried state are
float32; the products (``C B^T``, its weighted sum of ``dt x``, a chunk's
state, ``C H``, and their transposes in the backward) take operands in
``x``'s dtype and accumulate in float32. A decay is ``exp`` of a sum of
non-positive terms wherever it is kept; above the diagonal the exponent
is set to ``-inf`` BEFORE the ``exp``, so nothing overflows there in
either pass.

The kernels (docs/state-space.md has the picture). A grid step is one
batch row, one group of heads and one chunk; the chunk axis is the
grid's last and sequential. It reads the position-major blocks as the
convolution wrote them (``x`` [Q, per*p], ``B`` and ``C`` [Q, n]) once,
builds each head's [Q, Q] decays in VMEM from the chunk's running sums,
and carries the group's state across chunks in a float32 scratch,
TRANSPOSED: [n, per*p], a head's p columns on the lanes where its
columns of ``x`` lie, so that ``Y_out = C H`` and ``S = B^T (dt x)`` are
ONE product each over the group's whole width and the decay of a state
is a factor a lane. Heads narrower than a lane tile are worked on a
tile's heads at once (two at p = 64): a product's operand is the whole
128-lane tile and a head keeps its own lanes by a lane select, so no
lane moves (``ops/flash_attention.py``'s note on narrow heads). The
forward that a backward will follow also writes the state BEFORE each
chunk ([batch, chunks, n, heads*p] float32), the one residual besides
the inputs; the backward walks the chunks in reverse with the state's
cotangent in scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.setup_record import note_choice

CHUNK = 128
LANES = 128

# grid (batch, group, chunk): the chunk axis carries the state in scratch
_SCAN_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _decay(upto, since, strict=False):
    """``exp(upto_i - since_j)`` where ``i >= j`` (``strict``: ``i > j``)
    along the last two axes, zero elsewhere."""
    diff = upto[..., :, None] - since[..., None, :]
    i = jax.lax.broadcasted_iota(jnp.int32, diff.shape[-2:], 0)
    j = jax.lax.broadcasted_iota(jnp.int32, diff.shape[-2:], 1)
    return jnp.exp(jnp.where(i > j if strict else i >= j, diff, -jnp.inf))


def _sizes(x, b, chunk):
    bsz, s, heads, p = x.shape
    groups, n = b.shape[2:]
    if s % chunk or heads % groups:
        raise ValueError(f"{s} positions in chunks of {chunk}, {heads} "
                         f"heads over {groups} groups")
    return bsz, s, heads, p, groups, n, s // chunk, heads // groups


def ssd_xla(x, dt, a, b, c, d, chunk: int = CHUNK):
    """``ssd`` as XLA products, differentiated by JAX: the path of the
    CPU and of shapes the kernels do not take, and what the tests hold
    the kernels against. The states cross the chunks by ONE float32
    product with the lower-triangular matrix of the chunks' decays
    (``exp`` of differences of the running sum of the chunks' totals),
    not by a loop."""
    bsz, s, heads, p, groups, n, nc, per = _sizes(x, b, chunk)
    dtype, f32 = x.dtype, jnp.float32
    x32 = x.astype(f32)
    dt = dt.astype(f32)
    # running sums of a_t = dt_t A inside each chunk, [batch, nc, heads, q]
    run = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, nc, chunk, heads),
                     axis=2).transpose(0, 1, 3, 2)
    total = run[..., -1]                                # [batch, nc, heads]
    by_group = (bsz, nc, groups, per, chunk)
    run_g = run.reshape(by_group)

    def chunks(t, *tail):       # [batch, s, ...] -> [batch, nc, q, *tail]
        return t.reshape(bsz, nc, chunk, *tail)

    bc, cc = chunks(b, groups, n), chunks(c, groups, n)
    xd = chunks(x32 * dt[..., None], groups, per, p)    # dt x, float32
    # inside a chunk
    cb = jnp.einsum("zcign,zcjgn->zcgij", cc, bc, preferred_element_type=f32)
    mix = (cb[:, :, :, None] * _decay(run_g, run_g)).astype(dtype)
    y = jnp.einsum("zcgrij,zcjgrp->zcigrp", mix, xd.astype(dtype),
                   preferred_element_type=f32)
    # each chunk's own state, [batch, nc, groups, per, p, n], float32
    to_end = jnp.exp(run_g[..., -1:] - run_g)           # [.., per, q]
    state = jnp.einsum(
        "zcjgrp,zcjgn->zcgrpn",
        (xd * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype), bc,
        preferred_element_type=f32)
    # across chunks: the state BEFORE chunk c is the sum over the chunks
    # z < c of exp(the totals of the chunks between them) S_z
    upto = jnp.cumsum(total, axis=1).transpose(0, 2, 1)  # [batch, heads, nc]
    since = jnp.concatenate(
        [jnp.zeros_like(upto[..., :1]), upto[..., :-1]], -1)
    # row c, column z: exp(upto[c - 1] - upto[z]) for z <= c - 1
    carry = _decay(since, upto, strict=True)
    before = jnp.einsum(
        "zgrcw,zwgrpn->zcgrpn", carry.reshape(bsz, groups, per, nc, nc),
        state, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=f32)
    y = y + jnp.einsum(
        "zcign,zcgrpn->zcigrp", cc, before.astype(dtype),
        preferred_element_type=f32) * jnp.moveaxis(
            jnp.exp(run_g), -1, 2)[..., None]
    y = y.reshape(bsz, s, heads, p) + d.astype(f32)[:, None] * x32
    return y.astype(dtype)


# ------------------------------------------------------------ the kernels
# What a kernel is handed besides the blocks of x, B, C and y, all float32
# and small (a number a head and position where x has p), made by XLA under
# the caller's scope (``_small``; ``_places`` on how, and why no array with a
# group's 8 heads as its minor dimension is made on the way):
#   cols [batch, groups, s, 128]   a group's positions on the sublanes:
#         lane r < per the step dt of the group's head r, lane per + r its
#         running sum L inside the chunk; the backward writes d dt and d L
#         back in the same places
#   rows [batch, nc, groups, per, Q]   L again with the positions on the
#         lanes: a head's [Q, Q] decays are exp(column - row)
#   d_lane [1, heads*p]            D, a lane of x each
# A column is brought to a head's lanes by a lane broadcast and a select.

def _dot(lhs, rhs, contract):
    return jax.lax.dot_general(lhs, rhs, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _lane():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def _by_lane(cols, first, hpt, p):
    """[Q, 128]: for each lane of a lane tile the column of ``cols`` of
    the head the lane belongs to, ``first`` the column of the tile's
    first head."""
    out = jnp.broadcast_to(cols[:, first:first + 1], (cols.shape[0], LANES))
    for k in range(1, hpt):
        out = jnp.where(_lane() >= k * p, cols[:, first + k:first + k + 1],
                        out)
    return out


def _head_decay(cols, rows, per, h, keep):
    """Head ``h``'s [Q, Q] decays ``exp(L_i - L_j)``, zero above the
    diagonal: masked before the ``exp``."""
    return jnp.exp(jnp.where(
        keep, cols[:, per + h:per + h + 1] - rows[h:h + 1, :], -jnp.inf))


def _keep(q):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, y_ref, *rest,
                per, p, save):
    state = rest[-1]                        # [n, per*p] float32, transposed
    dtype, f32 = x_ref.dtype, jnp.float32
    q, hpt = x_ref.shape[1], LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    bm, cm = b_ref[0], c_ref[0]
    cols, rows = cols_ref[0, 0], rows_ref[0, 0, 0]
    before = state[...]
    if save:
        rest[0][0, 0] = before
    cb = _dot(cm, bm, _NT)                                  # [q, q]
    y_out = _dot(cm, before.astype(dtype), _NN)             # [q, per*p]
    keep = _keep(q)
    weighted, ends = [], []
    for t in range(per // hpt):
        lanes = slice(t * LANES, (t + 1) * LANES)
        run = _by_lane(cols, per + t * hpt, hpt, p)
        x32 = x_ref[0, :, lanes].astype(f32)
        xd = x32 * _by_lane(cols, t * hpt, hpt, p)          # dt x
        xdc = xd.astype(dtype)
        y_in = None
        for k in range(hpt):
            mix = (cb * _head_decay(cols, rows, per, t * hpt + k, keep)
                   ).astype(dtype)
            mine = _dot(mix, xdc, _NN)
            y_in = mine if k == 0 else jnp.where(_lane() >= k * p, mine, y_in)
        y_ref[0, :, lanes] = (y_in + y_out[:, lanes] * jnp.exp(run)
                              + d_ref[:, lanes] * x32).astype(dtype)
        end = run[q - 1:q, :]
        weighted.append((xd * jnp.exp(end - run)).astype(dtype))
        ends.append(end)
    state[...] = (jnp.exp(jnp.concatenate(ends, 1)) * before
                  + _dot(bm, jnp.concatenate(weighted, 1), _TN))


def _bwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, before_ref,
                dy_ref, dx_ref, db_ref, dc_ref, dcols_ref, drows_ref,
                dlane_ref, dstate, *, per, p):
    dtype, f32 = x_ref.dtype, jnp.float32
    q, hpt = x_ref.shape[1], LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    bm, cm = b_ref[0], c_ref[0]
    cols, rows = cols_ref[0, 0], rows_ref[0, 0, 0]
    before = before_ref[0, 0]                   # [n, per*p] float32
    before_c = before.astype(dtype)
    dafter = dstate[...]                        # d of the state AFTER it
    dafter_c = dafter.astype(dtype)
    cb = _dot(cm, bm, _NT)
    z = _dot(cm, before_c, _NN)                 # y_out before its decay
    dweighted = _dot(bm, dafter_c, _NN)         # [q, per*p]
    keep = _keep(q)
    dcb = jnp.zeros((q, q), f32)
    dz, weighted, ends = [], [], []
    dcols = jnp.zeros((q, LANES), f32)
    for t in range(per // hpt):
        lanes = slice(t * LANES, (t + 1) * LANES)
        step = _by_lane(cols, t * hpt, hpt, p)
        run = _by_lane(cols, per + t * hpt, hpt, p)
        x32 = x_ref[0, :, lanes].astype(f32)
        xd = x32 * step
        xdc = xd.astype(dtype)
        dy = dy_ref[0, :, lanes]
        dy32 = dy.astype(f32)
        grow = jnp.exp(run)
        end = run[q - 1:q, :]
        to_end = jnp.exp(end - run)
        xw = xd * to_end
        dw = dweighted[:, lanes]
        # d L a lane: + dy . y_out (the decay exp(L_i) before y_out),
        # - d weighted . weighted (exp(L_end - L_j) inside a chunk's state)
        dl_lane = dy32 * (z[:, lanes] * grow) - dw * xw
        dxd = dw * to_end
        for k in range(hpt):
            h = t * hpt + k
            own = jnp.logical_and(_lane() >= k * p, _lane() < (k + 1) * p)
            decay = _head_decay(cols, rows, per, h, keep)
            mix = cb * decay
            dmix = _dot(jnp.where(own, dy, jnp.zeros_like(dy)), xdc, _NT)
            dcb = dcb + dmix * decay
            moved = dmix * mix              # d exp(L_i - L_j) . itself
            dxd = dxd + jnp.where(
                own, _dot(mix.astype(dtype), dy, _TN), 0.0)
            dl = (moved.sum(1, keepdims=True)
                  + jnp.where(own, dl_lane, 0.0).sum(1, keepdims=True))
            dstep = jnp.where(own, dxd * x32, 0.0).sum(1, keepdims=True)
            dcols = jnp.where(_lane() == h, dstep, dcols)
            dcols = jnp.where(_lane() == per + h, dl, dcols)
            drows_ref[0, 0, 0, h:h + 1, :] = -moved.sum(0, keepdims=True)
        dx_ref[0, :, lanes] = (d_ref[:, lanes] * dy32
                               + dxd * step).astype(dtype)
        # a lane's share of d L_end (row 0) and of d D (row 1)
        dlane_ref[0, 0, 0:1, lanes] = (
            (dafter[:, lanes] * before[:, lanes]).sum(0, keepdims=True)
            * jnp.exp(end) + (dw * xw).sum(0, keepdims=True))
        dlane_ref[0, 0, 1:2, lanes] = (dy32 * x32).sum(0, keepdims=True)
        dz.append((dy32 * grow).astype(dtype))
        weighted.append(xw.astype(dtype))
        ends.append(end)
    dcols_ref[0, 0] = dcols
    dz, weighted = jnp.concatenate(dz, 1), jnp.concatenate(weighted, 1)
    dcb = dcb.astype(dtype)
    dc_ref[0] = (_dot(dcb, bm, _NN) + _dot(dz, before_c, _NT)).astype(dtype)
    db_ref[0] = (_dot(dcb, cm, _TN)
                 + _dot(weighted, dafter_c, _NT)).astype(dtype)
    dstate[...] = (jnp.exp(jnp.concatenate(ends, 1)) * dafter
                   + _dot(cm, dz, _TN))


def _dims(x, b, cols, rows, d_lane, packed):
    """Sizes of a call from its operands. ``packed``: ``x``, ``b`` and
    ``c`` are ONE array, [batch, s, heads*p + 2 groups*n], the three side
    by side as the mixer's convolution writes them, and a block's index
    on the last axis says which is read: ``at`` is where ``b``'s and
    ``c``'s blocks of ``n`` lanes start."""
    groups, per = cols.shape[1], rows.shape[3]
    inner = d_lane.shape[1]
    if packed:
        n = (x.shape[2] - inner) // (2 * groups)
        at = (inner // n, inner // n + groups)
    else:
        n, at = b.shape[2] // groups, (0, 0)
    return groups, per, inner // (groups * per), n, inner, at


def _specs(per, p, n, chunk, at, chunk_of):
    """BlockSpecs of a grid step's blocks; ``chunk_of`` maps the grid's
    last index to the chunk (the backward walks them in reverse)."""
    wide = per * p

    def spec(block, *order, lanes_from=0):
        def index(z, g, i):
            where = {"z": z, "g": g, "c": chunk_of(i), "0": 0}
            found = tuple(where[o] for o in order)
            return found[:-1] + (found[-1] + lanes_from,)
        return pl.BlockSpec(block, index)

    return dict(
        x=spec((1, chunk, wide), *"zcg"),
        b=spec((1, chunk, n), *"zcg", lanes_from=at[0]),
        c=spec((1, chunk, n), *"zcg", lanes_from=at[1]),
        bc=spec((1, chunk, n), *"zcg"),
        cols=spec((1, 1, chunk, LANES), *"zgc0"),
        rows=spec((1, 1, 1, per, chunk), *"zcg00"),
        d=spec((1, wide), *"0g"),
        state=spec((1, 1, n, wide), *"zc0g"),
        lane=spec((1, 1, 2, wide), *"zc0g"))


# each ONE jitted function, like the kernels of ops/grouped_matmul.py: the
# layers of a model share shapes, so each lowers once a step
@functools.partial(jax.jit, static_argnames=("chunk", "save", "packed",
                                              "interpret"))
def _fwd_call(x, b, c, cols, rows, d_lane, chunk, save, packed, interpret):
    """``x`` [batch, s, heads*p], ``b``, ``c`` [batch, s, groups*n] (or
    ``packed``, ``_dims``): ``y`` [batch, s, heads*p] and, with ``save``,
    the state before each chunk."""
    groups, per, p, n, inner, at = _dims(x, b, cols, rows, d_lane, packed)
    bsz, s = x.shape[:2]
    sp = _specs(per, p, n, chunk, at, lambda i: i)
    out_shape = [jax.ShapeDtypeStruct((bsz, s, inner), x.dtype)]
    out_specs = [sp["x"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((bsz, s // chunk, n, inner),
                                              jnp.float32))
        out_specs.append(sp["state"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, per=per, p=p, save=save),
        grid=(bsz, groups, s // chunk),
        in_specs=[sp["x"], sp["b"], sp["c"], sp["cols"], sp["rows"],
                  sp["d"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, per * p), jnp.float32)],
        compiler_params=_SCAN_SEMANTICS, interpret=interpret,
        name="bps_ssd_fwd",
    )(x, b, c, cols, rows, d_lane)
    return tuple(out) if save else (out[0], None)


@functools.partial(jax.jit, static_argnames=("chunk", "packed", "interpret"))
def _bwd_call(x, b, c, cols, rows, d_lane, before, dy, chunk, packed,
              interpret):
    """The cotangents of ``x``, ``b`` and ``c`` (three arrays, ``packed``
    or not) and the small operands' partial sums (``_bwd_kernel``)."""
    groups, per, p, n, inner, at = _dims(x, b, cols, rows, d_lane, packed)
    bsz, s = x.shape[:2]
    nc = s // chunk
    sp = _specs(per, p, n, chunk, at, lambda i: nc - 1 - i)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, per=per, p=p),
        grid=(bsz, groups, nc),
        in_specs=[sp["x"], sp["b"], sp["c"], sp["cols"], sp["rows"],
                  sp["d"], sp["state"], sp["x"]],
        out_specs=[sp["x"], sp["bc"], sp["bc"], sp["cols"], sp["rows"],
                   sp["lane"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, inner), x.dtype),
                   jax.ShapeDtypeStruct((bsz, s, groups * n), x.dtype),
                   jax.ShapeDtypeStruct((bsz, s, groups * n), x.dtype),
                   jax.ShapeDtypeStruct(cols.shape, f32),
                   jax.ShapeDtypeStruct(rows.shape, f32),
                   jax.ShapeDtypeStruct((bsz, nc, 2, inner), f32)],
        scratch_shapes=[pltpu.VMEM((n, per * p), f32)],
        compiler_params=_SCAN_SEMANTICS, interpret=interpret,
        name="bps_ssd_bwd",
    )(x, b, c, cols, rows, d_lane, before, dy)


_EXACT = jax.lax.Precision.HIGHEST   # a float32 product with ones is exact


def _places(heads, groups):
    """[groups, 2 heads, 128] float32 ones and zeros: a group's place in
    ``cols`` for each of ``dt`` (the first ``heads`` rows) and ``L`` (the
    rest). The small operands are a number a head, and an array whose
    minor dimension is a group's 8 heads is 16 times its size in HBM, so
    a number goes to its lane by a product with this, never by a
    transpose of such an array."""
    per = heads // groups
    g, k, lane = (jax.lax.broadcasted_iota(jnp.int32, (groups, 2 * heads,
                                                       LANES), i)
                  for i in range(3))
    head, half = k % heads, k // heads
    return jnp.logical_and(head // per == g,
                           lane == half * per + head % per
                           ).astype(jnp.float32)


def _small(dt, a, d, groups, p, chunk):
    """``cols``, ``rows`` and ``d_lane`` (above) of ``dt`` [batch, s,
    heads], ``a`` and ``d`` [heads], all float32."""
    bsz, s, heads = dt.shape
    nc, per = s // chunk, heads // groups
    run = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, heads), axis=2)
    cols = jnp.einsum(
        "zsk,gkl->zgsl",
        jnp.concatenate([dt, run.reshape(bsz, s, heads)], -1),
        _places(heads, groups), precision=_EXACT)
    rows = run.transpose(0, 1, 3, 2).reshape(bsz, nc, groups, per, chunk)
    return cols, rows, jnp.repeat(d, p)[None]


def _forward(xbc, dt, a, d, groups, p, chunk, save, packed, interpret):
    """(``y`` [batch, s, heads*p], the states before each chunk or None)
    of ``xbc``, the three operands ``_fwd_call`` takes."""
    return _fwd_call(*xbc, *_small(dt, a, d, groups, p, chunk), chunk=chunk,
                     save=save, packed=packed, interpret=interpret)


def _backward(xbc, dt, a, d, before, dy, groups, p, chunk, packed, interpret):
    """The cotangents of x, b, c (as ``_bwd_call`` gives them), dt, a, d."""
    bsz, s, heads = dt.shape
    nc = s // chunk
    dx, db, dc, dcols, drows, dlane = _bwd_call(
        *xbc, *_small(dt, a, d, groups, p, chunk), before, dy, chunk=chunk,
        packed=packed, interpret=interpret)
    # back from their lanes: d dt as the kernel has it, the column half
    # of d L
    dcols = jnp.einsum("zgsl,gkl->zsk", dcols, _places(heads, groups),
                       precision=_EXACT)
    dlane = dlane.reshape(bsz, nc, 2, heads, p).sum(-1)
    # d L: its column and row halves, and at a chunk's last position what
    # its total carried to the next chunk's state
    dl = (dcols[..., heads:].reshape(bsz, nc, chunk, heads)
          + drows.reshape(bsz, nc, heads, chunk).transpose(0, 1, 3, 2))
    dl = dl.at[:, :, -1].add(dlane[:, :, 0])
    # L is a running sum inside a chunk: d a_t is the sum of d L from t on
    da = jnp.flip(jnp.cumsum(jnp.flip(dl, 2), 2), 2).reshape(bsz, s, heads)
    return (dx, db, dc, dcols[..., :heads] + da * a, (da * dt).sum((0, 1)),
            dlane[:, :, 1].sum((0, 1)))


def _flat(x, b, c):
    bsz, s = x.shape[:2]
    return (x.reshape(bsz, s, -1), b.reshape(bsz, s, -1),
            c.reshape(bsz, s, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_kernels(x, dt, a, b, c, d, chunk=CHUNK, interpret=False):
    """``ssd`` by the kernels, whatever the platform (``interpret``: in
    Pallas' interpreter, for the tests); ``dt``, ``a`` and ``d`` float32.
    The shapes are ``supported``'s."""
    return _kernels_fwd(x, dt, a, b, c, d, chunk, interpret, save=False)[0]


def _kernels_fwd(x, dt, a, b, c, d, chunk, interpret, save=True):
    y, before = _forward(_flat(x, b, c), dt, a, d, b.shape[2], x.shape[3],
                         chunk, save, False, interpret)
    return y.reshape(x.shape), (x, dt, a, b, c, d, before)


def _kernels_bwd(chunk, interpret, res, dy):
    x, dt, a, b, c, d, before = res
    dx, db, dc, ddt, da, dd = _backward(
        _flat(x, b, c), dt, a, d, before, dy.reshape(dy.shape[:2] + (-1,)),
        b.shape[2], x.shape[3], chunk, False, interpret)
    return (dx.reshape(x.shape), ddt, da, db.reshape(b.shape),
            dc.reshape(c.shape), dd)


ssd_kernels.defvjp(_kernels_fwd, _kernels_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def ssd_kernels_packed(xbc, dt, a, d, groups, n, chunk=CHUNK,
                       interpret=False):
    """``ssd_kernels`` of ``x``, ``b`` and ``c`` side by side on the last
    axis of ONE array (``ssd_packed``): the kernels read their blocks out
    of it where they lie, and no slice of it is made."""
    return _packed_fwd(xbc, dt, a, d, groups, n, chunk, interpret,
                       save=False)[0]


def _packed_fwd(xbc, dt, a, d, groups, n, chunk, interpret, save=True):
    p = (xbc.shape[2] - 2 * groups * n) // dt.shape[2]
    y, before = _forward((xbc,) * 3, dt, a, d, groups, p, chunk, save, True,
                         interpret)
    return y, (xbc, dt, a, d, before)


def _packed_bwd(groups, n, chunk, interpret, res, dy):
    xbc, dt, a, d, before = res
    p = (xbc.shape[2] - 2 * groups * n) // dt.shape[2]
    dx, db, dc, ddt, da, dd = _backward(
        (xbc,) * 3, dt, a, d, before, dy, groups, p, chunk, True, interpret)
    return jnp.concatenate([dx, db, dc], -1), ddt, da, dd


ssd_kernels_packed.defvjp(_packed_fwd, _packed_bwd)


def supported(x_shape, b_shape, chunk: int = CHUNK) -> bool:
    """Shapes the kernels take: a head's width divides a lane tile and a
    group's heads fill whole tiles (an even number of them at width 64);
    the state's size and the chunk whole lane tiles; a group's steps and
    running sums side by side in one tile."""
    _, s, heads, p = x_shape
    groups, n = b_shape[2:]
    if s % chunk or heads % groups or LANES % p:
        return False
    per = heads // groups
    return (n % LANES == 0 and chunk % LANES == 0
            and per % (LANES // p) == 0 and 2 * per <= LANES)


def ssd(x, dt, a, b, c, d, chunk: int = CHUNK):
    """``y`` [batch, s, heads, p] of the recurrence above.

    ``x`` [batch, s, heads, p]; ``dt`` [batch, s, heads] float32, the
    steps after their softplus; ``a`` [heads] float32, negative; ``b``,
    ``c`` [batch, s, groups, n]; ``d`` [heads]. ``s`` is any whole number
    of chunks of ``chunk`` positions; the state before the first position
    is zero. The kernels on the TPU where ``supported``, ``ssd_xla``
    elsewhere."""
    _sizes(x, b, chunk)
    kernels = (jax.default_backend() == "tpu"
               and supported(x.shape, b.shape, chunk))
    note_choice("ssd", "kernels" if kernels else "xla",
                (tuple(x.shape), tuple(b.shape), chunk),
                "XLA products: the kernels need a head width that divides 128 "
                "with a group's heads in whole lane tiles, and a state size "
                "and chunk of whole lane tiles")
    if kernels:
        f32 = jnp.float32
        return ssd_kernels(x, dt.astype(f32), a.astype(f32), b, c,
                           d.astype(f32), chunk, False)
    # named so that a fall-back from the kernels shows in a trace
    with jax.named_scope("bps_ssd_xla"):
        return ssd_xla(x, dt, a, b, c, d, chunk)


def ssd_packed(xbc, dt, a, d, groups: int, n: int, chunk: int = CHUNK):
    """``ssd`` of ``x``, ``b`` and ``c`` side by side on the last axis,
    ``xbc`` [batch, s, heads*p + 2 groups*n] as a Mamba-2 mixer's
    convolution writes them: ``y`` [batch, s, heads*p]. Where ``ssd``
    would take the kernels and ``b`` starts at a whole block of ``n``
    lanes, they read their blocks out of ``xbc`` itself; elsewhere this
    is ``ssd`` of the three slices."""
    bsz, s, width = xbc.shape
    heads = dt.shape[2]
    inner = width - 2 * groups * n
    x_shape, b_shape = (bsz, s, heads, inner // heads), (bsz, s, groups, n)
    if (jax.default_backend() == "tpu" and inner % n == 0
            and supported(x_shape, b_shape, chunk)):
        note_choice("ssd", "kernels_packed", (x_shape, b_shape, chunk))
        f32 = jnp.float32
        return ssd_kernels_packed(xbc, dt.astype(f32), a.astype(f32),
                                  d.astype(f32), groups, n, chunk, False)
    return ssd(xbc[..., :inner].reshape(x_shape), dt, a,
               xbc[..., inner:inner + groups * n].reshape(b_shape),
               xbc[..., inner + groups * n:].reshape(b_shape), d,
               chunk).reshape(bsz, s, inner)


def ssd_steps(x, dt, a, b, c, d):
    """The same recurrence ONE position at a time, float32: what the
    tests hold ``ssd`` against."""
    bsz, s, heads, p = x.shape
    groups, n = b.shape[2:]
    per = heads // groups
    f32 = jnp.float32

    def step(state, at):
        xt, dtt, bt, ct = at            # [batch, heads, p], [batch, heads]
        bt = jnp.repeat(bt, per, axis=1)        # [batch, heads, n]
        ct = jnp.repeat(ct, per, axis=1)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, (state * ct[:, :, None, :]).sum(-1) + d[:, None] * xt

    first = jnp.zeros((bsz, heads, p, n), f32)
    _, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)

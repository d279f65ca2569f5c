"""The gated delta rule of a Gated DeltaNet layer (Yang, Kautz and
Hatamizadeh, 2024) in its chunked form: ``models/gated_delta_net.py``'s
mixer, the linear attention of a ``qwen3_next`` decoder.

A value head ``h`` carries a MATRIX state ``S`` in R^{dk x dv} through the
positions of a sequence, zero before the first::

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

with ``g_t <= 0`` the log of a scalar decay a head and position and
``beta_t`` in (0, 1) how much of the old value under ``k_t`` the step
replaces. ``q`` and ``k`` come with ``hk`` heads and serve ``hv / hk``
value heads each (value head ``h`` reads key head ``h // (hv / hk)``).
One step at a time (``recurrence``) that is ``s`` dependent rank-one
updates. In chunks of ``C`` positions it is products (the WY form of the
delta rule): with ``gamma`` the running sum of ``g`` inside a chunk,

    A  = strict_tril(beta_i (k_i . k_j) exp(gamma_i - gamma_j))
    T  = (I + A)^-1                            unit lower triangular
    U  = T (beta v)        W = T (beta exp(gamma) k)
    V' = U - W S                               with S entering the chunk
    O  = (exp(gamma) q) S + tril((q_i . k_j) exp(gamma_i - gamma_j)) V'
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) k)^T V'

``A``, ``T``, ``U``, ``W`` and the masked ``q k^T`` hang on no state; the
three lines with ``S`` run chunk after chunk.

``gated_delta`` is the entry and the dispatcher, as ``ops.ssd.ssd`` is: on
the TPU, for the shapes ``supported`` takes (heads and chunks of whole
128-lane tiles), ``gated_delta_kernels``: four Pallas kernels under ONE
``jax.custom_vjp`` over ``(q, k, v, g, beta) -> o`` whose backward is
written by hand. ``bps_gdn_inverse`` makes ``k k^T``, the decays and ``A``
in VMEM and writes ``T``; ``bps_gdn_fwd`` makes ``U``, ``W``, the decayed
``q k^T``, ``exp(gamma) q`` and ``exp(gamma_C - gamma) k`` in VMEM from the
tiles of q, k and v where they lie and runs the three lines with the state
in scratch; ``bps_gdn_bwd`` walks the chunks in reverse, remakes the same
operands, and carries the cotangents on down to ``dq``, ``dv``, ``dT`` and
the parts of ``dk``, ``dgamma`` and ``dbeta`` that do not pass through
``A``; ``bps_gdn_inverse_bwd`` takes ``dT`` through ``A`` to the rest. Of
the arrays with two chunk axes only ``T`` and ``dT`` cross HBM. Elsewhere
(the CPU, a chunk of 64, odd heads) ``gated_delta_xla``: every chunk's
operands at once as XLA products (``_chunk_operands``, ``k k^T`` and ``q
k^T`` once a KEY head), the inverse as XLA products and a ``lax.scan``
across the chunks, differentiated by JAX, under the scope ``bps_gdn_xla``,
which on a TPU is a recorded fall-back (``note_choice``, site
``gdn_scan``). It is what the tests hold the kernels against. Both are pure
functions (safe under ``jax.checkpoint``).

``T`` is made without a triangular solve a row at a time: the 16 x 16
blocks on the diagonal are nilpotent of index 16, so their inverse is
``(I - a)(I + a^2)(I + a^4)(I + a^8)`` exactly (six small products, the
powers growing at most as binom(15, 7)); two (C = 64) or three (C = 128)
merges then double the inverted blocks, ``inv - inv L inv`` with ``L``
the blocks under the diagonal at that level, each on the whole [C, C]
matrix under a mask. Its backward keeps ``T`` alone: ``dA = -T^T (dT)
T^T``. XLA's form takes every product in float32 at the highest
precision; the kernel takes each as three bfloat16 passes (2^-17 a term)
and writes ``T`` in the compute dtype.

float32: ``g``, its running sums, every ``exp`` of them, ``A``, the
inverse's products and the state carried across chunks; the products'
operands (``k k^T``, ``q k^T``, ``T`` into ``U`` and ``W``, the state into
``W S`` and ``q S``, ``V'``) are in ``v``'s dtype and accumulate in
float32. The hand-written backward is the same: ``dA``, the state's
cotangent, ``dgamma``, ``dbeta`` and every sum that makes them are float32
(and ``U`` and the cotangents that are only ADDED, ``dM``, ``d(exp(gamma)
q)``, ``d(exp(gamma_C - gamma) k)``, stay float32 where the XLA form
rounds them); what enters a product (``dU``, ``dW``, ``dT``, ``dM * D``,
``dA * beta * D``) is rounded to ``v``'s dtype first. Above the diagonal an
exponent is set to ``-inf`` BEFORE the ``exp``, so nothing overflows in
either pass. docs/linear-attention.md has the picture.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.setup_record import note_choice

CHUNK = 128         # positions a chunk: a lane tile, what the kernels take
LANES = 128
BLOCK = 16          # the diagonal blocks inverted by their Neumann series
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST

# the name a ``jax.checkpoint`` policy keeps the kernels' inverse under
# (``bps_gdn_inverse``'s result in ``gated_delta_kernels``' forward rule:
# [b, n, hv, c, c] in the compute dtype, 134 MB a layer at 2 x 8192 x 32
# heads in bfloat16): it is what every other kernel reads, so a layer that
# keeps it runs the series and the merges once a step and not again in its
# recompute
INVERSE_NAME = "gdn_inverse"

# grid (batch, key head, chunk): the chunk axis carries the states in scratch
_SCAN_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_EVERY = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def recurrence(q, k, v, g, beta):
    """The rule one position at a time, float32: what the chunked form is
    held against. ``q``, ``k`` [b, s, hk, dk]; ``v`` [b, s, hv, dv];
    ``g``, ``beta`` [b, s, hv]. Returns ``o`` [b, s, hv, dv] float32."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    rep = hv // hk
    q, k = (jnp.repeat(t.astype(_F32), rep, axis=2) for t in (q, k))

    def step(state, at):            # state [b, hv, dk, dv]
        qt, kt, vt, gt, bt = at
        state = jnp.exp(gt)[..., None, None] * state
        old = jnp.einsum("bhkv,bhk->bhv", state, kt, precision=_HIGHEST)
        state = state + kt[..., None] * (bt[..., None] * (vt - old))[
            ..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt,
                                 precision=_HIGHEST)

    along = tuple(jnp.moveaxis(t.astype(_F32), 1, 0)
                  for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), _F32), along)
    return jnp.moveaxis(o, 0, 1)


def _iota2(c):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _mm(x, y):
    return jnp.matmul(x, y, precision=_HIGHEST, preferred_element_type=_F32)


def _series_and_merges(a, mm):
    """``(I + a)^-1`` for ONE strictly lower-triangular [c, c] block of
    float32 (or a batch of them) by ``mm``'s products: the Neumann series
    of the 16-wide diagonal blocks, then the merges that double them."""
    c = a.shape[-1]
    i, j = _iota2(c)
    diag = jnp.where(i // BLOCK == j // BLOCK, a, 0.0)
    inv = jnp.where(i == j, 1.0, 0.0) - diag
    power, reach = diag, 2
    while reach < BLOCK:            # (I - a)(I + a^2)(I + a^4)(I + a^8)
        power = mm(power, power)
        inv = inv + mm(inv, power)
        reach *= 2
    blk = BLOCK
    while blk < c:                  # [[i1, 0], [-i2 L i1, i2]] a pair
        below = (i // (2 * blk) == j // (2 * blk)) & (i // blk != j // blk)
        inv = inv - mm(mm(inv, jnp.where(below, a, 0.0)), inv)
        blk *= 2
    return inv


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., c, c] strictly lower triangular,
    float32, ``c`` a multiple of ``BLOCK`` by powers of two. Its backward
    keeps the inverse alone: ``d a = -T^T (d T) T^T``, two products, and
    nothing of the series or the merges."""
    return _series_and_merges(a, _mm)


def _inverse_fwd(a):
    inv = unit_lower_inverse(a)
    return inv, inv


def _inverse_bwd(inv, d_inv):
    i, j = _iota2(inv.shape[-1])
    t = jnp.swapaxes(inv, -1, -2)
    return (jnp.where(i > j, -_mm(_mm(t, d_inv), t), 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _check(q, k, v, g, beta, chunk):
    b, s, hk, dk = q.shape
    hv = v.shape[2]
    if (k.shape != q.shape or v.shape[:2] != (b, s) or hv % hk
            or g.shape != (b, s, hv) or beta.shape != (b, s, hv)):
        raise ValueError(
            f"q {q.shape} and k {k.shape} [b, s, hk, dk], v {v.shape} "
            f"[b, s, hv, dv] with hv a multiple of hk, g {g.shape} and "
            f"beta {beta.shape} [b, s, hv]")
    if s % chunk or chunk % BLOCK or (chunk // BLOCK) & (chunk // BLOCK - 1):
        raise ValueError(f"{s} positions in chunks of {chunk}: a chunk is "
                         f"{BLOCK} times a power of two and divides them")


def _chunk_operands(q, k, v, g, beta, chunk, inverse=None):
    """What the chunks hand to the pass across them, made for every chunk
    at once, [b, n, hv, c, ...]: ``U`` and ``W`` (``T`` applied), the
    masked and decayed ``q k^T``, ``exp(gamma) q``, ``exp(gamma_C -
    gamma) k`` in ``v``'s dtype, and ``exp(gamma_C)`` [b, n, hv]
    float32. ``inverse``: what inverts ``I + A`` (``unit_lower_inverse``
    unless given)."""
    b, s, hk, dk = q.shape
    hv = v.shape[2]
    rep, n, c, dt = hv // hk, s // chunk, chunk, v.dtype

    def chunks(t):      # [b, s, heads, ...] -> [b, n, heads, c, ...]
        return jnp.moveaxis(t.reshape((b, n, c) + t.shape[2:]), 2, 3)

    qc, kc = chunks(q.astype(dt)), chunks(k.astype(dt))   # [b,n,hk,c,dk]
    vc = chunks(v)                                        # [b,n,hv,c,dv]
    beta_c = chunks(beta.astype(_F32))                    # [b,n,hv,c]
    gamma = jnp.cumsum(chunks(g.astype(_F32)), -1)
    i, j = _iota2(c)
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.exp(jnp.where(i >= j, diff, -jnp.inf))    # [b,n,hv,c,c]

    def by_key_head(t):     # a key head's product for its value heads
        return jnp.repeat(t, rep, axis=2) if rep > 1 else t

    kk = by_key_head(jnp.einsum("bngid,bngjd->bngij", kc, kc,
                                preferred_element_type=_F32))
    qk = by_key_head(jnp.einsum("bngid,bngjd->bngij", qc, kc,
                                preferred_element_type=_F32))
    a = jnp.where(i > j, beta_c[..., None] * kk * decay, 0.0)
    t = (inverse or unit_lower_inverse)(a).astype(dt)
    k_v, q_v = by_key_head(kc), by_key_head(qc)           # [b,n,hv,c,dk]
    # results in the compute dtype straight from the products (the MXU
    # sums in float32 either way): no float32 [c, d] array a head is made
    u = jnp.einsum("bnhij,bnhjv->bnhiv", t,
                   (beta_c[..., None] * vc.astype(_F32)).astype(dt),
                   preferred_element_type=dt)
    w = jnp.einsum("bnhij,bnhjd->bnhid", t,
                   ((beta_c * jnp.exp(gamma))[..., None]
                    * k_v.astype(_F32)).astype(dt),
                   preferred_element_type=dt)
    mix = (qk * decay).astype(dt)                         # tril: decay's zeros
    q_in = (jnp.exp(gamma)[..., None] * q_v.astype(_F32)).astype(dt)
    last = gamma[..., -1]                                 # [b,n,hv]
    k_out = (jnp.exp(last[..., None] - gamma)[..., None]
             * k_v.astype(_F32)).astype(dt)
    return u, w, mix, q_in, k_out, jnp.exp(last)


def _across_xla(u, w, mix, q_in, k_out, keep):
    """The three lines with the state, chunk after chunk, as a
    ``lax.scan`` differentiated by JAX: ``o`` [b, s, hv * dv]."""
    b, n, hv, c, dv = u.shape
    dk, dt = w.shape[-1], u.dtype

    def one(state, at):             # state [b, hv, dk, dv] float32
        u_n, w_n, mix_n, q_n, k_n, keep_n = at
        state_dt = state.astype(dt)
        fresh = u_n - jnp.einsum("bhid,bhdv->bhiv", w_n, state_dt,
                                 preferred_element_type=_F32)
        fresh_dt = fresh.astype(dt)
        o = (jnp.einsum("bhid,bhdv->bhiv", q_n, state_dt,
                        preferred_element_type=_F32)
             + jnp.einsum("bhij,bhjv->bhiv", mix_n, fresh_dt,
                          preferred_element_type=_F32))
        state = keep_n[..., None, None] * state + jnp.einsum(
            "bhid,bhiv->bhdv", k_n, fresh_dt, preferred_element_type=_F32)
        return state, o.astype(dt)

    along = tuple(jnp.moveaxis(x, 1, 0)
                  for x in (u, w, mix, q_in, k_out, keep))
    _, o = jax.lax.scan(one, jnp.zeros((b, hv, dk, dv), _F32), along)
    # [n, b, hv, c, dv] -> [b, s, hv * dv]
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, n * c, hv * dv)


# ------------------------------------------------------------ the kernels
# The rule as four Pallas kernels under ONE custom_vjp over (q, k, v, g,
# beta) -> o, its backward written by hand. What a chunk hands to the lines
# with the state (``A``, ``U``, ``W``, the decayed ``q k^T``, ``exp(gamma)
# q``, ``exp(gamma_C - gamma) k``) is made in VMEM from the tiles of q, k
# and v where they lie in [b, s, heads * d] and from gamma and beta, and is
# taken apart there in the backward: of the arrays with two chunk axes only
# ``T`` and its cotangent cross HBM, in the compute dtype, beside the state
# before each chunk [b, n, hv, dk, dv] float32 that the backward reads.
#
# A grid step is one batch row, one KEY head and one chunk, and takes every
# value head of that key head in turn (``rep`` of them: ``k k^T`` and ``q
# k^T`` are made once, ``dq`` and ``dk`` are summed in VMEM). ``bps_gdn_fwd``
# and ``bps_gdn_bwd`` walk the chunk axis last and in order (in reverse in
# the backward) with the states (their cotangents) [rep, dk, dv] float32 in
# scratch; ``bps_gdn_inverse`` and ``bps_gdn_inverse_bwd`` take the chunks
# in any order.
#
# gamma and beta come as ROWS, [b, n, hk, 2 rep, c] float32 (``_vector_rows``:
# XLA's running sum over [b, s, hv], 2 MB), and a kernel that wants one down
# the sublanes (a factor a position) turns it there (``_column``); the
# backward's ``dgamma`` and ``dbeta`` leave as rows too and XLA takes the
# reverse running sum.

def _dot(lhs, rhs, contract):
    return jax.lax.dot_general(lhs, rhs, (contract, ((), ())),
                               preferred_element_type=_F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _halves(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(_F32)).astype(jnp.bfloat16)


def _dot3(x, y, contract=_NN):
    """A float32 product as three bfloat16 passes of the operands' high
    and low halves: an error of 2^-17 of a term."""
    xh, xl = _halves(x)
    yh, yl = _halves(y)
    return (_dot(xh, yh, contract) + _dot(xh, yl, contract)
            + _dot(xl, yh, contract))


def _column(row):
    """[1, c] -> [c, 1], exactly: the diagonal of the row on every
    sublane, summed along the lanes."""
    i, j = _iota2(row.shape[-1])
    return jnp.sum(jnp.where(i == j, row, 0.0), axis=1, keepdims=True)


def _row(col):
    """[c, 1] -> [1, c], exactly."""
    i, j = _iota2(col.shape[0])
    return jnp.sum(jnp.where(i == j, col, 0.0), axis=0, keepdims=True)


def _lane_sum(*terms):
    """The sum of the terms' sums along the lanes, [c, 1]: terms of one
    width are added before ONE sum along the lanes."""
    by_width = {}
    for t in terms:
        by_width[t.shape[1]] = by_width.get(t.shape[1], 0.0) + t
    return sum(jnp.sum(t, 1, keepdims=True) for t in by_width.values())


def _decays(gcol, grow, strict):
    """``exp(gamma_i - gamma_j)`` under the diagonal (``strict``) or on and
    under it, zero elsewhere: the exponent is ``-inf`` there BEFORE the
    ``exp``."""
    i, j = _iota2(grow.shape[-1])
    return jnp.exp(jnp.where(i > j if strict else i >= j, gcol - grow,
                             -jnp.inf))


def _last_on_lanes(col, width):
    """A column's last entry on every lane of a row, [c, 1] -> [1, width]
    (Mosaic broadcasts one number along one axis at a time, not two)."""
    at_last = jax.lax.broadcasted_iota(
        jnp.int32, (col.shape[0], width), 0) == col.shape[0] - 1
    return jnp.sum(jnp.where(at_last, col, 0.0), axis=0, keepdims=True)


def _head_vectors(rows_ref, r, rep):
    """Value head ``r``'s gamma as a row and a column and its beta as a
    column."""
    grow = rows_ref[r:r + 1, :]
    return grow, _column(grow), _column(rows_ref[rep + r:rep + r + 1, :])


class _Chunk:
    """What one value head's chunk hands to the lines with the state, made
    in VMEM: ``_chunk_operands``' values for one [c, c] matrix."""

    def __init__(self, q32, k32, qk, v, inv, rows_ref, r, rep):
        dt, c = v.dtype, qk.shape[0]
        grow, gcol, self.bcol = _head_vectors(rows_ref, r, rep)
        self.inv = inv
        self.eg = jnp.exp(gcol)                             # [c, 1]
        self.decay = _decays(gcol, grow, False)             # [c, c]
        self.k_decay = jnp.exp(grow[:, c - 1:] - gcol)      # [c, 1]
        self.keep = jnp.exp(_last_on_lanes(gcol, v.shape[-1]))  # [1, dv]
        self.bv = (self.bcol * v.astype(_F32)).astype(dt)
        self.bek = ((self.bcol * self.eg) * k32).astype(dt)
        self.u = _dot(inv, self.bv, _NN)                    # float32
        self.w = _dot(inv, self.bek, _NN).astype(dt)
        self.mix = (qk * self.decay).astype(dt)
        self.q_in = (self.eg * q32).astype(dt)
        self.k_out = (self.k_decay * k32).astype(dt)


def _inverse_kernel(k_ref, rows_ref, inv_ref, *, rep):
    k = k_ref[...]
    kk = _dot(k, k, _NT)                        # once a KEY head
    for r in range(rep):
        grow, gcol, bcol = _head_vectors(rows_ref, r, rep)
        a = bcol * kk * _decays(gcol, grow, True)
        inv_ref[r] = _series_and_merges(a, _dot3).astype(inv_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, inv_ref, o_ref, *rest, rep,
                save):
    state = rest[-1]                            # [rep, dk, dv] float32
    dt, dv = v_ref.dtype, v_ref.shape[-1] // rep

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    q, k = q_ref[...], k_ref[...]
    q32, k32 = q.astype(_F32), k.astype(_F32)
    qk = _dot(q, k, _NT)
    for r in range(rep):
        lanes = slice(r * dv, (r + 1) * dv)
        x = _Chunk(q32, k32, qk, v_ref[:, lanes], inv_ref[r], rows_ref, r,
                   rep)
        before = state[r]
        if save:
            rest[0][r] = before
        before_dt = before.astype(dt)
        fresh = (x.u - _dot(x.w, before_dt, _NN)).astype(dt)
        o_ref[:, lanes] = (_dot(x.q_in, before_dt, _NN)
                           + _dot(x.mix, fresh, _NN)).astype(o_ref.dtype)
        state[r] = x.keep * before + _dot(x.k_out, fresh, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, inv_ref, before_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dinv_ref, drows_ref, dstate, *, rep):
    dt, dv = v_ref.dtype, v_ref.shape[-1] // rep
    c = q_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    q, k = q_ref[...], k_ref[...]
    q32, k32 = q.astype(_F32), k.astype(_F32)
    qk = _dot(q, k, _NT)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    dq, dk, dqk = jnp.zeros_like(q32), jnp.zeros_like(k32), jnp.zeros_like(qk)
    for r in range(rep):
        lanes = slice(r * dv, (r + 1) * dv)
        v = v_ref[:, lanes]
        x = _Chunk(q32, k32, qk, v, inv_ref[r], rows_ref, r, rep)
        before = before_ref[r]
        before_dt = before.astype(dt)
        dafter = dstate[r]                  # d of the state AFTER the chunk
        dafter_dt = dafter.astype(dt)
        do = do_ref[:, lanes]
        # the three lines with the state
        fresh = (x.u - _dot(x.w, before_dt, _NN)).astype(dt)
        du = (_dot(x.mix, do, _TN)
              + _dot(x.k_out, dafter_dt, _NN)).astype(dt)       # [c, dv]
        dw = (-_dot(du, before_dt, _NT)).astype(dt)             # [c, dk]
        dmix = _dot(do, fresh, _NT)                             # [c, c]
        dq_in = _dot(do, before_dt, _NT)                        # [c, dk]
        dk_out = _dot(fresh, dafter_dt, _NT)                    # [c, dk]
        # ``keep`` is ONE number a chunk: its cotangent is the whole sum
        dkeep = jnp.sum(jnp.sum(dafter * before, 0, keepdims=True), 1,
                        keepdims=True)                          # [1, 1]
        dstate[r] = (x.keep * dafter + _dot(x.q_in, do, _TN)
                     - _dot(x.w, du, _TN))
        # and on down through what the chunk made of q, k, v, gamma, beta
        dinv_ref[r] = (_dot(du, x.bv, _NT)
                       + _dot(dw, x.bek, _NT)).astype(dinv_ref.dtype)
        dbv = _dot(x.inv, du, _TN)                              # [c, dv]
        dbek = _dot(x.inv, dw, _TN)                             # [c, dk]
        dv_ref[:, lanes] = (x.bcol * dbv).astype(dv_ref.dtype)
        # a sum along the lanes costs more than the sums of what it sums:
        # each position's terms are added first
        via_w = x.eg * (dbek * k32)                         # [c, dk]
        via_k = x.k_decay * (dk_out * k32)
        e = dmix * qk * x.decay                 # dD * D: rows less columns
        dbeta = _row(_lane_sum(dbv * v.astype(_F32), via_w))
        dgamma = _row(_lane_sum(x.bcol * via_w + x.eg * (dq_in * q32)
                                - via_k, e))
        dlast = (_lane_sum(jnp.sum(via_k, 0, keepdims=True))
                 + x.keep[:, :1] * dkeep)                           # [1, 1]
        drows_ref[r:r + 1, :] = (dgamma - jnp.sum(e, 0, keepdims=True)
                                 + jnp.where(last, dlast, 0.0))
        drows_ref[rep + r:rep + r + 1, :] = dbeta
        dqk = dqk + dmix * x.decay
        dq = dq + x.eg * dq_in
        dk = dk + (x.bcol * x.eg) * dbek + x.k_decay * dk_out
    dqk = dqk.astype(dt)                        # once a KEY head
    dq_ref[...] = (dq + _dot(dqk, k, _NN)).astype(dq_ref.dtype)
    dk_ref[...] = dk + _dot(dqk, q, _TN)        # float32: A's part to come


def _inverse_bwd_kernel(k_ref, rows_ref, inv_ref, dinv_ref, dk_in_ref,
                        drows_in_ref, dk_ref, drows_ref, *, rep):
    k = k_ref[...]
    dt = k.dtype
    kk = _dot(k, k, _NT)
    i, j = _iota2(kk.shape[0])
    dkk = jnp.zeros_like(kk)
    for r in range(rep):
        inv = inv_ref[r]
        grow, gcol, bcol = _head_vectors(rows_ref, r, rep)
        beta = slice(rep + r, rep + r + 1)      # its row of the rows
        inner = _dot(_dot(inv, dinv_ref[r], _TN).astype(inv.dtype), inv, _NT)
        da = jnp.where(i > j, -inner, 0.0)      # under the diagonal
        decay = _decays(gcol, grow, True)
        dkk = dkk + da * bcol * decay
        part = da * kk * decay
        dbeta = _row(jnp.sum(part, 1, keepdims=True))
        drows_ref[r:r + 1, :] = (
            drows_in_ref[r:r + 1, :] + rows_ref[beta, :] * dbeta
            - jnp.sum(bcol * part, 0, keepdims=True))
        drows_ref[beta, :] = drows_in_ref[beta, :] + dbeta
    dkk = dkk.astype(dt)
    dk_ref[...] = (dk_in_ref[...] + _dot(dkk, k, _NN)
                   + _dot(dkk, k, _TN)).astype(dk_ref.dtype)


def _vector_rows(g, beta, hk, chunk):
    """``gamma`` (the running sum of ``g`` inside a chunk) and ``beta`` as
    rows: [b, s, hv] float32 twice -> [b, n, hk, 2 rep, c]."""
    b, s, hv = g.shape

    def rows(t):                    # [b, s, hv] -> [b, n, hk, rep, c]
        t = jnp.moveaxis(t.astype(_F32).reshape(b, s // chunk, chunk, hv),
                         2, 3)
        return t.reshape(b, s // chunk, hk, hv // hk, chunk)

    return jnp.concatenate([jnp.cumsum(rows(g), -1), rows(beta)], axis=3)


def _from_rows(rows):
    """[b, n, hk, rep, c] -> [b, s, hv]."""
    b, n, hk, rep, c = rows.shape
    return jnp.moveaxis(rows.reshape(b, n, hk * rep, c), 2, 3).reshape(
        b, n * c, hk * rep)


class _Specs:
    """The blocks of one grid step. ``at`` maps a grid point to (batch,
    chunk, key head)."""

    def __init__(self, c, dk, dv, rep, at):
        self.c, self.dk, self.dv, self.rep, self.at = c, dk, dv, rep, at

    def positions(self, width):
        """A chunk's [c, width] block of [b, s, heads * d]: a key head's
        lanes (its value heads') where they lie."""
        return pl.BlockSpec((None, self.c, width), self.at)

    def tiles(self, *block):
        """[b, n, hk, ...] or [b, n, hv, ...] at (batch, chunk, key
        head): ``block`` is what follows the first two axes."""
        return pl.BlockSpec((None, None) + block,
                            lambda *g: self.at(*g) + (0, 0))

    @property
    def q(self):
        return self.positions(self.dk)

    @property
    def v(self):
        return self.positions(self.rep * self.dv)

    @property
    def rows(self):
        return self.tiles(None, 2 * self.rep, self.c)

    @property
    def inv(self):
        return self.tiles(self.rep, self.c, self.c)

    @property
    def state(self):
        return self.tiles(self.rep, self.dk, self.dv)


def _sizes(q, v, rows):
    b, s, key_dim = q.shape
    _, n, hk, rep2, c = rows.shape
    rep = rep2 // 2
    return b, n, hk, rep, c, key_dim // hk, v.shape[2] // (hk * rep)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _inverse_call(k, rows, dtype, interpret):
    b, n, hk, rep, c, dk, _ = _sizes(k, k, rows)
    at = _Specs(c, dk, dk, rep, lambda z, i, h: (z, i, h))
    return pl.pallas_call(
        functools.partial(_inverse_kernel, rep=rep), grid=(b, n, hk),
        in_specs=[at.q, at.rows], out_specs=at.inv,
        out_shape=jax.ShapeDtypeStruct((b, n, hk * rep, c, c), dtype),
        compiler_params=_EVERY, interpret=interpret, name="bps_gdn_inverse",
    )(k, rows)


@functools.partial(jax.jit, static_argnames=("save", "interpret"))
def _fwd_call(q, k, v, rows, inv, save, interpret):
    b, n, hk, rep, c, dk, dv = _sizes(q, v, rows)
    at = _Specs(c, dk, dv, rep, lambda z, h, i: (z, i, h))
    out_shape, out_specs = [jax.ShapeDtypeStruct(v.shape, v.dtype)], [at.v]
    if save:
        out_shape.append(
            jax.ShapeDtypeStruct((b, n, hk * rep, dk, dv), _F32))
        out_specs.append(at.state)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, rep=rep, save=save), grid=(b, hk, n),
        in_specs=[at.q, at.q, at.v, at.rows, at.inv],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), _F32)],
        compiler_params=_SCAN_SEMANTICS, interpret=interpret,
        name="bps_gdn_fwd",
    )(q, k, v, rows, inv)
    return out if save else out[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(q, k, v, rows, inv, before, do, interpret):
    b, n, hk, rep, c, dk, dv = _sizes(q, v, rows)
    at = _Specs(c, dk, dv, rep, lambda z, h, i: (z, n - 1 - i, h))
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, rep=rep), grid=(b, hk, n),
        in_specs=[at.q, at.q, at.v, at.rows, at.inv, at.state, at.v],
        out_specs=[at.q, at.q, at.v, at.inv, at.rows],
        out_shape=[like(q.shape, q.dtype), like(k.shape, _F32),
                   like(v.shape, v.dtype), like(inv.shape, inv.dtype),
                   like(rows.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), _F32)],
        compiler_params=_SCAN_SEMANTICS, interpret=interpret,
        name="bps_gdn_bwd",
    )(q, k, v, rows, inv, before, do)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _inverse_bwd_call(k, rows, inv, dinv, dk, drows, interpret):
    b, n, hk, rep, c, dkey, _ = _sizes(k, k, rows)
    at = _Specs(c, dkey, dkey, rep, lambda z, i, h: (z, i, h))
    return pl.pallas_call(
        functools.partial(_inverse_bwd_kernel, rep=rep), grid=(b, n, hk),
        in_specs=[at.q, at.rows, at.inv, at.inv, at.q, at.rows],
        out_specs=[at.q, at.rows],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(rows.shape, _F32)],
        compiler_params=_EVERY, interpret=interpret,
        name="bps_gdn_inverse_bwd",
    )(k, rows, inv, dinv, dk, drows)


def _kernel_operands(q, k, v, g, beta, chunk):
    """q, k and v as [b, s, heads * d] in ``v``'s dtype (no copy: the
    heads' lanes lie side by side already) and the vectors' rows."""
    _check(q, k, v, g, beta, chunk)
    b, s, hk, _ = q.shape
    return (q.astype(v.dtype).reshape(b, s, -1),
            k.astype(v.dtype).reshape(b, s, -1), v.reshape(b, s, -1),
            _vector_rows(g, beta, hk, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gated_delta_kernels(q, k, v, g, beta, chunk: int = CHUNK,
                        interpret: bool = False):
    """``gated_delta`` by the four kernels, whatever the platform
    (``interpret``: in Pallas' interpreter, for the tests); the shapes are
    ``supported``'s. One ``jax.custom_vjp``: the backward is the kernels
    ``bps_gdn_bwd`` and ``bps_gdn_inverse_bwd`` and keeps ``T``
    (``INVERSE_NAME``) and the state before each chunk."""
    q_, k_, v_, rows = _kernel_operands(q, k, v, g, beta, chunk)
    inv = _inverse_call(k_, rows, v.dtype, interpret)
    return _fwd_call(q_, k_, v_, rows, inv, False, interpret).reshape(v.shape)


def _kernels_fwd(q, k, v, g, beta, chunk, interpret):
    q_, k_, v_, rows = _kernel_operands(q, k, v, g, beta, chunk)
    inv = checkpoint_name(_inverse_call(k_, rows, v.dtype, interpret),
                          INVERSE_NAME)
    o, before = _fwd_call(q_, k_, v_, rows, inv, True, interpret)
    return o.reshape(v.shape), (q, k, v, g, beta, inv, before)


def _kernels_bwd(chunk, interpret, res, do):
    q, k, v, g, beta, inv, before = res
    q_, k_, v_, rows = _kernel_operands(q, k, v, g, beta, chunk)
    dq, dk, dv, dinv, drows = _bwd_call(
        q_, k_, v_, rows, inv, before, do.astype(v.dtype).reshape(v_.shape),
        interpret)
    dk, drows = _inverse_bwd_call(k_, rows, inv, dinv, dk, drows, interpret)
    rep = rows.shape[3] // 2
    # gamma is a running sum inside a chunk: dg its reverse running sum
    dgamma = jnp.flip(jnp.cumsum(jnp.flip(drows[..., :rep, :], -1), -1), -1)
    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype), dv.reshape(v.shape),
            _from_rows(dgamma).astype(g.dtype),
            _from_rows(drows[..., rep:, :]).astype(beta.dtype))


gated_delta_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def supported(q_shape, v_shape, chunk: int) -> bool:
    """Shapes the kernels take: heads of whole lane tiles on both sides
    of the state and a chunk of whole lane tiles' rows."""
    return (q_shape[3] % LANES == 0 and v_shape[3] % LANES == 0
            and chunk % LANES == 0)


def gated_delta_xla(q, k, v, g, beta, chunk: int = CHUNK):
    """``gated_delta`` as XLA products, differentiated by JAX."""
    _check(q, k, v, g, beta, chunk)
    out = _across_xla(*_chunk_operands(q, k, v, g, beta, chunk))
    return out.reshape(v.shape)


def gated_delta(q, k, v, g, beta, chunk: int = CHUNK):
    """The gated delta rule over ``q``, ``k`` [b, s, hk, dk] (normalised
    and scaled by the caller), ``v`` [b, s, hv, dv], ``g`` (the decay's
    log, <= 0) and ``beta`` [b, s, hv] float32, from a zero state, in
    chunks of ``chunk`` positions: ``o`` [b, s, hv, dv] in ``v``'s dtype.
    A pure function (safe under ``jax.checkpoint``). On the TPU, for the
    shapes ``supported`` takes, the four kernels of ``gated_delta_kernels``;
    elsewhere (the CPU, odd shapes, a chunk shorter than a lane tile) XLA
    products and a ``lax.scan``."""
    kernels = (jax.default_backend() == "tpu"
               and supported(q.shape, v.shape, chunk))
    note_choice("gdn_scan", "kernels" if kernels else "xla",
                (tuple(q.shape), tuple(v.shape), chunk),
                "XLA products a chunk and a lax.scan across the chunks: the "
                f"kernels need heads and chunks of whole lane tiles ({LANES})")
    if kernels:
        return gated_delta_kernels(q, k, v, g, beta, chunk)
    with jax.named_scope("bps_gdn_xla"):
        return gated_delta_xla(q, k, v, g, beta, chunk)

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

``A``, ``T``, ``U``, ``W`` and the masked ``q k^T`` hang on no state and
are made for every chunk at once (``_chunk_operands``: XLA products
differentiated by JAX, ``k k^T`` and ``q k^T`` once a KEY head); the three
lines with ``S`` run chunk after chunk.

``gated_delta`` is the entry and the dispatcher, as ``ops.ssd.ssd`` is: on
the TPU, for the shapes ``supported`` takes (heads and chunks of whole
128-lane tiles), ``gated_delta_kernels``: the inverse and the pass across
the chunks are Pallas kernels (``bps_gdn_inverse`` / ``_bwd``,
``bps_gdn_fwd`` / ``bps_gdn_bwd``, each pair a ``jax.custom_vjp``);
elsewhere (the CPU, a chunk of 64, odd heads) ``gated_delta_xla``, the
inverse as XLA products and a ``lax.scan`` across the chunks under the
scope ``bps_gdn_xla``, which on a TPU is a recorded fall-back
(``note_choice``, site ``gdn_scan``). Both are pure functions (safe under
``jax.checkpoint``).

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
float32. Above the diagonal an exponent is set to ``-inf`` BEFORE the
``exp``, so nothing overflows in either pass. docs/linear-attention.md has
the picture.
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
# (``inverse_kernels``: [b, n, hv, c, c] in the compute dtype, 134 MB a
# layer at 2 x 8192 x 32 heads in bfloat16): it is what the inverse's own
# backward reads, so a layer that keeps it runs the series and the merges
# once a step and not again in its recompute
INVERSE_NAME = "gdn_inverse"

# grid (batch, value head, chunk): the chunk axis carries the state in scratch
_SCAN_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


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
# The pass ACROSS the chunks as two Pallas kernels under a custom_vjp: what
# a chunk needs of the state is four products of [c, 128] tiles, and a
# ``lax.scan`` pays for each with a round trip of the state and of every
# operand's slice through HBM and a loop iteration's fixed cost. A grid
# step is one batch row, one value head and one chunk, the chunk axis last
# and in order; the state [dk, dv] float32 lives in scratch. The forward
# that a backward will follow also writes the state BEFORE each chunk
# ([b, n, hv, dk, dv] float32); the backward walks the chunks in reverse
# with the state's cotangent in scratch and remakes ``V'`` from it.

def _dot(lhs, rhs, contract):
    return jax.lax.dot_general(lhs, rhs, (contract, ((), ())),
                               preferred_element_type=_F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _across_fwd_kernel(u_ref, w_ref, mix_ref, q_ref, k_ref, keep_ref, o_ref,
                       *rest, save):
    state = rest[-1]
    dt = u_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    before = state[...]
    if save:
        rest[0][...] = before
    before_dt = before.astype(dt)
    fresh = (u_ref[...].astype(_F32)
             - _dot(w_ref[...], before_dt, _NN)).astype(dt)
    o_ref[...] = (_dot(q_ref[...], before_dt, _NN)
                  + _dot(mix_ref[...], fresh, _NN)).astype(o_ref.dtype)
    state[...] = keep_ref[...] * before + _dot(k_ref[...], fresh, _TN)


def _across_bwd_kernel(u_ref, w_ref, mix_ref, q_ref, k_ref, keep_ref,
                       before_ref, do_ref, du_ref, dw_ref, dmix_ref, dq_ref,
                       dk_ref, dkeep_ref, dstate):
    dt = u_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    before = before_ref[...]
    before_dt = before.astype(dt)
    dafter = dstate[...]                    # d of the state AFTER the chunk
    dafter_dt = dafter.astype(dt)
    do = do_ref[...]
    fresh = (u_ref[...].astype(_F32)
             - _dot(w_ref[...], before_dt, _NN)).astype(dt)
    dfresh = (_dot(mix_ref[...], do, _TN)
              + _dot(k_ref[...], dafter_dt, _NN))           # [c, dv]
    dfresh_dt = dfresh.astype(dt)
    du_ref[...] = dfresh_dt
    dw_ref[...] = (-_dot(dfresh_dt, before_dt, _NT)).astype(dt)
    dmix_ref[...] = _dot(do, fresh, _NT).astype(dt)
    dq_ref[...] = _dot(do, before_dt, _NT).astype(dt)
    dk_ref[...] = _dot(fresh, dafter_dt, _NT).astype(dt)
    # ``keep`` is one number on every lane: a lane's cotangent is its own
    # column's sum, and the caller's broadcast sums the lanes
    dkeep_ref[...] = (dafter * before).sum(0, keepdims=True)
    dstate[...] = (keep_ref[...] * dafter + _dot(q_ref[...], do, _TN)
                   - _dot(w_ref[...], dfresh_dt, _TN))


def _tile(rows, cols, rev=None):
    """A [rows, cols] block of [b, n, hv, rows, cols] at (batch, chunk,
    head); ``rev``: the chunks walked from the last (``rev`` of them)."""
    def at(z, h, i):
        return z, (i if rev is None else rev - 1 - i), h, 0, 0
    return pl.BlockSpec((None, None, None, rows, cols), at)


def _positions(c, dv, rev=None):
    """A chunk's [c, dv] block of [b, s, hv * dv]: a head's lanes where
    they lie."""
    def at(z, h, i):
        return z, (i if rev is None else rev - 1 - i), h
    return pl.BlockSpec((None, c, dv), at)


@functools.partial(jax.jit, static_argnames=("save", "interpret"))
def _across_fwd_call(u, w, mix, q_in, k_out, keep, save, interpret):
    b, n, hv, c, dv = u.shape
    dk = w.shape[-1]
    out_shape = [jax.ShapeDtypeStruct((b, n * c, hv * dv), u.dtype)]
    out_specs = [_positions(c, dv)]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, n, hv, dk, dv), _F32))
        out_specs.append(_tile(dk, dv))
    out = pl.pallas_call(
        functools.partial(_across_fwd_kernel, save=save),
        grid=(b, hv, n),
        in_specs=[_tile(c, dv), _tile(c, dk), _tile(c, c), _tile(c, dk),
                  _tile(c, dk), _tile(1, dv)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_SCAN_SEMANTICS, interpret=interpret,
        name="bps_gdn_fwd",
    )(u, w, mix, q_in, k_out, keep)
    return out if save else out[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _across_bwd_call(u, w, mix, q_in, k_out, keep, before, do, interpret):
    b, n, hv, c, dv = u.shape
    dk = w.shape[-1]
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        _across_bwd_kernel, grid=(b, hv, n),
        in_specs=[_tile(c, dv, n), _tile(c, dk, n), _tile(c, c, n),
                  _tile(c, dk, n), _tile(c, dk, n), _tile(1, dv, n),
                  _tile(dk, dv, n), _positions(c, dv, n)],
        out_specs=[_tile(c, dv, n), _tile(c, dk, n), _tile(c, c, n),
                   _tile(c, dk, n), _tile(c, dk, n), _tile(1, dv, n)],
        out_shape=[like(u), like(w), like(mix), like(q_in), like(k_out),
                   like(keep)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_SCAN_SEMANTICS, interpret=interpret,
        name="bps_gdn_bwd",
    )(u, w, mix, q_in, k_out, keep, before, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _across_kernels(u, w, mix, q_in, k_out, keep, interpret=False):
    """``_across_xla`` by the kernels ``bps_gdn_fwd`` / ``bps_gdn_bwd``;
    ``keep`` [b, n, hv, 1, dv] float32, a chunk's ``exp(gamma_C)`` on
    every lane of the state's rows."""
    return _across_fwd_call(u, w, mix, q_in, k_out, keep, False, interpret)


def _across_vjp_fwd(u, w, mix, q_in, k_out, keep, interpret):
    o, before = _across_fwd_call(u, w, mix, q_in, k_out, keep, True,
                                 interpret)
    return o, (u, w, mix, q_in, k_out, keep, before)


def _across_vjp_bwd(interpret, res, do):
    return tuple(_across_bwd_call(*res, do, interpret))


_across_kernels.defvjp(_across_vjp_fwd, _across_vjp_bwd)


# The inverse as a kernel a matrix: XLA's form sends each of its twelve
# products' operands and result through HBM ([b, n, hv, c, c] float32 three
# times a product, 10 GB a layer and pass at 2 x 8192 x 32 heads); here a
# grid step reads one [c, c] matrix, runs the series and the merges in VMEM
# and writes the inverse. A float32 product is three bfloat16 passes of the
# operands' high and low halves (an error of 2^-17 of a term: the inverse
# is rounded to the compute dtype's 2^-9 where it is used). The kernel
# writes the inverse IN the compute dtype, which is also all its backward
# keeps: ``dA = -T^T (dT) T^T`` from the rounded ``T`` and the cotangent of
# the rounded ``T``, two plain products of operands in that dtype.

def _halves(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(_F32)).astype(jnp.bfloat16)


def _dot3(x, y, contract=_NN):
    xh, xl = _halves(x)
    yh, yl = _halves(y)
    return (_dot(xh, yh, contract) + _dot(xh, yl, contract)
            + _dot(xl, yh, contract))


def _inverse_kernel(a_ref, inv_ref):
    inv_ref[...] = _series_and_merges(a_ref[...], _dot3).astype(
        inv_ref.dtype)


def _inverse_bwd_kernel(inv_ref, d_ref, da_ref):
    inv, d_inv = inv_ref[...], d_ref[...]
    i, j = _iota2(inv.shape[-1])
    inner = _dot(_dot(inv, d_inv, _TN).astype(inv.dtype), inv, _NT)
    da_ref[...] = jnp.where(i > j, -inner, 0.0)     # under the diagonal


def _matrix(c):
    return pl.BlockSpec((None, None, None, c, c),
                        lambda z, i, h: (z, i, h, 0, 0))


_EVERY = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _inverse_call(a, dtype, interpret):
    c = a.shape[-1]
    return pl.pallas_call(
        _inverse_kernel, grid=a.shape[:3], in_specs=[_matrix(c)],
        out_specs=_matrix(c), out_shape=jax.ShapeDtypeStruct(a.shape, dtype),
        compiler_params=_EVERY, interpret=interpret, name="bps_gdn_inverse",
    )(a)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _inverse_bwd_call(inv, d_inv, interpret):
    c = inv.shape[-1]
    return pl.pallas_call(
        _inverse_bwd_kernel, grid=inv.shape[:3],
        in_specs=[_matrix(c), _matrix(c)], out_specs=_matrix(c),
        out_shape=jax.ShapeDtypeStruct(inv.shape, _F32),
        compiler_params=_EVERY, interpret=interpret,
        name="bps_gdn_inverse_bwd",
    )(inv, d_inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def inverse_kernels(a, dtype, interpret=False):
    """``unit_lower_inverse`` of ``a`` [b, n, hv, c, c] float32 by the
    kernels ``bps_gdn_inverse`` / ``bps_gdn_inverse_bwd``, rounded to
    ``dtype``."""
    return _inverse_kernels_fwd(a, dtype, interpret)[0]


def _inverse_kernels_fwd(a, dtype, interpret):
    inv = checkpoint_name(_inverse_call(a, dtype, interpret), INVERSE_NAME)
    return inv, inv


def _inverse_kernels_bwd(dtype, interpret, inv, d_inv):
    return (_inverse_bwd_call(inv, d_inv, interpret),)


inverse_kernels.defvjp(_inverse_kernels_fwd, _inverse_kernels_bwd)


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


def gated_delta_kernels(q, k, v, g, beta, chunk: int = CHUNK,
                        interpret: bool = False):
    """``gated_delta`` with the pass across the chunks by the kernels,
    whatever the platform (``interpret``: in Pallas' interpreter, for the
    tests); the shapes are ``supported``'s. The chunks' own operands are
    XLA products as in ``gated_delta_xla`` but for the inverse, which is
    the kernels ``bps_gdn_inverse`` / ``bps_gdn_inverse_bwd``."""
    _check(q, k, v, g, beta, chunk)
    *operands, keep = _chunk_operands(
        q, k, v, g, beta, chunk,
        functools.partial(inverse_kernels, dtype=v.dtype,
                          interpret=interpret))
    keep = jnp.broadcast_to(keep[..., None, None],
                            keep.shape + (1, v.shape[3]))
    return _across_kernels(*operands, keep, interpret).reshape(v.shape)


def gated_delta(q, k, v, g, beta, chunk: int = CHUNK):
    """The gated delta rule over ``q``, ``k`` [b, s, hk, dk] (normalised
    and scaled by the caller), ``v`` [b, s, hv, dv], ``g`` (the decay's
    log, <= 0) and ``beta`` [b, s, hv] float32, from a zero state, in
    chunks of ``chunk`` positions: ``o`` [b, s, hv, dv] in ``v``'s dtype.
    A pure function (safe under ``jax.checkpoint``). On the TPU, for the
    shapes ``supported`` takes, the pass across the chunks is the kernels
    ``bps_gdn_fwd`` / ``bps_gdn_bwd``; elsewhere (the CPU, odd shapes, a
    chunk shorter than a lane tile) a ``lax.scan``."""
    kernels = (jax.default_backend() == "tpu"
               and supported(q.shape, v.shape, chunk))
    note_choice("gdn_scan", "kernels" if kernels else "xla",
                (tuple(q.shape), tuple(v.shape), chunk),
                "a lax.scan across the chunks: the kernels need heads and "
                f"chunks of whole lane tiles ({LANES})")
    if kernels:
        return gated_delta_kernels(q, k, v, g, beta, chunk)
    with jax.named_scope("bps_gdn_xla"):
        return gated_delta_xla(q, k, v, g, beta, chunk)

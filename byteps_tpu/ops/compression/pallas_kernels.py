"""Pallas TPU kernels for the compression hot ops.

The onebit pack/unpack is the per-step bandwidth hot path of compressed
push_pull (every gradient byte flows through it twice). The jnp fallback
lowers to a dozen XLA ops with intermediate materialization; these
kernels do the whole bit-twiddle in one VMEM pass on the VPU.

Layout: a flat buffer of n floats is viewed as ``[n/32, 32]`` — 32
consecutive elements per row, one packed uint32 word per row, MSB-first
within the row (payload-identical to the jnp path in onebit.py, which
follows the reference's packing, reference: impl/onebit.cc:34-67).

On non-TPU backends the same kernels run under Pallas interpret mode, so
tests validate the exact kernel logic on the CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 32          # bits per packed word
_BLOCK_ROWS = 512  # words per kernel instance (512×32 f32 = 64 KiB VMEM)


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


@functools.cache
def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _pack_kernel(x_ref, out_ref):
    # int32 throughout: Mosaic has no unsigned reductions, and since the
    # bits are disjoint, two's-complement addition is still a bitwise OR
    x = x_ref[:]                                        # [B, 32] f32
    neg = (x < 0).astype(jnp.int32)
    shifts = (PACK - 1) - jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    out_ref[:] = jnp.sum(neg << shifts, axis=1, keepdims=True)


def _unpack_kernel(p_ref, out_ref):
    w = p_ref[:]                                        # [B, 1] int32
    shifts = (PACK - 1) - jax.lax.broadcasted_iota(
        jnp.int32, (w.shape[0], PACK), 1)
    # arithmetic >> then &1 extracts the bit regardless of the sign bit
    bits = (w >> shifts) & jnp.int32(1)
    # bit 1 → negative (reference: sign = 1 - ((x & 1) << 1))
    out_ref[:] = 1.0 - 2.0 * bits.astype(jnp.float32)


def onebit_pack(x: jnp.ndarray, chunks: int) -> jnp.ndarray:
    """Sign-pack a flat float buffer into ``chunks`` uint32 words.

    ``x`` is zero-padded internally (sign bit of +0.0 is 0, matching the
    reference's padded tail).

    Layout note: the 32-wide minor dim uses a quarter of the 128-lane
    vreg; a [rows, 128]→4-words layout would fill it but needs cross-lane
    regrouping Mosaic lowers poorly. As-is the compiled kernel measures
    ~8× the fused-XLA path on a v5e chip — bandwidth-bound, not
    lane-bound.
    """
    rows = _cdiv(chunks, _BLOCK_ROWS) * _BLOCK_ROWS
    xp = jnp.pad(x.astype(jnp.float32), (0, rows * PACK - x.shape[0]))
    words = pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, PACK), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(xp.reshape(rows, PACK))
    return jax.lax.bitcast_convert_type(words.reshape(-1)[:chunks],
                                        jnp.uint32)


def onebit_unpack(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """Expand packed sign words to ±1.0 floats of length ``n`` (unscaled)."""
    chunks = packed.shape[0]
    rows = _cdiv(chunks, _BLOCK_ROWS) * _BLOCK_ROWS
    wi = jax.lax.bitcast_convert_type(packed, jnp.int32)
    wp = jnp.pad(wi, (0, rows - chunks)).reshape(rows, 1)
    signs = pl.pallas_call(
        _unpack_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, PACK), jnp.float32),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, PACK), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(wp)
    return signs.reshape(-1)[:n]


# ----------------------------------------------------- int8 quantization
#
# The fused compression plane's int8 hot path (byteps_tpu/compress):
# symmetric max-abs linear quantization with ONE fp32 scale per bucket,
# round-half-even — byte-identical to the host codec
# (compress.wire.encode CODEC_INT8), so a device-side quantize can feed
# the same wire format the numpy pack workers produce. Lanes are the
# full 128-wide vreg (unlike the onebit kernels' 32-wide packing
# geometry); the int8 output tile minimum is (32, 128), so the block
# row count stays a multiple of 32.

_LANES = 128
_Q_ROWS = 256      # 256×128 f32 in + int8 out ≈ 160 KiB VMEM per step


def _int8_q_kernel(x_ref, scale_ref, out_ref):
    # DIVIDE, exactly like the host codec's rint(x / scale): a
    # reciprocal-multiply is ~1 ulp off and flips round-half-even ties
    # on ~4e-7 of elements — enough to break byte-identity with the
    # wire codec on large buckets. scale <= 0 is substituted with 1.0
    # host-side (matching wire.encode's zero-amax rule).
    q = jnp.clip(jnp.round(x_ref[:] / scale_ref[0]), -127.0, 127.0)
    out_ref[:] = q.astype(jnp.int8)


def _int8_dq_kernel(q_ref, scale_ref, out_ref):
    out_ref[:] = q_ref[:].astype(jnp.float32) * scale_ref[0]


def _q_grid(n: int):
    rows = _cdiv(_cdiv(n, _LANES), _Q_ROWS) * _Q_ROWS
    return rows, rows // _Q_ROWS


def int8_quantize(x: jnp.ndarray, scale) -> jnp.ndarray:
    """Quantize a flat float buffer to int8 at ``scale`` (fp32 scalar;
    elements map to ``clip(round(x/scale), -127, 127)``). Zero-padded
    internally; the padding quantizes to 0 and is sliced off."""
    n = x.shape[0]
    rows, grid = _q_grid(n)
    xp = jnp.pad(x.astype(jnp.float32), (0, rows * _LANES - n))
    scale = jnp.asarray(scale, jnp.float32).reshape(1)
    # the host codec never divides by a non-positive scale (wire.encode
    # substitutes 1.0 for a zero amax) — mirror that rule here so the
    # kernel stays byte-identical AND total on degenerate inputs
    scale = jnp.where(scale > 0, scale, 1.0)
    q = pl.pallas_call(
        _int8_q_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int8),
        grid=(grid,),
        in_specs=[pl.BlockSpec((_Q_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((_Q_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(xp.reshape(rows, _LANES), scale)
    return q.reshape(-1)[:n]


# ------------------------------------------------- fp8 stochastic round
#
# Kernel twin of ops/compression/fp8sr.py (the fused plane's fp8 rungs):
# deterministic counter-based stochastic rounding to the fp8 byte
# encoding, run ENTIRELY as uint32 bit-math — no float8 cast, so the
# kernel works on backends whose Mosaic has no fp8 type support and is
# byte-identical to the numpy reference by construction (same mixer,
# same integer adds, same truncation). The per-element noise counter is
# the element's flat index, so the payload is a pure function of
# (x, scale, seed) on every backend.

def _fp8_sr_kernel(x_ref, scale_ref, seed_ref, out_ref, *, kind: int,
                   block: int):
    from . import fp8sr
    mx, _, base, emin, e_sub, qbits = fp8sr.fmt_params(kind)
    u32 = jnp.uint32
    y = x_ref[:] / scale_ref[0]
    y = jnp.clip(y, -mx, mx)
    bits = jax.lax.bitcast_convert_type(y, jnp.uint32)
    sign = bits >> u32(31)
    mag = bits & u32(0x7FFFFFFF)
    e = (mag >> u32(23)).astype(jnp.int32)
    # flat element index = this block's offset + local (row, lane)
    off = (pl.program_id(0) * block * _LANES).astype(jnp.int32)
    local = (jax.lax.broadcasted_iota(jnp.int32, y.shape, 0) * _LANES
             + jax.lax.broadcasted_iota(jnp.int32, y.shape, 1))
    idx = (off + local).astype(jnp.uint32)
    h = (idx * u32(0x9E3779B9)) ^ seed_ref[0]
    h = h ^ (h >> u32(16))
    h = h * u32(0x85EBCA6B)
    h = h ^ (h >> u32(13))
    h = h * u32(0xC2B2AE35)
    h = h ^ (h >> u32(16))
    d = jnp.clip(jnp.int32(emin + base) - e, base, 23).astype(jnp.uint32)
    mask = (u32(1) << d) - u32(1)
    mag_grid = (mag + (h & mask)) & ~mask
    tiny = e < jnp.int32(e_sub)
    # < 2**24, so the int32 hop is exact; Mosaic has no uint32 -> f32 cast
    u24 = (h >> u32(8)).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(2.0 ** -24)
    t = jnp.abs(y) * jnp.float32(2.0 ** (127 - e_sub))
    mag_tiny = jnp.where(u24 < t, u32(qbits), u32(0))
    mag2 = jnp.where(tiny, mag_tiny, mag_grid)
    mag2 = jnp.where(mag == u32(0), u32(0), mag2)
    e2 = (mag2 >> u32(23)).astype(jnp.int32)
    f2 = mag2 & u32(0x7FFFFF)
    norm = (((e2 - jnp.int32(emin - 1)).astype(jnp.uint32)
             << u32(23 - base)) | (f2 >> u32(base)))
    sub_shift = jnp.clip(jnp.int32(emin + base) - e2, 0, 31) \
        .astype(jnp.uint32)
    sub = ((u32(1) << u32(23)) | f2) >> sub_shift
    out = jnp.where(e2 >= jnp.int32(emin), norm, sub)
    out = jnp.where(mag2 == u32(0), u32(0), out)
    out_ref[:] = ((sign << u32(7)) | out).astype(jnp.uint8)


def fp8_sr_quantize(x: jnp.ndarray, scale, seed, kind: int) -> jnp.ndarray:
    """Stochastically round a flat float buffer to fp8 byte encodings
    (uint8) at ``scale`` — byte-identical to
    ``fp8sr.sr_quantize_bits`` for the same (x, scale, seed). ``kind``
    is ``fp8sr.E4M3`` / ``fp8sr.E5M2``; zero-padding quantizes to 0 and
    is sliced off (the padded tail's noise never aliases real elements:
    the counter is the flat index)."""
    import functools as _ft
    n = x.shape[0]
    rows, grid = _q_grid(n)
    xp = jnp.pad(x.astype(jnp.float32), (0, rows * _LANES - n))
    scale = jnp.asarray(scale, jnp.float32).reshape(1)
    # zero-amax rule shared with the host codec (fp8sr divides too)
    scale = jnp.where(scale > 0, scale, 1.0)
    seed = jnp.asarray(seed, jnp.uint32).reshape(1)
    q = pl.pallas_call(
        _ft.partial(_fp8_sr_kernel, kind=kind, block=_Q_ROWS),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.uint8),
        grid=(grid,),
        in_specs=[pl.BlockSpec((_Q_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((_Q_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(xp.reshape(rows, _LANES), scale, seed)
    return q.reshape(-1)[:n]


def int8_dequantize(q: jnp.ndarray, scale, n: int = None) -> jnp.ndarray:
    """Expand int8 values back to fp32 (``q * scale``)."""
    m = q.shape[0]
    n = m if n is None else n
    rows, grid = _q_grid(m)
    qp = jnp.pad(q.astype(jnp.int8), (0, rows * _LANES - m))
    scale = jnp.asarray(scale, jnp.float32).reshape(1)
    out = pl.pallas_call(
        _int8_dq_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        grid=(grid,),
        in_specs=[pl.BlockSpec((_Q_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((_Q_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(qp.reshape(rows, _LANES), scale)
    return out.reshape(-1)[:n]

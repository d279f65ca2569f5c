"""Device-side PS_COMPRESS: encode the bucket BEFORE the D2H copy.

The host codec path (PR 7) compresses on the pack worker — after every
leaf already crossed PCIe dense, so only the WIRE shrank. This module
moves the whole encode onto the accelerator as one jitted pipeline per
bucket recipe:

    gather segments (device) -> fold EF residual (device) -> amax /
    scale -> Pallas quantize kernel -> D2H of the ENCODED bytes only

so the D2H copy, the host pack, and the wire shrink together (~4x for
int8/fp8). EF residuals become DEVICE-resident: the new residual is
computed on device (``x - dequant(q)``) and never crosses PCIe; the
plane's commit-on-pull protocol handles it unchanged (the pending slot
just holds a ``jax.Array``).

Byte-identity contract: the payload produced here is BYTE-IDENTICAL to
``wire.encode`` on the same dense input — same pure-f32 ``amax/denom``
scale rule (``wire.amax_scale``), the PR-7-proven int8 kernel, and the
fp8 kernel whose uint32 SR math is shared with the numpy reference.
``_probe()`` verifies this end to end on an adversarial vector at
startup; any mismatch (or a backend whose Mosaic rejects the kernels)
is an error that names the codec — ``BPS_COMPRESS_DEVICE=0`` is how to
ask for the host codec.

``BPS_COMPRESS_DEVICE``: ``auto`` (default — on when the default JAX
backend is an accelerator), ``1`` (force, e.g. CPU tests via Pallas
interpret mode), ``0`` (off).
"""

from __future__ import annotations

import functools
import os
import struct
import threading
from typing import List, Tuple

import numpy as np

from ..common.logging import get_logger
from . import wire

#: codecs the device pipeline can produce (topk's argsort has no
#: kernel; fp16 buckets gain nothing from a kernel — the cast IS the
#: D2H narrowing and jnp does it fine, but the astype path below
#: handles it anyway for uniform d2h accounting)
DEVICE_CODECS = (wire.CODEC_INT8, wire.CODEC_FP8_E4M3,
                 wire.CODEC_FP8_E5M2)

_log = get_logger()
_probe_lock = threading.Lock()
_probed = False


def _fp8_decode_device(q, kind):
    """fp8 byte encodings -> f32 on device, as pure uint32 math (no
    fp8 dtype needed — portable to Mosaics without float8 support);
    value-identical to ``fp8sr.decode_bits``."""
    import jax
    import jax.numpy as jnp

    from ..ops.compression import fp8sr
    _, mant, base, emin, e_sub, _ = fp8sr.fmt_params(kind)
    b = q.astype(jnp.uint32)
    sign = b >> jnp.uint32(7)
    mag8 = b & jnp.uint32(0x7F)
    e8 = mag8 >> jnp.uint32(mant)
    f8 = mag8 & jnp.uint32((1 << mant) - 1)
    norm_bits = (((e8 + jnp.uint32(emin - 1)) << jnp.uint32(23))
                 | (f8 << jnp.uint32(base)))
    norm = jax.lax.bitcast_convert_type(norm_bits, jnp.float32)
    sub = f8.astype(jnp.float32) * jnp.float32(2.0 ** (e_sub - 127))
    val = jnp.where(e8 > 0, norm, sub)
    return jnp.where(sign > 0, -val, val)


@functools.lru_cache(maxsize=256)
def _gather_amax(spec: Tuple[Tuple[int, int], ...], ef: bool):
    """Jitted stage 1 per (bucket segment recipe, EF): gather the
    bucket's flat f32 view on device, fold the residual, reduce amax.
    ``x`` stays device-resident for stage 2."""
    import jax
    import jax.numpy as jnp

    def fn(residual, *leaves):
        xs = [jnp.ravel(l)[off:off + ln].astype(jnp.float32)
              for l, (off, ln) in zip(leaves, spec)]
        x = xs[0] if len(xs) == 1 else jnp.concatenate(xs)
        if ef:
            x = x + residual
        return x, jnp.max(jnp.abs(x))

    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _quantize(level: int, ef: bool):
    """Jitted stage 2 per (codec, EF): quantize at the HOST-computed
    scale (see ``wire.scale_from_amax`` — dividing on device is ~1 ulp
    off numpy and would break payload byte-identity), and compute the
    new device residual."""
    import jax
    import jax.numpy as jnp

    from ..ops.compression import fp8sr
    from ..ops.compression.pallas_kernels import (fp8_sr_quantize,
                                                 int8_quantize)
    kind = None if level == wire.CODEC_INT8 else (
        fp8sr.E4M3 if level == wire.CODEC_FP8_E4M3 else fp8sr.E5M2)

    def fn(x, scale, seed):
        if level == wire.CODEC_INT8:
            q = int8_quantize(x, scale)
            deq = q.astype(jnp.float32) * scale
        else:
            q = fp8_sr_quantize(x, scale, seed, kind)
            deq = _fp8_decode_device(q, kind) * scale
        new_r = (x - deq) if ef else None
        return q, new_r

    return jax.jit(fn)


def encode_bucket(parts: List[tuple], size: int, level: int, seed: int,
                  residual, ef: bool, div: int = wire.TOPK_DIV) -> tuple:
    """Encode one bucket on device. ``parts`` =
    ``[(device leaf, leaf_offset, length), ...]`` in bucket-segment
    order covering exactly ``size`` f32 elements. Returns
    ``(payload bytes, new device residual or None, d2h_bytes)``.

    Two jitted stages with a 4-byte amax sync between them: the sync is
    what lets the scale take the host division every other encode site
    uses (byte-identity), and it serializes nothing the pack worker
    wasn't already going to wait for — the payload D2H follows
    immediately."""
    import jax.numpy as jnp
    if level not in DEVICE_CODECS:
        raise ValueError(f"codec {wire.codec_name(level)} has no device "
                         f"encode")
    spec = tuple((int(off), int(ln)) for _, off, ln in parts)
    leaves = tuple(l for l, _, _ in parts)
    r = residual
    if ef and r is None:
        r = jnp.zeros(size, jnp.float32)
    x, amax = _gather_amax(spec, bool(ef))(r, *leaves)
    if level == wire.CODEC_INT8:
        denom = 127.0
    else:
        from ..ops.compression import fp8sr
        denom = fp8sr.fmt_max(fp8sr.E4M3 if level == wire.CODEC_FP8_E4M3
                              else fp8sr.E5M2)
    scale = wire.scale_from_amax(np.asarray(amax), denom)   # 4B sync
    q, new_r = _quantize(int(level), bool(ef))(
        x, jnp.float32(scale), jnp.uint32(seed & 0xFFFFFFFF))
    q_np = np.asarray(q)                      # the ONLY bulk D2H copy
    hdr = wire._HDR.pack(wire.MAGIC, wire.VERSION, level,
                         b"float32".ljust(8, b"\0"), size)
    body = (q_np.view(np.int8) if level == wire.CODEC_INT8
            else q_np.view(np.uint8)).tobytes()
    payload = hdr + struct.pack("<f", scale) + body
    return payload, new_r, len(body) + 4


def _probe() -> None:
    """Bitwise probe: device payloads must equal the host codec's on an
    adversarial vector (ties, zeros, binade edges, denormal-range
    values). A kernel the backend refuses, or a byte mismatch, raises
    with the codec's name."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0xB5C1)
    x = np.concatenate([
        rng.randn(3800).astype(np.float32),
        rng.randn(120).astype(np.float32) * 1e-4,
        rng.randn(120).astype(np.float32) * 1e3,
        np.array([0.0, -0.0, 0.5, -0.5, 1.0, 2.0 ** -10, -2.0 ** -10,
                  3.5, -3.5] * 6 + [1e-30, -1e-30], np.float32)])
    xd = jnp.asarray(x)
    n = x.size
    for cid in DEVICE_CODECS:
        what = (f"BPS_COMPRESS_DEVICE: device {wire.codec_name(cid)} "
                f"encode on the {jax.default_backend()} backend")
        host = wire.encode(cid, x, seed=1234)
        try:
            dev, _, _ = encode_bucket([(xd, 0, n)], n, cid, 1234,
                                      None, False)
        except Exception as e:
            raise RuntimeError(
                f"{what} failed ({type(e).__name__}: {e}); "
                f"BPS_COMPRESS_DEVICE=0 asks for the host codec") from e
        if dev != host:
            raise RuntimeError(
                f"{what} diverges from the host codec; "
                f"BPS_COMPRESS_DEVICE=0 asks for the host codec")


def device_encode_enabled() -> bool:
    """Resolve BPS_COMPRESS_DEVICE (probe verdict cached per process;
    ``reset_probe`` for tests). ``auto`` keeps CPU rigs on the host
    codec — interpret-mode kernels are correct but not a speed-up.
    Anywhere else the probe must pass: a failure raises, it does not
    fall back to the host codec."""
    global _probed
    v = (os.environ.get("BPS_COMPRESS_DEVICE", "auto") or "auto") \
        .strip().lower()
    if v in ("0", "off", "false", "none"):
        return False
    if v == "auto":
        import jax
        if jax.default_backend() == "cpu":
            return False
    with _probe_lock:
        if not _probed:
            _probe()
            _probed = True
    return True


def reset_probe() -> None:
    """Forget the cached probe verdict (tests flip envs/backends)."""
    global _probed
    with _probe_lock:
        _probed = False
    _gather_amax.cache_clear()
    _quantize.cache_clear()

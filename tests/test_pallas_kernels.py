"""Pallas compression kernels vs the pure-jnp reference path.

On the CPU test mesh the kernels run under Pallas interpret mode, so the
exact kernel logic (layout, shifts, padding) is what's being validated."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.compression.onebit import OnebitCompressor
from byteps_tpu.ops.compression.pallas_kernels import (onebit_pack,
                                                       onebit_unpack)


@pytest.mark.parametrize("n", [32, 1000, 4096, 16384 + 7])
def test_pack_matches_jnp_payload(n):
    rng = np.random.RandomState(n)
    x = rng.randn(n).astype(np.float32)
    jnp_c = OnebitCompressor(n, backend="jnp", use_scale=True)
    pal_c = OnebitCompressor(n, backend="pallas", use_scale=True)
    pj, _ = jnp_c.compress(jnp.asarray(x), ())
    pp, _ = pal_c.compress(jnp.asarray(x), ())
    np.testing.assert_array_equal(np.asarray(pj["packed"]),
                                  np.asarray(pp["packed"]))
    np.testing.assert_allclose(float(pj["scale"]), float(pp["scale"]))


@pytest.mark.parametrize("n", [32, 1000, 4096])
def test_roundtrip_cross_backend(n):
    """pallas-compressed payloads decompress identically via either path."""
    rng = np.random.RandomState(n + 1)
    x = rng.randn(n).astype(np.float32)
    jnp_c = OnebitCompressor(n, backend="jnp", use_scale=True)
    pal_c = OnebitCompressor(n, backend="pallas", use_scale=True)
    payload, _ = pal_c.compress(jnp.asarray(x), ())
    got = np.asarray(pal_c.decompress(payload))
    want = np.asarray(jnp_c.decompress(payload))
    np.testing.assert_allclose(got, want)
    # signs preserved exactly where x != 0
    np.testing.assert_array_equal(np.sign(got), np.sign(x))


def test_pack_unpack_primitives_jit():
    n = 2048
    x = jnp.asarray(np.random.RandomState(0).randn(n).astype(np.float32))

    @jax.jit
    def roundtrip(x):
        words = onebit_pack(x, n // 32)
        return onebit_unpack(words, n)

    signs = np.asarray(roundtrip(x))
    np.testing.assert_array_equal(signs, np.where(np.asarray(x) < 0, -1.0, 1.0))


# ------------------------------------------------ int8 quantize pair
#
# The fused compression plane's int8 hot path (byteps_tpu/compress):
# the Pallas kernel pair must match the host codec's math exactly
# (same scale convention, round-half-even), so device-quantized bytes
# are interchangeable with pack-worker-quantized ones on the wire.

from byteps_tpu.ops.compression.pallas_kernels import (int8_dequantize,
                                                       int8_quantize)


@pytest.mark.parametrize("n", [128, 1000, 4096, 32768 + 13])
def test_int8_quantize_matches_host_codec(n):
    from byteps_tpu.compress import wire as cwire
    rng = np.random.RandomState(n)
    x = rng.randn(n).astype(np.float32)
    payload = cwire.encode(cwire.CODEC_INT8, x)
    import struct
    body = payload[cwire._HDR.size:]
    (scale,) = struct.unpack("<f", body[:4])
    q_host = np.frombuffer(body[4:], np.int8)
    q_dev = np.asarray(int8_quantize(jnp.asarray(x), scale))
    np.testing.assert_array_equal(q_dev, q_host)


@pytest.mark.parametrize("n", [128, 1000, 4096])
def test_int8_roundtrip_and_bounds(n):
    rng = np.random.RandomState(n + 1)
    x = rng.randn(n).astype(np.float32) * 3.0
    scale = np.float32(np.abs(x).max() / 127.0)
    q = np.asarray(int8_quantize(jnp.asarray(x), scale))
    assert q.min() >= -127 and q.max() <= 127
    out = np.asarray(int8_dequantize(jnp.asarray(q), scale, n))
    # reconstruction error bounded by half a quantization step
    assert float(np.abs(out - x).max()) <= 0.5 * float(scale) + 1e-6


def test_int8_quantize_pair_jit():
    n = 5000
    x = jnp.asarray(np.random.RandomState(3).randn(n).astype(np.float32))
    scale = jnp.float32(0.02)

    @jax.jit
    def roundtrip(x):
        return int8_dequantize(int8_quantize(x, scale), scale, n)

    out = np.asarray(roundtrip(x))
    want = np.clip(np.rint(np.asarray(x) / 0.02), -127, 127) * 0.02
    np.testing.assert_allclose(out, want.astype(np.float32), rtol=1e-6)


def test_int8_zero_scale_quantizes_to_zero():
    """amax == 0 (all-zero bucket): inv-scale 0 → all-zero q, no NaNs."""
    q = np.asarray(int8_quantize(jnp.zeros(256, jnp.float32), 0.0))
    assert not q.any()


# ------------------------------------------------ fp8 stochastic round
#
# The fp8 rungs (compress.wire fp8_e4m3/fp8_e5m2): the Pallas kernel
# and the numpy reference share the SAME uint32 SR bit-math (counter-
# based murmur3 noise, per-binade discard, integer fp8 packing), so
# device-quantized bytes must be IDENTICAL to host-quantized ones —
# the contract that lets the device encode feed the same wire format.

from byteps_tpu.ops.compression import fp8sr
from byteps_tpu.ops.compression.pallas_kernels import fp8_sr_quantize


def _adversarial(n, seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(n).astype(np.float32)
    x[::7] *= 1e-4          # deep-subnormal range under the scale
    x[::11] *= 1e4          # near-max range
    x[::13] = 0.0           # exact zeros
    x[1::97] = -0.0         # negative zeros
    return x


@pytest.mark.parametrize("kind", [fp8sr.E4M3, fp8sr.E5M2])
@pytest.mark.parametrize("n", [128, 1000, 32768 + 13])
def test_fp8_sr_kernel_matches_host_bits(kind, n):
    x = _adversarial(n, n + kind)
    scale = np.float32(np.float32(np.max(np.abs(x)))
                       / np.float32(fp8sr.fmt_max(kind)))
    host = fp8sr.sr_quantize_bits(x, scale, kind, seed=777)
    dev = np.asarray(fp8_sr_quantize(jnp.asarray(x), scale, 777, kind))
    np.testing.assert_array_equal(host, dev.view(np.uint8))


@pytest.mark.parametrize("kind", [fp8sr.E4M3, fp8sr.E5M2])
def test_fp8_sr_kernel_seed_and_padding(kind):
    """Different seeds give different bytes; the padded tail never
    aliases real elements (the noise counter is the flat index)."""
    x = _adversarial(4096, 40 + kind)
    scale = np.float32(0.01)
    a = np.asarray(fp8_sr_quantize(jnp.asarray(x), scale, 1, kind))
    b = np.asarray(fp8_sr_quantize(jnp.asarray(x), scale, 2, kind))
    assert not np.array_equal(a, b)
    # a longer buffer's prefix quantizes identically (same indices)
    x2 = np.concatenate([x, _adversarial(1000, 41 + kind)])
    c = np.asarray(fp8_sr_quantize(jnp.asarray(x2), scale, 1, kind))
    np.testing.assert_array_equal(a, c[:4096])


@pytest.mark.slow
@pytest.mark.parametrize("kind", [fp8sr.E4M3, fp8sr.E5M2])
def test_fp8_sr_kernel_adversarial_sweep_2p6m(kind):
    """The PR-7 2.6M-element adversarial harness applied to the fp8
    pair: zero byte mismatches between the kernel and the host
    reference at production bucket scale."""
    x = _adversarial(2_600_000, 99 + kind)
    scale = np.float32(np.float32(np.max(np.abs(x)))
                       / np.float32(fp8sr.fmt_max(kind)))
    host = fp8sr.sr_quantize_bits(x, scale, kind, seed=31337)
    dev = np.asarray(fp8_sr_quantize(jnp.asarray(x), scale, 31337,
                                     kind)).view(np.uint8)
    assert (host != dev).sum() == 0


def test_device_encode_bucket_matches_wire_payloads():
    """compress.device.encode_bucket: the whole device pipeline
    (gather -> amax -> host-division scale -> kernel -> payload
    assembly) is byte-identical to wire.encode for every device codec,
    including a multi-leaf segment gather."""
    from byteps_tpu.compress import device as cdev
    from byteps_tpu.compress import wire as cwire
    a = jnp.asarray(np.random.RandomState(50).randn(64, 50)
                    .astype(np.float32))
    b = jnp.asarray(np.random.RandomState(51).randn(1500)
                    .astype(np.float32))
    parts = [(a, 100, 2000), (b, 0, 1000)]
    packed = np.concatenate([np.asarray(a).reshape(-1)[100:2100],
                             np.asarray(b)[:1000]])
    for cid in cdev.DEVICE_CODECS:
        payload, _, d2h = cdev.encode_bucket(parts, 3000, cid, 55,
                                             None, False)
        assert payload == cwire.encode(cid, packed, seed=55)
        assert d2h == 3000 + 4      # 1B/elem + the scale scalar


def test_device_encode_probe_failure_is_an_error(monkeypatch):
    """No hidden fallback: a kernel the backend refuses, or a diverging
    payload (both simulated), raises with the codec's name; the host
    codec is used only when BPS_COMPRESS_DEVICE=0 asks for it."""
    from byteps_tpu.compress import device as cdev
    cdev.reset_probe()
    cdev._probe()                       # this backend is bit-clean
    monkeypatch.setenv("BPS_COMPRESS_DEVICE", "1")

    def refuse(*a, **k):
        raise NotImplementedError("Unsupported cast")
    monkeypatch.setattr(cdev, "encode_bucket", refuse)
    with pytest.raises(RuntimeError, match="int8.*Unsupported cast"):
        cdev.device_encode_enabled()
    monkeypatch.setattr(cdev, "encode_bucket",
                        lambda *a, **k: (b"wrong", None, 0))
    with pytest.raises(RuntimeError, match="int8.*diverges"):
        cdev.device_encode_enabled()
    monkeypatch.setenv("BPS_COMPRESS_DEVICE", "0")
    assert cdev.device_encode_enabled() is False
    cdev.reset_probe()      # drop the poisoned verdict for later tests

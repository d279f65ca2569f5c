"""The flash kernels under a causal band (``window``) and grouped kv heads,
in Pallas' interpret mode against the naive product: each of the four
kernels (the online and the one-block forward, the fused backward, the
split dq / dkv pair) for window x grouping x causal, the XLA fall-backs,
and what a call must not combine."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.flash_attention import (attention, flash_attention,
                                            local_attention)

# (seq, block_q, block_k): which kernels a call's forward and backward are
GEOMETRY = {
    "one_block_fused": (256, None, None),      # _fwd_single + bwd_fused
    "online_split": (512, 128, 128),           # _fwd_kernel + dq + dkv
    "wide_q_blocks": (512, 256, 128),
    "wide_k_blocks": (512, 128, 256),
}
# None: all keys; 0: the causal triangle; else a band of that many keys
MASKS = [None, 0, 100, 128, 300]


def _qkv(seed, s, heads, kv_heads, d=64, dtype=np.float32):
    rng = np.random.RandomState(seed)
    make = lambda h: jnp.asarray(rng.randn(2, s, h, d).astype(dtype))  # noqa: E731
    return make(heads), make(kv_heads), make(kv_heads)


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (4, 1)])
@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_kernels_match_the_naive_product(geometry, heads, kv_heads, mask):
    s, bq, bk = GEOMETRY[geometry]
    causal, window = mask is not None, mask or None
    q, k, v = _qkv(0, s, heads, kv_heads)

    def flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal, None, bq, bk, True, window=window)))

    def naive(q, k, v):
        return jnp.sum(jnp.sin(local_attention(q, k, v, causal=causal,
                                               window=window)))

    (lf, gf), (ln, gn) = (jax.value_and_grad(f, (0, 1, 2))(q, k, v)
                          for f in (flash, naive))
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-5)
    assert gf[1].shape == k.shape and gf[2].shape == v.shape
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                                   atol=3e-4, err_msg=name)


@pytest.mark.parametrize("seq,blocks,kernels", [
    (256, None, ("bps_flash_fwd", "bps_flash_bwd_fused")),
    (512, 128, ("bps_flash_fwd", "bps_flash_bwd_dq", "bps_flash_bwd_dkv")),
], ids=["one_block_fused", "online_split"])
def test_a_band_over_grouped_heads_runs_the_kernels_by_their_names(
        seq, blocks, kernels):
    q, k, v = _qkv(1, seq, 8, 1)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, blocks, blocks, True,
                               window=100).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert set(re.findall(r"name=(bps_flash_\w+)", jaxpr)) == set(kernels)
    # k and v reach the kernels as they are: no copy a query head
    assert "repeat" not in jaxpr and "broadcast_in_dim[shape=(2, 8," not in jaxpr


def test_a_band_visits_only_the_blocks_it_touches():
    """The kv dimension of the grid is as long as the band, not as the
    sequence: 1024 keys in blocks of 128 under a window of 200 is 3 kv
    steps a q block (the diagonal block and two before), not 8."""
    q, k, v = _qkv(2, 1024, 2, 1)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, True,
                               window=200).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    grids = re.findall(r"grid=\((\d+), (\d+), (\d+), (\d+)\)", jaxpr)
    # forward and dq: (b, kv heads, group x q blocks, band steps); dkv:
    # (b, kv heads, kv blocks, group x band steps)
    assert ("2", "1", "16", "3") in grids and ("2", "1", "8", "6") in grids
    assert not any(g[2:] == ("16", "8") for g in grids)


def test_the_xla_fall_back_takes_the_same_arguments():
    q, k, v = _qkv(3, 256, 4, 2)
    want = local_attention(q, k, v, causal=True, window=64)
    got = attention(q, k, v, causal=True, impl="naive", window=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    # and the band is not the triangle
    assert float(jnp.abs(want - local_attention(q, k, v, causal=True)).max()
                 ) > 1e-3


def test_bf16_band_over_grouped_heads_is_close():
    q, k, v = _qkv(4, 512, 4, 1)
    want = local_attention(q, k, v, causal=True, window=200)
    got = flash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)), True,
                          None, 128, 128, True, window=200)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=3e-2, atol=3e-2)


def test_what_a_call_must_not_combine():
    q, k, v = _qkv(5, 128, 4, 2)
    with pytest.raises(ValueError, match="causal band"):
        flash_attention(q, k, v, False, None, None, None, True, window=16)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, k[:, :, :1].repeat(3, 2), v, True, None, None,
                        None, True)
    table = jnp.zeros((4, 32))
    with pytest.raises(ValueError, match="neither a window nor"):
        flash_attention(q, k, v, False, None, None, None, True,
                        rel_table=table)
    with pytest.raises(ValueError, match="causal band"):
        local_attention(q, k, v, window=16)

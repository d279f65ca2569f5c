"""Ring attention vs single-device attention equivalence on the fake mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from byteps_tpu.ops.flash_attention import local_attention
from byteps_tpu.parallel.mesh import make_mesh
from byteps_tpu.parallel.ring import ring_attention


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_local(causal):
    mesh = make_mesh({"seq": 8})
    b, s, h, d = 2, 64, 4, 16
    rng = np.random.RandomState(0)
    q = rng.randn(b, s, h, d).astype(np.float32) * 0.5
    k = rng.randn(b, s, h, d).astype(np.float32) * 0.5
    v = rng.randn(b, s, h, d).astype(np.float32)

    want = np.asarray(local_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), causal=causal))

    def f(q, k, v):
        return ring_attention(q, k, v, "seq", causal=causal)

    spec = P(None, "seq")
    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec,
                               check_vma=False))
    sharding = NamedSharding(mesh, spec)
    got = np.asarray(fn(jax.device_put(q, sharding), jax.device_put(k, sharding),
                        jax.device_put(v, sharding)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_ring_attention_long_context_8k():
    """VERDICT r4 #8: SP correctness at a LONG length on the virtual
    mesh — 8192 tokens over 8 sequence shards (1024 local each), the
    same geometry the measured 64k-128k single-chip points use, scaled
    to what one CI core can verify against a full O(s^2) reference."""
    mesh = make_mesh({"seq": 8})
    b, s, h, d = 1, 8192, 2, 32
    rng = np.random.RandomState(3)
    q = rng.randn(b, s, h, d).astype(np.float32) * 0.3
    k = rng.randn(b, s, h, d).astype(np.float32) * 0.3
    v = rng.randn(b, s, h, d).astype(np.float32)

    want = np.asarray(local_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), causal=True))

    spec = P(None, "seq")
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=True),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
    sharding = NamedSharding(mesh, spec)
    got = np.asarray(fn(jax.device_put(q, sharding),
                        jax.device_put(k, sharding),
                        jax.device_put(v, sharding)))
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_ring_attention_bf16():
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    b, s, h, d = 1, 32, 2, 8
    rng = np.random.RandomState(1)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d), dtype=jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    want = np.asarray(local_attention(q, k, v).astype(jnp.float32))

    spec = P(None, "seq")
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq"),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
    sharding = NamedSharding(mesh, spec)
    got = np.asarray(fn(jax.device_put(q, sharding), jax.device_put(k, sharding),
                        jax.device_put(v, sharding)).astype(jnp.float32))
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_ring_matches_naive_ring(causal):
    """Pallas flash ring (interpret mode) vs pure-JAX ring: fwd + grads."""
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    b, s, h, d = 1, 512, 2, 16   # 128-token shards: flash-supported
    rng = np.random.RandomState(2)
    q = rng.randn(b, s, h, d).astype(np.float32) * 0.5
    k = rng.randn(b, s, h, d).astype(np.float32) * 0.5
    v = rng.randn(b, s, h, d).astype(np.float32)

    def run(impl):
        def f(q, k, v):
            def loss(q, k, v):
                o = ring_attention(q, k, v, "seq", causal=causal,
                                   impl=impl, interpret=True)
                return (o * (o + 1.0)).sum()
            l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            return jax.lax.psum(l, "seq"), g

        spec = P(None, "seq")
        fn = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=spec,
            out_specs=(P(), spec), check_vma=False))
        sharding = NamedSharding(mesh, spec)
        l, g = fn(*(jax.device_put(x, sharding) for x in (q, k, v)))
        return float(l), tuple(np.asarray(x) for x in g)

    l_naive, g_naive = run("naive")
    l_flash, g_flash = run("flash")
    np.testing.assert_allclose(l_flash, l_naive, rtol=1e-4)
    for gf, gn in zip(g_flash, g_naive):
        np.testing.assert_allclose(gf, gn, rtol=2e-3, atol=2e-3)


def test_flash_ring_matches_local_single_device():
    """Flash ring on a 1-shard 'ring' == plain local attention."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("seq",))
    b, s, h, d = 2, 256, 2, 16
    rng = np.random.RandomState(3)
    q = rng.randn(b, s, h, d).astype(np.float32) * 0.5
    k = rng.randn(b, s, h, d).astype(np.float32) * 0.5
    v = rng.randn(b, s, h, d).astype(np.float32)
    want = np.asarray(local_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), causal=True))
    spec = P(None, "seq")
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq", causal=True,
                                       impl="flash", interpret=True),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
    sharding = NamedSharding(mesh, spec)
    got = np.asarray(fn(*(jax.device_put(x, sharding) for x in (q, k, v))))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_ring_statistics_lane_dense():
    """The ring's flash kernels, its lse carry and its hoisted delta keep
    the row statistics [b, h, s]: no [.., s, 1] column crosses a
    pallas_call (every branch of the causal switch included) or rides a
    loop as a carry, where it would be padded 128x on the TPU."""
    from tests.test_flash_attention import (assert_statistics_lane_dense,
                                            equations)
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    b, s, h, d = 1, 2048, 2, 64          # 512-token shards: fused backward
    q = jnp.zeros((b, s, h, d), jnp.bfloat16)

    def f(q, k, v):
        def loss(q, k, v):
            o = ring_attention(q, k, v, "seq", causal=True, impl="flash")
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    spec = P(None, "seq")
    jaxpr = jax.make_jaxpr(jax.shard_map(
        f, mesh=mesh, in_specs=spec, out_specs=(P(), spec),
        check_vma=False))(q, q, q)
    # forward: diagonal + visible, in the loop and after it; as many
    # backward calls
    assert_statistics_lane_dense(jaxpr, b * h * (s // 4), 8)
    # fori_loop over a static trip count traces as a scan
    loops = equations(jaxpr, "scan") + equations(jaxpr, "while")
    assert loops
    for eqn in loops:
        for var in eqn.outvars:
            assert var.aval.shape[-1:] != (1,), var.aval.shape

"""The gated delta rule (``ops/gated_delta.py``) on the CPU at a small
size, seeded inputs: the chunked form against the one-step recurrence,
forward and every gradient, at two chunk lengths, over several chunks,
with decays near 0 and near 1, in float32 and with bfloat16 operands; the
triangular inverse and its hand-written backward against a general
inverse; the four Pallas kernels and their hand-written backward, in
the interpreter, against the XLA form and the recurrence; what a
checkpoint keeps; what the entry refuses and records."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common import setup_record
from byteps_tpu.ops import gated_delta as gd


def _inputs(seed, s, dtype=jnp.float32, bsz=2, hk=2, hv=4, dk=16, dv=8,
            rates=(-6.0, 2.0)):
    """Sizes of the seeded model's kind: l2-normalised q and k, q scaled,
    ``beta`` in (0, 1), ``g = -exp(u)`` with ``u`` uniform in ``rates``:
    from decays of 0.998 a position down to ones that forget the state in
    a single position."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (bsz, s, hk, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return ((unit(k[0]) * dk ** -0.5).astype(dtype), unit(k[1]).astype(dtype),
            jax.random.normal(k[2], (bsz, s, hv, dv)).astype(dtype),
            -jnp.exp(jax.random.uniform(k[3], (bsz, s, hv), minval=rates[0],
                                        maxval=rates[1])),
            jax.nn.sigmoid(jax.random.normal(k[4], (bsz, s, hv))))


# float32: the two forms differ by the order of their sums and by the
# inverse (a product form against forward substitution one position at a
# time). bfloat16: the chunked form rounds the operands of its products
# (k k', q k', T, beta v, the state) to 8 bits of mantissa, 2^-8 = 0.4 % an
# operand, and chains three such products a chunk, against a float32
# recurrence over the same rounded inputs
@pytest.mark.parametrize("chunk,chunks", [(16, 1), (16, 5), (32, 4), (64, 3)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
def test_the_chunked_rule_is_the_one_step_recurrence(chunk, chunks, dtype,
                                                     tol):
    args = _inputs(chunks, chunk * chunks, dtype)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def loss(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()

    def chunked(*a):
        return gd.gated_delta(*a, chunk=chunk)

    got, want = chunked(*args), gd.recurrence(*args)
    assert got.dtype == dtype and got.shape == args[2].shape
    assert want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=tol * float(jnp.abs(want).max()))
    every = tuple(range(5))             # q, k, v, g, beta
    for g, w in zip(jax.grad(loss(chunked), every)(*args),
                    jax.grad(loss(gd.recurrence), every)(*args)):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=tol * float(jnp.abs(w.astype(jnp.float32)).max()))


@pytest.mark.parametrize("rates", [(-9.0, -7.0), (2.5, 3.5)],
                         ids=["decays_near_one", "decays_near_zero"])
def test_decays_near_one_and_near_zero(rates):
    """``g`` of -1e-4 .. -1e-3 (a chunk keeps all of its state: the delta
    rule's correction is then at its largest) and of -12 .. -33 (a running
    sum of -2,000 inside a chunk: its ``exp`` is zero, no difference of
    two sums is taken above the diagonal, nothing is NaN in either
    pass)."""
    args = _inputs(4, 128, rates=rates)
    got = gd.gated_delta(*args, chunk=64)
    want = gd.recurrence(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()))
    grads = jax.grad(lambda *a: gd.gated_delta(*a, chunk=64).sum(),
                     tuple(range(5)))(*args)
    wants = jax.grad(lambda *a: gd.recurrence(*a).sum(),
                     tuple(range(5)))(*args)
    # where every decay is all but zero the gradient of ``g`` is too (1e-7
    # beside 1 for ``v``'s): held to the size of the others' rounding
    for g, w in zip(grads, wants):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5 * float(jnp.abs(w).max()) + 1e-7)


def test_keys_that_repeat_with_beta_near_one_stay_accurate():
    """Every key of a chunk the same, beta 0.999, no decay: ``I + A`` is
    all ones under the diagonal and its inverse has -1 there alone; a
    Neumann series over the whole chunk would cancel powers of the size of
    binom(63, 31), the blocks of 16 hold it to binom(15, 7)."""
    q, k, v, g, beta = _inputs(6, 128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.full_like(g, -1e-4), jnp.full_like(beta, 0.999)
    got = gd.gated_delta(q, k, v, g, beta, chunk=64)
    want = gd.recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("c", [16, 32, 64, 128])
def test_the_triangular_inverse_and_its_backward(c):
    a = jnp.tril(0.3 * jax.random.normal(jax.random.PRNGKey(c), (3, c, c)),
                 -1)
    eye = jnp.eye(c)
    got = gd.unit_lower_inverse(a)
    np.testing.assert_allclose(np.asarray(got),
                               np.linalg.inv(np.asarray(eye + a, np.float64)),
                               atol=2e-5 * float(jnp.abs(got).max()))
    weight = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    mine = jax.grad(lambda m: (gd.unit_lower_inverse(m) * weight).sum())(a)
    plain = jax.grad(lambda m: (jnp.linalg.inv(eye + jnp.tril(m, -1))
                                * weight).sum())(a)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(plain),
                               atol=1e-4 * float(jnp.abs(plain).max()))
    assert float(jnp.abs(jnp.triu(mine)).max()) == 0.0


def _repeated_keys(q, k, v, g, beta):
    """Every key of a sequence the same, beta 0.999, a decay of 0.9999: ``I
    + A`` is all ones under the diagonal."""
    return (q, jnp.broadcast_to(k[:, :1], k.shape), v,
            jnp.full_like(g, -1e-4), jnp.full_like(beta, 0.999))


def _beta_near_one(q, k, v, g, beta):
    return q, k, v, g, 1.0 - 1e-3 * beta


def _exact_dot3(x, y, contract=gd._NN):
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


# name: (key heads, value heads, chunks, the rates ``g`` is drawn from, what
# is done to the inputs, float32 tolerance against the XLA form, ``dg``'s
# where it is another). Slow decays (``g`` of -1e-4 .. -7e-3: a chunk keeps
# 0.7 of the state it was handed): the cotangent of ``exp(gamma_C)`` reaches
# ``g`` only there; under the mixed rates a chunk keeps nothing and that path
# is zero. A state that lasts sums over every chunk's terms, in another order
# a form.
# Repeated keys: ``T`` holds entries that cancel to 1 part in 6,000
# (binom(15, 7)), so the inverse kernel's three bfloat16 passes (2^-17 a
# term) show as 3-5 % of the result, and bfloat16 operands as 30 % of ``dv``
# in BOTH forms: that case holds the backward's ALGEBRA, in float32 with the
# inverse's products exact (then the result is the XLA form's bit for bit),
# and ``dg``, row sums less column sums a thousand times its own size, to 2 %
_KERNEL_CASES = {
    "mixed_decays": (1, 2, 4, (-6.0, 2.0), None, 2e-6, None),
    "slow_decays": (1, 2, 4, (-9.0, -5.0), None, 1e-5, None),
    "one_chunk": (1, 1, 1, (-6.0, 2.0), None, 2e-6, None),
    "one_chunk_slow_two_a_key_head": (1, 2, 1, (-9.0, -5.0), None, 1e-5,
                                      None),
    "a_value_head_a_key_head": (2, 2, 2, (-9.0, 2.0), None, 1e-5, None),
    "four_a_key_head": (1, 4, 2, (-9.0, -1.0), None, 1e-5, None),
    "decays_near_zero": (1, 2, 2, (2.5, 3.5), None, 2e-6, None),
    "beta_near_one": (1, 2, 2, (-9.0, 2.0), _beta_near_one, 1e-5, None),
    "repeated_keys_beta_near_one": (1, 2, 2, (-6.0, 2.0), _repeated_keys,
                                    1e-5, 2e-2),
}
_EXACT_INVERSE = ("repeated_keys_beta_near_one",)


@pytest.mark.parametrize("case,dtype", [
    (case, dtype) for case in _KERNEL_CASES
    for dtype in (jnp.float32, jnp.bfloat16)
    if not (case in _EXACT_INVERSE and dtype == jnp.bfloat16)],
    ids=lambda x: x if isinstance(x, str) else x.__name__)
def test_the_kernels_and_their_backward_in_the_interpreter(case, dtype,
                                                           monkeypatch):
    """``gated_delta_kernels`` (``bps_gdn_inverse``, ``bps_gdn_fwd`` and, in
    the hand-written backward, ``bps_gdn_bwd`` and ``bps_gdn_inverse_bwd``)
    at heads of 128 in chunks of 128: the result and all five cotangents
    (``dq``, ``dk``, ``dv``, ``dg``, ``dbeta``) against the XLA form's, which
    JAX differentiates (float32: the same products, sums in another order),
    and against the recurrence's."""
    hk, hv, chunks, rates, change, tol, dg_tol = _KERNEL_CASES[case]
    args = _inputs(2, 128 * chunks, dtype, bsz=1, hk=hk, hv=hv, dk=128,
                   dv=128, rates=rates)
    if change is not None:
        args = change(*args)
    if dtype == jnp.bfloat16:
        tol = 2e-2
    if case in _EXACT_INVERSE:      # the jitted calls are cached by shape
        monkeypatch.setattr(gd, "_dot3", _exact_dot3)
        jax.clear_caches()
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def loss(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()

    def kernels(*a):
        return gd.gated_delta_kernels(*a, 128, True)

    def scan(*a):
        return gd.gated_delta_xla(*a, 128)

    f32 = jnp.float32
    try:
        got = kernels(*args)
        grads = jax.grad(loss(kernels), tuple(range(5)))(*args)
    finally:
        if case in _EXACT_INVERSE:
            jax.clear_caches()
    want, truth = scan(*args), gd.recurrence(*args)
    far = 2e-3 if case in _EXACT_INVERSE else max(tol, 1e-5) * 2
    assert got.dtype == dtype and got.shape == args[2].shape
    np.testing.assert_allclose(np.asarray(got, f32), np.asarray(want, f32),
                               atol=tol * float(jnp.abs(truth).max()))
    np.testing.assert_allclose(np.asarray(got, f32), np.asarray(truth),
                               atol=far * float(jnp.abs(truth).max()))
    by_jax = jax.grad(loss(scan), tuple(range(5)))(*args)
    by_step = jax.grad(loss(gd.recurrence), tuple(range(5)))(
        *(a.astype(f32) for a in args))
    for name, g, w, r in zip(("dq", "dk", "dv", "dg", "dbeta"), grads,
                             by_jax, by_step):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        size = float(jnp.abs(r).max())
        near = dg_tol if name == "dg" and dg_tol else tol
        # where every decay is all but zero the gradient of ``g`` is too
        # (1e-7 beside 1 for ``v``'s): held to the others' rounding
        np.testing.assert_allclose(
            np.asarray(g, f32), np.asarray(w, f32), atol=near * size + 1e-7,
            err_msg=name)
        if not (name == "dg" and dg_tol):   # XLA's own is 12 % off there
            np.testing.assert_allclose(
                np.asarray(g, f32), np.asarray(r), err_msg=name,
                atol=far * size + 1e-7)


def test_the_inverse_kernel_on_repeated_keys_is_as_exact_as_its_passes():
    """The same keys with the inverse's products as the kernel takes them,
    three bfloat16 passes (2^-17 a term): the powers of a 16-wide block
    grow to binom(15, 7), so the result is held to 2^-17 x 6,435 = 5 % of
    its size, and is NOT where a series over the whole chunk would be
    (binom(127, 63) = 1e37)."""
    args = _repeated_keys(*_inputs(2, 256, bsz=1, hk=1, hv=2, dk=128,
                                   dv=128))
    got = gd.gated_delta_kernels(*args, 128, True)
    want = gd.recurrence(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want),
        atol=2.0 ** -17 * 6435 * float(jnp.abs(want).max()))


def test_what_the_kernels_take_and_what_a_checkpoint_keeps():
    """``supported``: heads and chunks of whole lane tiles. Under a
    checkpoint that keeps ``INVERSE_NAME`` the backward runs the forward
    kernel again (it needs the states) and NOT the inverse; without the
    name both run again."""
    args = _inputs(3, 128, bsz=1, hk=1, hv=2, dk=128, dv=128)
    assert gd.supported(args[0].shape, args[2].shape, 128)
    assert not gd.supported(args[0].shape, args[2].shape, 64)
    assert not gd.supported((1, 512, 1, 64), args[2].shape, 128)

    def calls(policy):
        fn = jax.checkpoint(
            lambda *a: gd.gated_delta_kernels(*a, 128, True).sum(),
            policy=policy)
        text = str(jax.make_jaxpr(jax.grad(fn, (0, 1, 2, 3, 4)))(*args))
        return collections.Counter(re.findall(r"name=(bps_gdn\w*)", text))

    names = jax.checkpoint_policies.save_only_these_names
    assert calls(names(gd.INVERSE_NAME)) == {
        "bps_gdn_inverse": 1, "bps_gdn_fwd": 2, "bps_gdn_bwd": 1,
        "bps_gdn_inverse_bwd": 1}
    assert calls(names("nothing"))["bps_gdn_inverse"] == 2


def test_what_the_entry_refuses_and_records():
    q, k, v, g, beta = _inputs(1, 64)
    with pytest.raises(ValueError, match="chunks of 48"):
        gd.gated_delta(q, k, v, g, beta, chunk=48)     # 3 blocks of 16
    with pytest.raises(ValueError, match="chunks of 64"):
        gd.gated_delta(q[:, :40], k[:, :40], v[:, :40], g[:, :40],
                       beta[:, :40], chunk=64)
    with pytest.raises(ValueError, match="hv a multiple of hk"):
        gd.gated_delta(q, k, v[:, :, :3], g[..., :3], beta[..., :3])
    with pytest.raises(ValueError, match=r"\[b, s, hv\]"):
        gd.gated_delta(q, k, v, g[..., :2], beta)
    # safe to rematerialise, and a choice the set-up record counts: XLA's
    # form, which off the TPU is no fall-back
    rec = setup_record.open_record()
    try:
        out = jax.checkpoint(lambda *a: gd.gated_delta(*a, chunk=32))(
            q, k, v, g, beta)
        assert out.shape == v.shape
        assert rec["choices"]["gdn_scan", "xla"] == 1
        assert not rec["fallbacks"]
    finally:
        setup_record.close(rec)

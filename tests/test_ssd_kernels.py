"""The state-space scan's Pallas kernels (``ops/ssd.py``: ``bps_ssd_fwd``,
``bps_ssd_bwd``) in Pallas' interpreter on the CPU, at the widths the
kernels are written for (heads of 64, a state of 128, chunks of 128):
against the XLA form (``ssd_xla``) and against the recurrence one position
at a time (``ssd_steps``), value and every gradient; a chunk that forgets
everything; buffers nothing wrote; ``jax.checkpoint``; and which path the
dispatcher ``ssd`` takes for which shapes. What the kernels cost and read
on the chip is the benchmark's business (PERF.md section 5); that Mosaic
takes them at the cell's shape is ``tests/test_chip_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops import ssd as S

Q = S.CHUNK
EVERY = tuple(range(6))


def _inputs(seed, chunks, dtype=jnp.float32, bsz=1, per=2, groups=2, p=64,
            n=128):
    """The seeded model's kind (``tests/test_nemotron_h.py::_scan_inputs``):
    steps of 0.02 to 0.7 under ``A`` in [-16, -1], so that a chunk forgets
    some heads' state and keeps others'; ``D`` a number a head."""
    heads, s = per * groups, chunks * Q
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (bsz, s, heads, p)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (bsz, s, heads)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (heads,), maxval=2.77)),
            (0.3 * jax.random.normal(k[3], (bsz, s, groups, n))).astype(dtype),
            (0.3 * jax.random.normal(k[4], (bsz, s, groups, n))).astype(dtype),
            1.0 + 0.1 * jax.random.normal(k[5], (heads,)))


def _kernels(*args):
    return S.ssd_kernels(*args, Q, True)


def _loss(fn, weight):
    return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()


def _close(got, want, tol):
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol * float(np.abs(want).max()))


# today's tolerances (tests/test_nemotron_h.py): float32, the forms differ
# by the order of their sums; bfloat16, the operands of the products are
# rounded to 8 bits of mantissa, against a float32 recurrence
@pytest.mark.parametrize("chunks", [1, 2, 5])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("per,groups,bsz", [(2, 2, 2), (8, 1, 1)],
                         ids=["2_heads_a_group-batch_2",
                              "8_heads_a_group-batch_1"])
def test_the_kernels_are_the_xla_form_and_the_recurrence(chunks, dtype, tol,
                                                         per, groups, bsz):
    args = _inputs(chunks, chunks, dtype, bsz, per, groups)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape,
                               jnp.float32)
    got = _kernels(*args)
    assert got.dtype == dtype and got.shape == args[0].shape
    _close(got, S.ssd_steps(*args), tol)
    # the same casts in the same places: the two chunked forms agree to
    # the order of their float32 sums, in either dtype
    _close(got, S.ssd_xla(*args), 2e-5 if dtype == jnp.float32 else 1e-2)
    grads = jax.grad(_loss(_kernels, weight), EVERY)(*args)
    for name, g, arg, w, v in zip(
            "x dt A B C D".split(), grads, args,
            jax.grad(_loss(S.ssd_steps, weight), EVERY)(*args),
            jax.grad(_loss(S.ssd_xla, weight), EVERY)(*args)):
        assert g.dtype == arg.dtype and g.shape == arg.shape, name
        _close(g, w, tol)
        _close(g, v, tol)


@pytest.mark.parametrize("p,per,n", [(128, 1, 128), (32, 4, 256)],
                         ids=["a_head_a_lane_tile", "four_heads_a_tile"])
def test_the_tile_follows_the_heads_width(p, per, n):
    """One algorithm whose lane tile holds 128 / p heads."""
    args = _inputs(7, 2, per=per, groups=2, p=p, n=n)
    weight = jax.random.normal(jax.random.PRNGKey(2), args[0].shape)
    _close(_kernels(*args), S.ssd_xla(*args), 2e-5)
    for g, w in zip(jax.grad(_loss(_kernels, weight), EVERY)(*args),
                    jax.grad(_loss(S.ssd_xla, weight), EVERY)(*args)):
        _close(g, w, 1e-4)      # A's: a float32 sum over every position


def test_a_long_forgetting_chunk_overflows_nothing_in_the_kernels():
    """Steps of 1 under A = -16: a chunk's total is -2048 here (-256 at
    the 16 positions of ``test_nemotron_h``'s chunk, passed at the 16th)
    and exp of its negation is infinite in float32; the masked exponents
    never see it, forward or backward."""
    x, dt, a, b, c, d = _inputs(4, 3)
    dt, a = jnp.ones_like(dt), jnp.full_like(a, -16.0)
    grads = jax.grad(lambda *t: _kernels(*t).sum(), EVERY)(x, dt, a, b, c, d)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    np.testing.assert_allclose(np.asarray(_kernels(x, dt, a, b, c, d)),
                               np.asarray(S.ssd_steps(x, dt, a, b, c, d)),
                               atol=1e-5)


def test_every_buffer_is_written_before_it_is_read():
    """The interpreter hands a kernel its outputs and scratch full of NaN
    (what the chip hands it is whatever the memory held, PERF.md section
    6, PR 30): the carried state starts from zero, every state before a
    chunk is written, the first as zeros, and the backward leaves no lane
    of its partial sums unwritten."""
    x, dt, a, b, c, d = _inputs(5, 3, per=2, groups=2)
    groups = b.shape[2]
    flat = S._flat(x, b, c)
    small = S._small(dt, a, d, groups, x.shape[3], Q)
    y, before = S._fwd_call(*flat, *small, chunk=Q, save=True, packed=False,
                            interpret=True)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(before).all())
    assert not np.asarray(before[:, 0]).any()
    assert np.asarray(before[:, 1:]).any()
    outs = S._bwd_call(*flat, *small, before, jnp.ones_like(flat[0]),
                       chunk=Q, packed=False, interpret=True)
    for name, out in zip("dx db dc dcols drows dlane".split(), outs):
        assert bool(jnp.isfinite(out).all()), name
    per = x.shape[2] // groups
    assert not np.asarray(outs[3][..., 2 * per:]).any()     # dcols' pad


def test_the_kernels_are_safe_to_rematerialise():
    args = _inputs(3, 2)
    weight = jax.random.normal(jax.random.PRNGKey(1), args[0].shape)
    plain = jax.value_and_grad(_loss(_kernels, weight), EVERY)(*args)
    again = jax.value_and_grad(
        _loss(jax.checkpoint(_kernels), weight), EVERY)(*args)
    for g, w in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _pack(x, b, c):
    return jnp.concatenate(S._flat(x, b, c), -1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_read_their_blocks_out_of_the_convolutions_output(dtype):
    """``x``, ``B`` and ``C`` side by side as the mixer's convolution
    writes them: the same kernels on the same blocks, so the same numbers
    to the last bit, and the three cotangents side by side."""
    x, dt, a, b, c, d = _inputs(11, 2, dtype, bsz=2, per=4, groups=2)
    weight = jax.random.normal(jax.random.PRNGKey(3),
                               x.shape[:2] + (x.shape[2] * x.shape[3],))
    groups, n = b.shape[2:]

    def packed(xbc, dt, a, d):
        return S.ssd_kernels_packed(xbc, dt, a, d, groups, n, Q, True)

    def apart(xbc, dt, a, d):
        inner, gn = x.shape[2] * x.shape[3], groups * n
        return _kernels(xbc[..., :inner].reshape(x.shape), dt, a,
                        xbc[..., inner:inner + gn].reshape(b.shape),
                        xbc[..., inner + gn:].reshape(c.shape),
                        d).reshape(weight.shape)

    args = (_pack(x, b, c), dt, a, d)
    got = jax.value_and_grad(_loss(packed, weight), (0, 1, 2, 3))(*args)
    want = jax.value_and_grad(_loss(apart, weight), (0, 1, 2, 3))(*args)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_packed_operands_take_the_same_paths(monkeypatch):
    """``ssd_packed`` is ``ssd`` of the three slices wherever ``ssd`` would
    not take the kernels (here: the CPU); on the TPU the kernels' operand
    is the packed array itself and no slice of it is made."""
    x, dt, a, b, c, d = _inputs(12, 1)
    groups, n = b.shape[2:]
    xbc = _pack(x, b, c)
    np.testing.assert_array_equal(
        np.asarray(S.ssd_packed(xbc, dt, a, d, groups, n)),
        np.asarray(S.ssd(x, dt, a, b, c, d).reshape(xbc.shape[:2] + (-1,))))
    trace = lambda: str(jax.make_jaxpr(jax.grad(   # noqa: E731
        lambda *t: S.ssd_packed(*t, groups, n).sum(), (0, 1, 2, 3)))(
            xbc, dt, a, d))
    assert "pallas_call" not in trace()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = trace()
    assert "bps_ssd_fwd" in jaxpr and "bps_ssd_bwd" in jaxpr
    width = xbc.shape[2]
    assert "slice[" not in "".join(
        line for line in jaxpr.splitlines() if f",{width}]" in line)


@pytest.mark.parametrize("x_shape,b_shape,chunk,takes", [
    ((2, 8192, 64, 64), (2, 8192, 8, 128), 128, True),      # the cell's
    ((1, 256, 4, 64), (1, 256, 2, 128), 128, True),
    ((1, 256, 2, 128), (1, 256, 2, 128), 128, True),    # a head a lane tile
    ((1, 256, 8, 32), (1, 256, 2, 256), 128, True),     # four heads a tile
    ((1, 512, 4, 64), (1, 512, 2, 128), 256, True),
    ((1, 256, 3, 64), (1, 256, 3, 128), 128, False),    # a head alone in
    ((1, 256, 6, 64), (1, 256, 2, 128), 128, False),    # a tile, odd heads
    ((1, 256, 4, 96), (1, 256, 2, 128), 128, False),    # across two tiles
    ((1, 256, 4, 64), (1, 256, 2, 64), 128, False),     # half a tile of state
    ((1, 64, 4, 8), (1, 64, 2, 16), 16, False),         # the tests' tiny one
    ((1, 256, 4, 64), (1, 256, 2, 128), 64, False),
    ((1, 256, 256, 64), (1, 256, 2, 128), 128, False),  # 128 heads a group
], ids=str)
def test_supported_by_shape(x_shape, b_shape, chunk, takes):
    assert S.supported(x_shape, b_shape, chunk) is takes


def _shapes(x_shape, b_shape):
    f32 = jnp.float32
    heads = x_shape[2]
    return (jax.ShapeDtypeStruct(x_shape, jnp.bfloat16),
            jax.ShapeDtypeStruct(x_shape[:3], f32),
            jax.ShapeDtypeStruct((heads,), f32),
            jax.ShapeDtypeStruct(b_shape, jnp.bfloat16),
            jax.ShapeDtypeStruct(b_shape, jnp.bfloat16),
            jax.ShapeDtypeStruct((heads,), f32))


def _traced(chunk, *shapes):
    """What ``ssd`` traces to, forward and backward: the jaxpr (it holds
    the kernels' names) and the lowering's locations (the scopes)."""
    grad = jax.grad(lambda *a: S.ssd(*a, chunk=chunk).astype(
        jnp.float32).sum(), EVERY)
    jaxpr = str(jax.make_jaxpr(grad)(*shapes))
    if "pallas_call" in jaxpr:      # Mosaic's lowering is the chip's
        return jaxpr, ""
    return jaxpr, jax.jit(grad).lower(*shapes).as_text(debug_info=True)


def test_the_dispatcher_follows_platform_and_shape(monkeypatch):
    """No argument, config field or environment variable: on the TPU the
    kernels where ``supported``, the XLA form under ``bps_ssd_xla``
    elsewhere, said once a shape."""
    takes = _shapes((1, 256, 4, 64), (1, 256, 2, 128))
    odd = _shapes((1, 64, 4, 8), (1, 64, 2, 16))
    jaxpr, text = _traced(128, *takes)              # here: the CPU
    assert "bps_ssd_fwd" not in jaxpr and "bps_ssd_xla" in text

    warned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from byteps_tpu.common import setup_record
    monkeypatch.setattr(setup_record, "_warned", set())
    from byteps_tpu.common import logging as bps_logging
    monkeypatch.setattr(bps_logging.get_logger(), "warning",
                        lambda *a: warned.append(a))
    jaxpr, _ = _traced(128, *takes)
    assert "bps_ssd_fwd" in jaxpr and "bps_ssd_bwd" in jaxpr
    assert not warned
    jaxpr, text = _traced(16, *odd)
    assert "pallas_call" not in jaxpr and "bps_ssd_xla" in text
    _traced(16, *odd)
    assert len(warned) == 1 and "falls back" in warned[0][0]


def test_each_kernel_is_one_jitted_function():
    """Three layers of one shape lower each kernel once (PERF.md section
    6, PR 30: a kernel traced once a call site cost 20 s of set-up)."""
    args = _inputs(0, 1)

    def three(*a):
        x = a[0]
        for _ in range(3):
            x = _kernels(x, *a[1:])
        return x.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(three)).lower(*args).as_text()
    assert text.count("func.func private @_fwd_call") == 2   # save or not
    assert text.count("func.func private @_bwd_call") == 1

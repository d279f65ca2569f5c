"""The kernels on either side of a Mamba-2 mixer's scan
(``ops/mamba2_kernels.py``: ``bps_ssm_conv_fwd`` / ``_bwd``,
``bps_ssm_norm_fwd`` / ``_bwd``) in Pallas' interpreter on the CPU,
against the XLA form they replace (``mamba2.causal_conv`` + SiLU,
``mamba2.group_rmsnorm`` of the gate): value and every gradient in both
dtypes; the convolution's halo at position 0, at every block and strip
boundary and between the sequences of a batch, with what must not be read
poisoned; which form ``mamba2``'s two sites take for which shapes; the
mixer and a training step of a small ``nemotron_h`` by both forms. What
the kernels cost on the chip is the benchmark's business (PERF.md section
5); that Mosaic takes them at the cell's shape is
``tests/test_chip_compile.py``'s."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common import setup_record
from byteps_tpu.models import decoder
from byteps_tpu.models import mamba2 as M
from byteps_tpu.ops import mamba2_kernels as K
from byteps_tpu.ops import ssd as S

ROWS, STRIP, EPS = 32, 16, 1e-5     # the kernels' own tests: small blocks
SEQ = K.ROWS[-1]        # the least sequence the two sites give the kernels
DTYPES = pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)],
    ids=["float32", "bfloat16"])


def _conv_xla(x, w, bias):
    return jax.nn.silu(M.causal_conv(x, w, bias)).astype(x.dtype)


def _norm_xla(y, z, scale, groups):
    f32 = jnp.float32
    return M.group_rmsnorm(y.astype(f32) * jax.nn.silu(z.astype(f32)),
                           scale, groups, EPS).astype(y.dtype)


def _conv(x, w, bias, rows=ROWS, strip=STRIP):
    return K.conv_silu_kernels(x, w, bias, rows, strip, True)


def _norm(y, z, scale, groups, rows=ROWS, strip=STRIP):
    return K.gated_norm_kernels(y, z, scale, groups, EPS, rows, strip, True)


def _conv_inputs(seed, bsz, s, channels, dtype, taps=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (bsz, s, channels)).astype(dtype),
            jax.random.uniform(k[1], (taps, channels), minval=-.5, maxval=.5),
            jax.random.uniform(k[2], (channels,), minval=-.5, maxval=.5),
            jax.random.normal(k[3], (bsz, s, channels)))


def _norm_inputs(seed, bsz, s, channels, dtype):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (bsz, s, channels)).astype(dtype),
            jax.random.normal(k[1], (bsz, s, channels)).astype(dtype),
            1.0 + 0.1 * jax.random.normal(k[2], (channels,)),
            jax.random.normal(k[3], (bsz, s, channels)))


def _close(got, want, tol):
    assert got.dtype == want.dtype and got.shape == want.shape
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol * float(np.abs(want).max()))


def _weighted(fn, weight):
    return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()


# ---------------------------------------------- against the XLA form
@DTYPES
@pytest.mark.parametrize("what", ["value", "d_xBC", "d_conv_w", "d_conv_b"])
@pytest.mark.parametrize("bsz,s,channels,taps", [
    (2, 96, 256, 4),        # three blocks of two strips, one run of lanes
    (1, 64, 640, 4),        # five lane tiles: five runs of one
    (2, 32, 128, 2),        # one block; a convolution of two taps
], ids=["3_blocks", "5_lane_tiles", "2_taps"])
def test_the_convolution_is_the_xla_form(dtype, tol, what, bsz, s, channels,
                                         taps):
    x, w, bias, weight = _conv_inputs(s, bsz, s, channels, dtype, taps)
    if what == "value":
        return _close(_conv(x, w, bias), _conv_xla(x, w, bias), tol)
    at = ["d_xBC", "d_conv_w", "d_conv_b"].index(what)
    _close(jax.grad(_weighted(_conv, weight), at)(x, w, bias),
           jax.grad(_weighted(_conv_xla, weight), at)(x, w, bias), tol)


@DTYPES
@pytest.mark.parametrize("what", ["value", "d_y", "d_z", "d_gated_norm"])
@pytest.mark.parametrize("bsz,s,channels,groups", [
    (2, 96, 256, 2),        # a lane tile a group
    (1, 64, 1024, 2),       # four, as in the cell
    (2, 32, 384, 1),        # one group of three
], ids=["1_tile_a_group", "4_tiles_a_group", "1_group"])
def test_the_gated_norm_is_the_xla_form(dtype, tol, what, bsz, s, channels,
                                        groups):
    y, z, scale, weight = _norm_inputs(s, bsz, s, channels, dtype)
    norm = functools.partial(_norm, groups=groups)
    xla = functools.partial(_norm_xla, groups=groups)
    if what == "value":
        return _close(norm(y, z, scale), xla(y, z, scale), tol)
    at = ["d_y", "d_z", "d_gated_norm"].index(what)
    _close(jax.grad(_weighted(norm, weight), at)(y, z, scale),
           jax.grad(_weighted(xla, weight), at)(y, z, scale), tol)


# ------------------------------------------------------------ the halo
@pytest.mark.parametrize("rows,strip", [(96, 96), (96, 16), (32, 32),
                                        (32, 8), (16, 16)], ids=str)
def test_the_cut_of_blocks_and_strips_moves_no_number(rows, strip):
    """A position's sums are the same operations in the same order
    wherever the block's and the strip's edges fall: every halo (a block's
    small blocks before and after, a strip's carried rows) hands on the
    rows themselves."""
    x, w, bias, weight = _conv_inputs(1, 2, 96, 128, jnp.bfloat16)
    grads = jax.value_and_grad(_weighted(_conv, weight), (0, 1, 2))
    want = grads(x, w, bias)
    got = jax.value_and_grad(
        _weighted(functools.partial(_conv, rows=rows, strip=strip), weight),
        (0, 1, 2))(x, w, bias)
    for name, g, v in zip(("loss", "d_xBC"), jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(v, np.float32), name)
    for g, v in zip(got[1][1:], want[1][1:]):   # float32 sums, another order
        _close(g, v, 1e-5)


def test_before_position_0_there_are_zeros():
    """The first taps - 1 outputs of EACH sequence see the bias and the
    taps that fall inside it, nothing else; and a cotangent at the last
    positions goes nowhere past the end."""
    x, w, bias, _ = _conv_inputs(2, 2, 64, 128, jnp.float32)
    got = np.asarray(_conv(x, w, bias))
    xn, wn, bn = (np.asarray(t, np.float64) for t in (x, w, bias))
    for t in range(3):
        pre = bn + sum(wn[3 - j] * xn[:, t - j] for j in range(t + 1))
        np.testing.assert_allclose(got[:, t], pre / (1 + np.exp(-pre)),
                                   atol=1e-5)
    # d x of the last position is its own tap's alone
    dy = jnp.zeros_like(x).at[:, -1].set(1.0)
    dx = jax.vjp(_conv, x, w, bias)[1](dy)[0]
    pre = M.causal_conv(x, w, bias)[:, -1]
    sig = jax.nn.sigmoid(pre)
    np.testing.assert_allclose(
        np.asarray(dx[:, -1]),
        np.asarray(w[3] * sig * (1 + pre * (1 - sig))), atol=1e-5)
    assert not np.asarray(dx[:, :-4]).any()


@pytest.mark.parametrize("poisoned", [0, 1], ids=["sequence_0_poisoned",
                                                  "sequence_1_poisoned"])
@pytest.mark.parametrize("stage", ["conv", "norm"])
def test_a_sequence_never_reads_its_neighbour(stage, poisoned):
    """One sequence of the batch all NaN, operands and cotangent: the
    other's outputs and gradients are what they are alone (the halo
    blocks stay inside a batch row, and the ends are selected, never
    multiplied, away)."""
    clean = 1 - poisoned
    if stage == "conv":
        x, w, bias, weight = _conv_inputs(3, 2, 64, 128, jnp.bfloat16)
        fn, small = _conv, (w, bias)
        big = (x,)
    else:
        y, z, scale, weight = _norm_inputs(3, 2, 64, 256, jnp.bfloat16)
        fn, small = functools.partial(_norm, groups=2), (scale,)
        big = (y, z)
    weight = weight.astype(jnp.bfloat16)
    bad = tuple(t.at[poisoned].set(jnp.nan) for t in big)
    out, pull = jax.vjp(lambda *a: fn(*a, *small), *bad)
    grads = pull(weight.at[poisoned].set(jnp.nan))
    alone = tuple(t[clean:clean + 1] for t in big)
    want, pull = jax.vjp(lambda *a: fn(*a, *small), *alone)
    wants = pull(weight[clean:clean + 1])
    assert bool(jnp.isnan(out[poisoned]).all())
    for g, v in zip((out,) + grads, (want,) + wants):
        np.testing.assert_array_equal(np.asarray(g[clean], np.float32),
                                      np.asarray(v[0], np.float32))


def test_the_kernels_are_safe_to_rematerialise():
    x, w, bias, weight = _conv_inputs(4, 1, 64, 256, jnp.float32)

    def both(x, w, bias, scale):
        return _norm(_conv(x, w, bias), x, scale, 2)

    args = (x, w, bias, jnp.ones((256,)))
    plain = jax.value_and_grad(_weighted(both, weight), (0, 1, 2, 3))(*args)
    again = jax.value_and_grad(
        _weighted(jax.checkpoint(both), weight), (0, 1, 2, 3))(*args)
    for g, v in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(v))


def test_each_kernel_is_one_jitted_function():
    """Three layers of one shape lower each kernel once (PERF.md section
    6, PR 30: a kernel traced once a call site cost 20 s of set-up)."""
    x, w, bias, _ = _conv_inputs(5, 1, 32, 128, jnp.float32)

    def three(x, w, bias):
        for _ in range(3):
            x = _norm(_conv(x, w, bias), x, bias, 1)
        return x.sum()

    text = jax.jit(jax.grad(three)).lower(x, w, bias).as_text()
    for call in ("_conv_fwd_call", "_conv_bwd_call", "_norm_fwd_call",
                 "_norm_bwd_call"):
        assert text.count(f"func.func private @{call}") == 1, call


# ------------------------------------------- which form a site takes
@pytest.mark.parametrize("x_shape,taps,takes", [
    ((2, 8192, 6144), 4, True),         # the cell's
    ((1, 512, 128), 2, True),
    ((2, 8192, 6100), 4, False),        # channels off the lane tile
    ((1, 768, 128), 4, True),           # three blocks of 256
    ((2, 8000, 6144), 4, False),        # positions off the block
    ((1, 64, 96), 4, False),            # the tests' tiny one
    ((2, 8192, 6144), 10, False),       # more taps than a strip carries
    ((2, 8192, 6144), 1, False),        # no convolution at all
], ids=str)
def test_the_convolutions_shapes(x_shape, taps, takes):
    assert K.conv_supported(x_shape, (taps, x_shape[2])) is takes


@pytest.mark.parametrize("y_shape,groups,takes", [
    ((2, 8192, 4096), 8, True),         # the cell's: four lane tiles a group
    ((1, 512, 1024), 2, True),
    ((2, 8192, 4096), 64, False),       # a group half a lane tile
    ((2, 8192, 4096), 4, False),        # a group wider than a block
    ((1, 768, 384), 1, True),           # three blocks of 256
    ((2, 8000, 4096), 8, False),        # positions off the block
    ((1, 64, 32), 2, False),            # the tests' tiny one
], ids=str)
def test_the_gated_norms_shapes(y_shape, groups, takes):
    assert K.norm_supported(y_shape, groups) is takes


def _sites(s, channels, groups):
    x, w, bias, _ = _conv_inputs(0, 1, s, channels, jnp.bfloat16)

    def both(x, w, bias):
        return M.gated_norm(M.conv_silu(x, w, bias), x, bias, groups,
                            EPS).astype(jnp.float32).sum()

    return str(jax.make_jaxpr(jax.grad(both, (0, 1, 2)))(x, w, bias))


NAMES = {"bps_ssm_conv_fwd", "bps_ssm_conv_bwd", "bps_ssm_norm_fwd",
         "bps_ssm_norm_bwd"}


def test_the_sites_follow_platform_and_shape(monkeypatch):
    """No argument, config field or environment variable: on the TPU the
    kernels where the shapes allow, the XLA form elsewhere, counted in the
    set-up record and said once a site and shape."""
    assert "pallas_call" not in _sites(SEQ, 256, 2)         # here: the CPU

    warned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(setup_record, "_warned", set())
    monkeypatch.setattr(setup_record.get_logger(), "warning",
                        lambda *a: warned.append(a))
    rec = setup_record.open_record()
    try:
        jaxpr = _sites(SEQ, 256, 2)
        assert set(re.findall(r"name=(bps_ssm_\w+)", jaxpr)) == NAMES
        assert not warned and not rec["fallbacks"]
        for _ in range(2):      # off the lane tile; off the block
            assert "pallas_call" not in _sites(SEQ, 96, 2)
        assert "pallas_call" not in _sites(SEQ - 16, 256, 2)
    finally:
        setup_record.close(rec)
    assert dict(rec["choices"]) == {
        ("ssm_conv", "kernels"): 1, ("ssm_norm", "kernels"): 1,
        ("ssm_conv", "xla"): 3, ("ssm_norm", "xla"): 3}
    assert sorted((k[0], v) for k, v in rec["fallbacks"].items()) == [
        ("ssm_conv", 1), ("ssm_conv", 2), ("ssm_norm", 1), ("ssm_norm", 2)]
    assert len(warned) == 4 and all("falls back" in w[0] for w in warned)


# ------------------------------------------ the mixer and a training step
@pytest.fixture
def interpreted(monkeypatch):
    """The TPU's choices on the CPU: every site takes its kernels, run in
    the interpreter. Returns a switch that sends this PR's two sites back
    to the XLA form and leaves the scan's kernels where they are."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conv, norm, scan = (K.conv_silu_kernels, K.gated_norm_kernels,
                        S.ssd_kernels_packed)
    monkeypatch.setattr(K, "conv_silu_kernels",
                        lambda *a: conv(*a, 0, K.CONV_STRIP, True))
    monkeypatch.setattr(K, "gated_norm_kernels",
                        lambda *a: norm(*a, 0, K.NORM_STRIP, True))
    monkeypatch.setattr(S, "ssd_kernels_packed",
                        lambda *a: scan(*a[:-1], True))

    def xla_form():
        monkeypatch.setattr(K, "conv_supported", lambda *a: False)
        monkeypatch.setattr(K, "norm_supported", lambda *a: False)
    return xla_form


SSM = M.SSMConfig(heads=4, head_dim=64, groups=2, state=128)


@DTYPES
def test_the_mixer_by_the_kernels_is_the_mixer_by_xla(interpreted, dtype, tol):
    hidden = 64
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    blk = M.init_mixer(k[0], hidden, SSM)
    a = jax.random.normal(k[1], (2, SEQ, hidden)).astype(dtype)
    weight = jax.random.normal(k[2], a.shape)
    run = jax.value_and_grad(_weighted(
        lambda a, blk: M.mixer(a, blk, SSM, EPS), weight), (0, 1))
    rec = setup_record.open_record()
    try:
        got = run(a, blk)
    finally:
        setup_record.close(rec)
    assert rec["choices"]["ssm_conv", "kernels"] == 1
    assert rec["choices"]["ssm_norm", "kernels"] == 1
    interpreted()
    want = run(a, blk)
    for g, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(g, v, tol)


def test_a_training_step_by_the_kernels_is_the_step_by_xla(interpreted):
    """One AdamW step of a small ``nemotron_h`` (state-space and routed
    layers, checkpointed; attention's kernels have no interpreter switch
    to steer from here) whose state-space shapes the kernels take,
    within ``tests/test_nemotron_h.py``'s tolerances: the loss, every
    gradient's norm, the norm of every parameter's change."""
    import optax
    cfg = decoder.nemotron_h_tiny(
        ssm_head_dim=64, ssm_state=128, chunk=128, remat=True,
        layer_kinds=("ssm", "moe", "ssm"))
    params = decoder.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, SEQ), 0,
                                cfg.vocab_size)
    tx = optax.adamw(1e-3)

    def norms(tree):
        return np.asarray([jnp.linalg.norm(leaf.astype(jnp.float32))
                           for leaf in jax.tree_util.tree_leaves(tree)])

    def step(params):
        loss, grads = jax.value_and_grad(decoder.causal_lm_loss)(
            params, cfg, tokens)
        updates, _ = tx.update(grads, tx.init(params), params)
        return float(loss), grads, norms(updates)

    rec = setup_record.open_record()
    try:
        got = step(params)
    finally:
        setup_record.close(rec)
    assert rec["choices"]["ssm_conv", "kernels"] == 2
    assert rec["choices"]["ssm_norm", "kernels"] == 2
    interpreted()
    want = step(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_allclose(norms(got[1]), norms(want[1]), rtol=2e-4)
    np.testing.assert_allclose(got[2], want[2], rtol=2e-3)
    for (path, g), v in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree_util.tree_leaves(want[1])):
        v = np.asarray(v)
        np.testing.assert_allclose(np.asarray(g), v, err_msg=str(path),
                                   atol=2e-4 * float(np.abs(v).max()))

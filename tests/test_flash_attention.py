"""Flash-attention Pallas kernels vs the naive reference path.

Runs under Pallas interpret mode on the CPU test mesh, so the exact
kernel logic (online softmax, block masking, backward recompute) is what
is validated — forward values and all three input gradients, causal and
bidirectional, fp32 and bf16."""

import ast
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu.ops.flash_attention as fa
from byteps_tpu.ops.flash_attention import (attention, flash_attention,
                                            local_attention)


def make_qkv(rng, b, s, h, d, dtype):
    q = rng.randn(b, s, h, d).astype(dtype)
    k = rng.randn(b, s, h, d).astype(dtype)
    v = rng.randn(b, s, h, d).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,bq,bk", [(256, 128, 128), (384, 128, 128),
                                     (256, 256, 128)])
def test_forward_matches_reference(causal, s, bq, bk):
    rng = np.random.RandomState(0)
    q, k, v = make_qkv(rng, 2, s, 2, 64, np.float32)
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, bq, bk, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    rng = np.random.RandomState(1)
    q, k, v = make_qkv(rng, 1, 256, 2, 64, np.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, None, 128, 128, True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = local_attention(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_bf16_forward_close():
    rng = np.random.RandomState(2)
    q, k, v = make_qkv(rng, 1, 256, 2, 64, np.float32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = local_attention(q, k, v)
    out = flash_attention(qb, kb, vb, False, None, 128, 128, True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.0, atol=0.05)


def test_dispatcher_falls_back_on_cpu():
    rng = np.random.RandomState(3)
    q, k, v = make_qkv(rng, 1, 100, 2, 32, np.float32)  # odd seq
    out = attention(q, k, v)           # must not try the kernel path
    ref = local_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_scale_override():
    rng = np.random.RandomState(4)
    q, k, v = make_qkv(rng, 1, 128, 1, 64, np.float32)
    out = flash_attention(q, k, v, False, 0.5, 128, 128, True)
    ref = local_attention(q, k, v, scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_naive_fallback_warns_once_per_shape(monkeypatch):
    """On TPU, silently downgrading to O(s^2) attention must be loud."""
    import logging

    from byteps_tpu.common.logging import get_logger

    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    from byteps_tpu.common import setup_record
    monkeypatch.setattr(setup_record, "_warned", set())
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    logger = get_logger()
    logger.addHandler(handler)
    prev_level = logger.level
    logger.setLevel(logging.WARNING)    # env may have raised it to ERROR
    try:
        q = jnp.zeros((1, 65, 2, 8), jnp.float32)   # 65 % 128 != 0
        fa.attention(q, q, q)
        fa.attention(q, q, q)                        # same shape: no repeat
        warns = [m for m in records if "falls back to xla" in m
                 and "naive O(s^2)" in m]
        assert len(warns) == 1, records
    finally:
        logger.setLevel(prev_level)
        logger.removeHandler(handler)


# ---------------------------------------------------------------------------
# round 4: mismatched q/kv lengths (cross-attention) + additive score bias
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk", [(128, 384), (384, 128), (256, 256)])
def test_cross_attention_mismatched_lengths(sq, sk):
    """The tiling contract is per-axis: q and kv sequence lengths may
    differ (decoder queries over encoder memory). Forward and all
    three gradients must match the einsum reference."""
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, sq, 2, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(2, sk, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, sk, 2, 64).astype(np.float32))
    out = flash_attention(q, k, v, False, None, 128, 128, True)
    ref = local_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss(f):
        return lambda q, k, v: (f(q, k, v) ** 2).sum()
    gf = jax.grad(loss(lambda *a: flash_attention(
        *a, False, None, 128, 128, True)), argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss(local_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, gn, "qkv"):
        assert a.shape == b.shape, nm
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=1e-4, atol=1e-5, err_msg=nm)


def test_causal_cross_attention_rejected():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, q, True, None, 128, 128, True)


def _naive_product(q, k, v, bias, causal=False):
    """softmax(q k' / sqrt(d) + bias) v spelled out, no helper of the
    package in it: what ``local_attention(bias=)`` is held to."""
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    sc = sc + bias[None]
    if causal:
        sc = jnp.where(jnp.tril(jnp.ones(sc.shape[-2:], bool)), sc, -jnp.inf)
    p = jnp.exp(sc - sc.max(-1, keepdims=True))
    return jnp.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("causal", [False, True])
def test_bias_forward_backward_exact(causal):
    """Additive [h, sq, sk] score bias (T5 relative position) in the
    reference, ``local_attention(bias=)``: what ``attention`` falls back
    to for a ``rel_table`` off the kernels, and what every ``rel_table``
    test compares the kernels with. Forward, dq/dk/dv and dbias against
    the product spelled out. (The kernels took such a bias until PR 47;
    no caller passed one.)"""
    rng = np.random.RandomState(3)
    b, s, h, d = 2, 256, 2, 64
    q, k, v = make_qkv(rng, b, s, h, d, np.float32)
    bias = jnp.asarray(rng.randn(h, s, s).astype(np.float32))
    out = local_attention(q, k, v, causal=causal, bias=bias)
    ref = _naive_product(q, k, v, bias, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def f_loss(q, k, v, bb):
        return (local_attention(q, k, v, causal=causal, bias=bb)
                ** 2).sum()

    def n_loss(q, k, v, bb):
        return (_naive_product(q, k, v, bb, causal) ** 2).sum()

    gf = jax.grad(f_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gn = jax.grad(n_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b_, nm in zip(gf, gn, ["dq", "dk", "dv", "dbias"]):
        scale = float(jnp.abs(b_).max())
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b_) / scale,
                                   rtol=1e-4, atol=1e-5, err_msg=nm)


def test_mismatched_bias_cross():
    """bias + mismatched lengths together in the reference (biased
    cross-attention is not a T5 case, but the fall-back's contract
    covers it), and through ``attention``'s fall-back a ``rel_table``
    over them."""
    from byteps_tpu.ops.relpos import relative_bias
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 384, 2, 64).astype(np.float32))
    bias = jnp.asarray(rng.randn(2, 128, 384).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(local_attention(q, k, v, bias=bias)),
        np.asarray(_naive_product(q, k, v, bias)), rtol=2e-5, atol=2e-5)
    table = jnp.asarray(rng.randn(2, 32).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(attention(q, k, v, impl="naive", rel_table=table)),
        np.asarray(_naive_product(
            q, k, v, relative_bias(table.T, 128, 384, True, 32, 128))),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_bwd_matches_split(causal):
    """VERDICT r4 #1: the single-block-pair fused backward (one kernel,
    shared p/dp recompute, 5 matmuls) must produce the same dq/dk/dv as
    the split dq + dkv kernels (7 matmuls), which the same tensors take
    in blocks of 128 (two a side).

    Tolerance is float-level, not bitwise: the fused kernel computes
    the softmax correction IN-KERNEL as sum_j p_ij*dp_ij while the
    split path sums do*out over d — mathematically identical, but the
    fp32 summation order differs (~1e-5 absolute on unit-scale
    inputs)."""
    rng = np.random.RandomState(11)
    q, k, v = make_qkv(rng, 2, 256, 4, 64, np.float32)

    def grads(block):
        def loss(q, k, v):
            return (flash_attention(q, k, v, causal, None, block, block,
                                    True).astype(jnp.float32) ** 2).sum()
        grad = jax.grad(loss, argnums=(0, 1, 2))
        names = set(re.findall(r"name=(bps_flash_bwd\w+)",
                               str(jax.make_jaxpr(grad)(q, k, v))))
        return names, grad(q, k, v)

    fused_names, fused = grads(512)
    split_names, split = grads(128)
    assert fused_names == {"bps_flash_bwd_fused"}
    assert split_names == {"bps_flash_bwd_dq", "bps_flash_bwd_dkv"}
    for a, b_, nm in zip(fused, split, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=2e-5, err_msg=nm)


def test_rel_table_ht_clamp_keeps_divisibility(monkeypatch):
    """ADVICE r4 (medium): clamping a head tile to the dtable row bound
    must re-check h % ht — a tile of 12 with h=12 clamped to
    min(12, 8)=8 would cover only heads 0-7 and silently emit garbage
    for the rest. The clamp must land on a divisor (6)."""
    from byteps_tpu.ops.flash_attention import _clamp_ht
    assert _clamp_ht(12, 12) == 6
    assert _clamp_ht(8, 16) == 8
    assert _clamp_ht(16, 16) == 8
    assert _clamp_ht(7, 7) == 7
    assert _clamp_ht(5, 5) == 5      # already <= bound, kept
    assert _clamp_ht(13, 13) == 1    # prime > bound: no divisor fits

    from byteps_tpu.ops.relpos import relative_bias
    monkeypatch.setattr(fa, "_head_tile", lambda *a, **kw: 12)
    rng = np.random.RandomState(7)
    b, s, h, d, nb = 1, 128, 12, 8, 16
    q, k, v = make_qkv(rng, b, s, h, d, np.float32)
    table = jnp.asarray(rng.randn(h, nb).astype(np.float32))
    out = flash_attention(q, k, v, False, 1.0, 128, 128, True,
                          rel_table=table)
    mat = relative_bias(table.T, s, s, True, nb, 128)
    ref = local_attention(q, k, v, causal=False, scale=1.0, bias=mat)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,bidir", [(False, True), (True, False)])
def test_rel_table_in_kernel_exact(causal, bidir):
    """T5 relative bias computed IN-KERNEL from the [h, nb] table
    (bucket map from block offsets, dtable accumulated in VMEM
    scratch) must match the materialized-bias reference — forward,
    dq/dk/dv, and dtable."""
    from byteps_tpu.ops.relpos import relative_bias
    rng = np.random.RandomState(5)
    b, s, h, d, nb = 2, 256, 2, 64, 32
    q, k, v = make_qkv(rng, b, s, h, d, np.float32)
    table = jnp.asarray(rng.randn(h, nb).astype(np.float32))

    def flash(q, k, v, t):
        return flash_attention(q, k, v, causal, 1.0, 128, 128, True,
                               rel_table=t, rel_bidirectional=bidir)

    def ref(q, k, v, t):
        mat = relative_bias(t.T, s, s, bidir, nb, 128)
        return local_attention(q, k, v, causal=causal, scale=1.0,
                               bias=mat)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v, table)), np.asarray(ref(q, k, v, table)),
        rtol=2e-5, atol=2e-5)
    gf = jax.grad(lambda *a: (flash(*a) ** 2).sum(),
                  argnums=(0, 1, 2, 3))(q, k, v, table)
    gn = jax.grad(lambda *a: (ref(*a) ** 2).sum(),
                  argnums=(0, 1, 2, 3))(q, k, v, table)
    for a, b_, nm in zip(gf, gn, ["dq", "dk", "dv", "dtable"]):
        scale = float(jnp.abs(b_).max())
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b_) / scale,
                                   rtol=1e-4, atol=1e-5, err_msg=nm)


def test_rel_table_no_materialized_bias_in_jaxpr():
    """The whole point of the in-kernel form: a long-sequence biased
    self-attention must not create ANY [*, s, s]-shaped value outside
    the kernel (the materialized bias is 32 GB at s=32k, h=8). Checked
    on the jaxpr of a length-4096 forward+backward."""
    s, h, d, nb = 4096, 2, 64, 32
    q = jnp.zeros((1, s, h, d), jnp.bfloat16)
    table = jnp.zeros((h, nb), jnp.float32)

    def loss(q, t):
        return (flash_attention(q, q, q, False, 1.0, 512, 512, True,
                                rel_table=t).astype(jnp.float32)
                ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(q, table)
    big = s * s
    for eqn in jaxpr.jaxpr.eqns:
        for var in list(eqn.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", ())
            assert int(np.prod(shape or (1,))) < big, (
                f"O(s^2) intermediate {shape} materialized by {eqn.primitive}")


# ---- one function makes a block's scores and one its mask (PR 47)

@pytest.mark.parametrize("body,shape,table,scores,masks", [
    # a rel_table at one block pair: four heads a program in all three
    ("_fwd_kernel", (2, 256, 4, 64), True, 4, 4),
    ("_dq_kernel", (2, 256, 4, 64), True, 4, 4),
    ("_dkv_kernel", (2, 256, 4, 64), True, 4, 4),
    # GPT-2's forward: two heads a program, two row chunks of 512, and
    # the mask shared by the heads of a chunk
    ("_fwd_single_kernel", (2, 1024, 4, 64), False, 4, 2),
    ("_dqkv_fused_kernel", (2, 512, 8, 128), False, 2, 2),
], ids=["online_forward", "dq", "dkv", "single_block", "fused_backward"])
def test_every_body_makes_its_scores_and_mask_by_the_one_function(
        monkeypatch, body, shape, table, scores, masks):
    """Each of the five kernel bodies gets a block's scores from
    ``_scores`` and its mask from ``_mask``, one call a head (the single
    block's mask one a row chunk: its heads share it): what the next
    mechanism on the scores or the mask has to edit is one function, not
    five bodies. Traced without interpret mode, so the head tiles are the
    chip's."""
    traced = getattr(fa, body)
    # no body makes a position's iota of its own
    assert "broadcasted_iota" not in inspect.getsource(traced)
    inside = []                 # non-empty while ``body`` is being traced
    counts = {"_scores": 0, "_mask": 0}

    def entered(*args, **kwargs):
        inside.append(body)
        try:
            return traced(*args, **kwargs)
        finally:
            inside.pop()
    monkeypatch.setattr(fa, body, entered)
    for name in counts:
        def counted(*args, _fn=getattr(fa, name), _name=name, **kwargs):
            counts[_name] += len(inside)
            return _fn(*args, **kwargs)
        monkeypatch.setattr(fa, name, counted)
    q = jnp.zeros(shape, jnp.bfloat16)
    operands = (q, q, q) + (
        (jnp.zeros((shape[2], 32), jnp.float32),) if table else ())

    def loss(q, k, v, *t):
        return flash_attention(q, k, v, True, rel_table=t[0] if t else None,
                               rel_bidirectional=False).astype(
                                   jnp.float32).sum()

    jax.make_jaxpr(jax.grad(loss, tuple(range(len(operands)))))(*operands)
    assert counts == {"_scores": scores, "_mask": masks}


# ---- the row statistics' contract: lane-dense across every pallas_call

def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (list, tuple)) else (value,):
            v = getattr(v, "jaxpr", v)            # ClosedJaxpr -> Jaxpr
            if hasattr(v, "eqns"):
                yield v


def equations(jaxpr, primitive):
    """Every ``primitive`` equation of a jaxpr, those of its sub-jaxprs
    (scan, cond, shard_map, custom_vjp...) included; kernel bodies are
    not entered."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == primitive:
                found.append(eqn)
            if eqn.primitive.name != "pallas_call":
                for sub in _sub_jaxprs(eqn):
                    walk(sub)

    walk(getattr(jaxpr, "jaxpr", jaxpr))
    return found


def assert_statistics_lane_dense(jaxpr, rows, min_calls):
    """No operand or result of any pallas_call ends in a dimension of 1,
    and each call's float32 row statistics (one number a row of
    attention: ``rows`` numbers) end in whole 128-lane rows. Every flash
    kernel moves at least one statistic, so a call without one means
    this test no longer sees them."""
    calls = equations(jaxpr, "pallas_call")
    assert len(calls) >= min_calls, [str(c.params.get("name")) for c in calls]
    for eqn in calls:
        name = eqn.params.get("name")
        statistics = 0
        for var in list(eqn.invars) + list(eqn.outvars):
            shape, dtype = var.aval.shape, var.aval.dtype
            assert shape[-1] != 1, (name, shape)
            if dtype == jnp.float32 and int(np.prod(shape)) == rows:
                statistics += 1
                assert shape[-1] % 128 == 0, (name, shape)
        assert statistics >= 1, (name, [v.aval.shape for v in eqn.invars])


@pytest.mark.parametrize("sq,sk,h,causal,blocks,extra,calls", [
    (512, 512, 16, False, (512, 512), None, 2),       # fused backward, ht 4/2
    (128, 128, 16, False, (512, 512), None, 2),       # fused, ht 8
    (1024, 1024, 16, False, (512, 512), None, 3),     # split backward, ht 1
    (1024, 1024, 16, True, (512, 512), None, 3),      # causal split
    (256, 256, 4, True, (128, 128), "rel_table", 3),
    (128, 384, 4, False, (128, 128), None, 3),        # cross: follows sq
    (384, 128, 4, False, (128, 128), None, 3),
    (1024, 1024, 16, True, (None, None), None, 3),    # GPT-2's cell
], ids=["fused", "fused_s128", "split", "causal", "rel_table",
        "cross_sq128", "cross_sq384", "default_blocks"])
def test_no_padded_statistic_crosses_a_kernel(sq, sk, h, causal, blocks,
                                              extra, calls):
    """lse (and delta) cross HBM with the sequence on the lane axis: a
    [b, h, s, 1] column is padded 128x there (512 bytes a row of
    attention instead of 4). Traced without interpret mode, so the head
    tiles are the chip's."""
    b, d = 2, 64
    q = jnp.zeros((b, sq, h, d), jnp.bfloat16)
    k = jnp.zeros((b, sk, h, d), jnp.bfloat16)
    operand = {None: (),
               "rel_table": (jnp.zeros((h, 32), jnp.float32),)}[extra]

    def loss(q, k, v, *e):
        return (flash_attention(q, k, v, causal, None, *blocks, False,
                                **dict(zip([extra], e)))
                .astype(jnp.float32) ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=tuple(range(3 + len(operand)))))(q, k, k, *operand)
    assert_statistics_lane_dense(jaxpr, b * h * sq, calls)


def _plain_lse(q, k, causal, scale):
    """[b, h, s] log-sum-exp of the scores as ``local_attention`` masks
    them (q, k: [b, h, s, d])."""
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                    preferred_element_type=jnp.float32) * scale
    if causal:
        sc = jnp.where(jnp.tril(jnp.ones(sc.shape[-2:], bool)), sc, -jnp.inf)
    return jax.scipy.special.logsumexp(sc, axis=-1)


def _plain_out(q, k, v, causal, scale):
    """``local_attention`` on [b, h, s, d] operands."""
    return jnp.swapaxes(local_attention(
        *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), causal=causal,
        scale=scale), 1, 2)


@pytest.mark.parametrize("ht", [1, 8])
@pytest.mark.parametrize("s", [128, 384, 1024])
def test_lse_matches_xla_forward(monkeypatch, s, ht):
    """The kernel's lse, converted to rows 128 at a time, is the plain
    forward's [b, h, s] log-sum-exp: one block (128, 384) and two (1024),
    one head a program and eight."""
    monkeypatch.setattr(fa, "_head_tile", lambda *a, **kw: ht)
    rng = np.random.RandomState(13)
    b, h, d = 1, 8, 16
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
               for _ in range(3))
    bq = min(s, 512)
    out, lse = fa._flash_fwd(q, k, v, True, d ** -0.5, bq, bq, True)
    want = _plain_lse(q, k, True, d ** -0.5)
    assert lse.shape == want.shape == (b, h, s)
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_plain_out(q, k, v, True, d ** -0.5)),
        rtol=2e-5, atol=2e-5)


# ---- the single-block forward (one kv block holds the row's keys)

def _bhsd(rng, b, h, s, d):
    return jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))


@pytest.mark.parametrize("s,causal,blocks", [
    (256, False, (256, 256)),
    (256, True, (256, 256)),
    (384, True, (384, 384)),        # triangle, one chunk of rows
    (1024, True, (1024, 1024)),     # triangle, two chunks of 512 rows
    (1024, False, (512, 1024)),     # two q blocks against the whole kv
    (512, True, (128, 512)),        # causal, q block from the grid
], ids=["s256", "s256_causal", "s384_causal", "s1024_triangle",
        "s1024_nq2", "s512_causal_nq4"])
def test_single_block_forward_matches_online(s, causal, blocks):
    """With the whole kv row in one block the forward is a plain softmax
    (no carried scratch state); out and lse are the online kernel's (128
    blocks) and the plain XLA forward's."""
    from byteps_tpu.ops.flash_attention import _flash_fwd
    rng = np.random.RandomState(17)
    b, h, d = 1, 2, 32
    q, k, v = (_bhsd(rng, b, h, s, d) for _ in range(3))
    scale = d ** -0.5
    out, lse = _flash_fwd(q, k, v, causal, scale, *blocks, True)
    online_out, online_lse = _flash_fwd(q, k, v, causal, scale, 128, 128,
                                        True)
    ref_out = _plain_out(q, k, v, causal, scale)
    ref_lse = _plain_lse(q, k, causal, scale)
    for got, want in ((out, online_out), (out, ref_out)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    for got, want in ((lse, online_lse), (lse, ref_lse)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=2e-6)


@pytest.mark.parametrize("sq,sk,causal,extra,forward,want", [
    (512, 512, False, False, True, (512, 512)),
    (128, 128, False, False, True, (128, 128)),
    (1024, 1024, True, False, True, (1024, 1024)),   # whole q too: triangle
    (1024, 1024, False, False, True, (512, 1024)),
    (2048, 1024, False, False, True, (512, 1024)),   # cross: kv whole
    (2048, 2048, True, False, True, (1024, 1024)),   # past _WHOLE_KV
    (1024, 1024, True, True, True, (512, 512)),      # a rel_table
    (1024, 1024, True, False, False, (512, 512)),    # the backward
    (8192, 8192, True, False, True, (1024, 1024)),   # the 8k cells' calls
    (8192, 8192, True, False, False, (1024, 1024)),
    (8192, 8192, True, {"window": 2048}, True, (1024, 1024)),
    (8192, 8192, True, {"window": 2048}, False, (1024, 1024)),
    (8192, 8192, True, {"kv_heads": 1}, True, (1024, 1024)),
    (8192, 8192, True, {"kv_heads": 1}, False, (1024, 1024)),
    (2048, 2048, True, False, False, (1024, 1024)),
    (4096, 4096, False, False, True, (1024, 1024)),  # no mask: the same
    (2048, 2048, True, True, True, (512, 512)),      # a rel_table: not measured
    (1536, 1536, True, False, True, (512, 512)),     # 1024 does not divide
], ids=["s512", "s128", "s1024_causal", "s1024", "cross", "s2048",
        "biased", "backward", "s8192", "s8192_backward", "s8192_band",
        "s8192_band_backward", "s8192_grouped", "s8192_grouped_backward",
        "s2048_backward", "s4096_full", "s2048_biased", "s1536"])
def test_default_blocks(sq, sk, causal, extra, forward, want):
    """No block named: in a plain call's forward the whole kv sequence
    up to 1024 keys (with the whole q when causal); past them 1024 x
    1024, forward and backward, a band and grouped kv heads like the
    triangle (``_default_block``: what the chip said, PR 45); 512 in the
    backward of at most 1024 keys and with a rel_table (``plain`` False:
    since PR 47 the one biased form the kernels take); a named block is
    taken as given."""
    from byteps_tpu.ops.flash_attention import _default_block, _resolve
    shown = extra if isinstance(extra, dict) else {}
    plain = extra is False or bool(shown)
    q = jnp.zeros((1, sq, 2, 64), jnp.bfloat16)
    k = jnp.zeros((1, sk, shown.get("kv_heads", 2), 64), jnp.bfloat16)
    block = _default_block(sk, 64, 64, plain)
    assert block == _default_block(sk, 128, 128, plain)   # widths alike
    whole = forward and plain
    assert _resolve(q, k, None, None, None, whole, causal, block)[1:] == want
    assert _resolve(q, k, None, 128, 128, whole, causal, block)[1:] == (
        128, 128)
    if shown:       # through the call: the blocks of its three kernels
        def loss(q, k):
            return flash_attention(q, k, k, causal, window=shown.get(
                "window")).astype(jnp.float32).sum()
        calls = equations(jax.make_jaxpr(jax.grad(loss, (0, 1)))(q, k),
                          "pallas_call")
        group = 2 // k.shape[2]
        grids = {str(e.params["name"]): e.params["grid_mapping"].grid[2:]
                 for e in calls}
        steps = 3 if "window" in shown else 8    # 2,048 keys: 3 of 1024
        assert grids == {"bps_flash_fwd": (8 * group, steps),
                         "bps_flash_bwd_dq": (8 * group, steps),
                         "bps_flash_bwd_dkv": (8, steps * group)}


def test_default_forward_is_single_block_at_1024():
    """GPT-2's benchmark shape (causal, 1024) takes the single-block
    forward by default, one program a head tile, and its gradients are
    the reference's."""
    rng = np.random.RandomState(19)
    q, k, v = make_qkv(rng, 1, 1024, 2, 32, np.float32)

    def loss(q, k, v):
        o = flash_attention(q, k, v, True, None, None, None, True)
        return jnp.sum(jnp.sin(o))

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v)
    grids = {str(e.params["name"]): e.params["grid_mapping"].grid
             for e in equations(jaxpr, "pallas_call")}
    assert grids["bps_flash_fwd"][2:] == (1, 1)
    assert grids["bps_flash_bwd_dq"][2:] == (2, 2)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(local_attention(q, k, v, causal=True)))

    gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# ---- what chooses the path: arguments and shapes, nothing else

@pytest.mark.parametrize("name,value", [
    ("BPS_FLASH_FUSED_BWD", "0"), ("BPS_FLASH_BQ", "128"),
    ("BPS_FLASH_BK", "128"), ("BPS_FLASH_HT", "2"),
    ("BPS_FLASH_VMEM_BUDGET", "1")])
def test_no_environment_variable_steers_the_kernels(monkeypatch, name, value):
    """The five retired variables: set or not, a call traces the same
    kernels, blocks and head tile (BERT's shape class: one block pair, the
    fused backward, eight heads a program)."""
    q = jnp.zeros((1, 256, 8, 64), jnp.bfloat16)

    def trace():
        def loss(q, k, v):
            return flash_attention(q, k, v).astype(jnp.float32).sum()
        return str(jax.make_jaxpr(jax.value_and_grad(
            loss, argnums=(0, 1, 2)))(q, q, q))

    monkeypatch.delenv(name, raising=False)
    clean = trace()
    monkeypatch.setenv(name, value)
    assert trace() == clean
    assert "name=bps_flash_bwd_fused" in clean and "grid=(1, 1, 1, 1)" in clean


def test_hybrid_is_no_impl():
    q = jnp.zeros((1, 128, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="got 'hybrid'"):
        attention(q, q, q, impl="hybrid")


def test_the_kernels_import_nothing_from_parallel():
    """``parallel/ring.py`` imports the kernels; the arrow points one way."""
    with open(fa.__file__) as f:
        tree = ast.parse(f.read())
    imported = [(n.module or "", n.level) for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)]
    imported += [(a.name, 0) for n in ast.walk(tree)
                 if isinstance(n, ast.Import) for a in n.names]
    assert not [m for m, level in imported
                if m.startswith("byteps_tpu.parallel")
                or (level == 2 and m.split(".")[0] == "parallel")], imported


# ---- heads narrower than a lane tile cross HBM as [b, s, heads*d] (PR 33)

KERNELS = {"bps_flash_fwd", "bps_flash_bwd_fused", "bps_flash_bwd_dq",
           "bps_flash_bwd_dkv"}


def flash_calls(fn, *args):
    """(the pallas_call equations, the transpose equations) of ``fn``'s
    jaxpr, kernel bodies not entered."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return equations(jaxpr, "pallas_call"), equations(jaxpr, "transpose")


def wide_operands(eqn):
    """Shapes of a kernel's q, k, v, out, do, dq, dk, dv: its operands and
    results that are no float32 statistic or table."""
    return [v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)
            if v.aval.ndim >= 3 and v.aval.shape[-2] != 1
            and v.aval.dtype != jnp.float32]


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("s,h,d,causal,kernels,tile", [
    (512, 16, 64, False, ("bps_flash_fwd", "bps_flash_bwd_fused"), 2),
    (1024, 4, 64, True,
     ("bps_flash_fwd", "bps_flash_bwd_dq", "bps_flash_bwd_dkv"), 2),
    (256, 8, 32, True, ("bps_flash_fwd", "bps_flash_bwd_fused"), 4),
], ids=["bert_s512_fused", "gpt2_s1024_split", "width32_tile4"])
def test_lane_dense_matches_reference(s, h, d, causal, kernels, tile, dtype):
    """Forward and the three gradients of the lane-dense path against
    ``local_attention`` at the blocks a plain call takes: BERT's one
    block pair, GPT-2's single forward over 1024 keys with the backward
    split at 512, and width 32. Every kernel's blocks of q, k, v, out and
    the cotangents are (1, rows, tile * d): whole 128-lane tiles."""
    rng = np.random.RandomState(33)
    q, k, v = (x.astype(dtype) for x in make_qkv(rng, 1, s, h, d, np.float32))

    def flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal, None, None, None, True).astype(jnp.float32)))

    def naive(q, k, v):
        return jnp.sum(jnp.sin(local_attention(
            q, k, v, causal=causal).astype(jnp.float32)))

    calls, _ = flash_calls(jax.value_and_grad(flash, (0, 1, 2)), q, k, v)
    assert {str(e.params["name"]) for e in calls} == set(kernels)
    for eqn in calls:
        blocks = [bm.block_shape for bm in
                  eqn.params["grid_mapping"].block_mappings]
        wide = [tuple(int(getattr(n, "block_size", n)) for n in bs)
                for bs in blocks if len(bs) == 3]
        assert len(wide) >= 4, (eqn.params["name"], blocks)
        assert all(bs[0] == 1 and bs[2] == tile * d for bs in wide), wide
    (lf, gf), (ln, gn) = (jax.value_and_grad(f, (0, 1, 2))(q, k, v)
                          for f in (flash, naive))
    tol = 3e-4 if dtype == np.float32 else 6e-2
    np.testing.assert_allclose(float(lf), float(ln),
                               rtol=1e-5 if dtype == np.float32 else 2e-2)
    for a, b_, name in zip(gf, gn, "qkv"):
        assert a.shape == b_.shape and a.dtype == b_.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32), rtol=tol,
                                   atol=tol, err_msg=name)


@pytest.mark.parametrize("causal,bwd_blocks", [(False, 256), (True, 128)],
                         ids=["fused", "causal_split"])
def test_lane_dense_equals_head_major_on_the_same_tensors(causal, bwd_blocks):
    """One set of mathematics: out, lse, dq, dk and dv of the two layouts
    are equal, a head's lanes against a head's rows, to the rounding of
    float32 sums taken in another order (a lane-dense product runs over
    the head's whole lane tile, its neighbour's lanes zero or dropped)."""
    rng = np.random.RandomState(34)
    b, s, h, d = 2, 256, 4, 64
    q, k, v = make_qkv(rng, b, s, h, d, np.float32)
    do = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    scale = d ** -0.5

    def dense(x):
        return x.reshape(b, s, h * d)

    def major(x):
        return jnp.swapaxes(x, 1, 2)

    out_d, lse_d = fa._flash_fwd(dense(q), dense(k), dense(v), causal, scale,
                                 s, s, True, heads=h)
    out_m, lse_m = fa._flash_fwd(major(q), major(k), major(v), causal, scale,
                                 s, s, True)
    assert out_d.shape == (b, s, h * d) and out_m.shape == (b, h, s, d)
    np.testing.assert_allclose(np.asarray(lse_d), np.asarray(lse_m),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out_d.reshape(b, s, h, d)), np.asarray(major(out_m)),
        rtol=1e-5, atol=1e-6)
    grads_d = fa._flash_bwd(dense(q), dense(k), dense(v), out_d, lse_d,
                            dense(do), causal, scale, bwd_blocks, bwd_blocks,
                            True, heads=h)[:3]
    grads_m = fa._flash_bwd(major(q), major(k), major(v), out_m, lse_m,
                            major(do), causal, scale, bwd_blocks, bwd_blocks,
                            True)[:3]
    for gd, gm, name in zip(grads_d, grads_m, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(gd.reshape(b, s, h, d)), np.asarray(major(gm)),
            rtol=1e-5, atol=2e-6, err_msg=name)


def _squared_loss(**kwargs):
    def loss(q, k, v, *extra):
        named = dict(zip(kwargs.get("extra", ()), extra))
        return (flash_attention(
            q, k, v, kwargs.get("causal", False), None, None, None, True,
            window=kwargs.get("window"), **named)
            .astype(jnp.float32) ** 2).sum()
    return loss


def test_narrow_heads_cross_no_transpose():
    """At [2, 256, 16, 64] neither the forward nor the backward holds a
    ``transpose``: q, k, v, out and the cotangents reach the kernels as
    [b, s, heads*d], a reshape of the arguments. At 1024 causal keys the
    split backward joins, and the one transpose left is delta's
    [b, s, heads] -> [b, heads, s], 4 bytes a row of attention."""
    q = jnp.zeros((2, 256, 16, 64), jnp.bfloat16)
    calls, transposes = flash_calls(
        jax.value_and_grad(_squared_loss(), (0, 1, 2)), q, q, q)
    assert {str(e.params["name"]) for e in calls} == {
        "bps_flash_fwd", "bps_flash_bwd_fused"}
    assert transposes == []
    for eqn in calls:
        assert set(wide_operands(eqn)) == {(2, 256, 16 * 64)}, eqn.params[
            "name"]

    q = jnp.zeros((1, 1024, 4, 64), jnp.bfloat16)
    more, transposes = flash_calls(
        jax.value_and_grad(_squared_loss(causal=True), (0, 1, 2)), q, q, q)
    names = {str(e.params["name"]) for e in calls + more}
    assert names == KERNELS
    assert [t.invars[0].aval.shape for t in transposes] == [(1, 1024, 4)]
    for eqn in more:
        assert set(wide_operands(eqn)) == {(1, 1024, 4 * 64)}


@pytest.mark.parametrize("heads,kv_heads,d,kwargs", [
    (8, 1, 128, dict(causal=True)),
    (8, 1, 128, dict(causal=True, window=100)),
    (8, 8, 128, dict()),
    (4, 4, 64, dict(extra=("rel_table",))),
    (4, 4, 64, dict(causal=True, extra=("rel_table",))),
    (4, 2, 64, dict()),
    (3, 3, 64, dict()),
], ids=["width128_grouped", "width128_grouped_window", "width128",
        "rel_table", "rel_table_causal", "grouped_width64", "three_heads"])
def test_every_other_call_keeps_the_head_major_path(heads, kv_heads, d,
                                                    kwargs):
    """Width 128, a table (T5's encoder and its causal decoder), grouped
    kv heads, a window, a head count no lane tile divides: the kernels'
    operands are [b, h, s, d]
    (q and its kin folded over the group) behind a swapaxes each: three
    in and one out, in the backward two for the shapes, do in and three
    out, as before the lane-dense layout existed."""
    b, s = 2, 256
    q = jnp.zeros((b, s, heads, d), jnp.bfloat16)
    k = jnp.zeros((b, s, kv_heads, d), jnp.bfloat16)
    extra = {"rel_table": jnp.zeros((heads, 32), jnp.float32)}
    operands = tuple(extra[e] for e in kwargs.get("extra", ()))
    calls, transposes = flash_calls(
        jax.value_and_grad(_squared_loss(**kwargs),
                           tuple(range(3 + len(operands)))),
        q, k, k, *operands)
    assert {str(e.params["name"]) for e in calls} <= KERNELS
    assert len(calls) >= 2
    group = heads // kv_heads
    for eqn in calls:
        assert set(wide_operands(eqn)) <= {(b, kv_heads, group * s, d),
                                           (b, kv_heads, s, d)}, (
            eqn.params["name"], wide_operands(eqn))
    swaps = [t for t in transposes
             if tuple(t.params["permutation"]) == (0, 2, 1, 3)]
    assert len(swaps) == 10, len(swaps)


def test_three_heads_of_64_are_right_on_the_head_major_path():
    """Three heads of 64 fill no whole number of lane tiles two at a
    time: the call keeps today's path (above) and its gradients are the
    reference's."""
    rng = np.random.RandomState(35)
    q, k, v = make_qkv(rng, 1, 256, 3, 64, np.float32)
    assert fa._dense_tile(3, 1, 1, 256, 256, 64, True, mats=1) is None

    def flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, True, None, None,
                                               None, True)))

    def naive(q, k, v):
        return jnp.sum(jnp.sin(local_attention(q, k, v, causal=True)))

    (lf, gf), (ln, gn) = (jax.value_and_grad(f, (0, 1, 2))(q, k, v)
                          for f in (flash, naive))
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-5)
    for a, b_, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=3e-4,
                                   atol=3e-4, err_msg=name)


def test_the_ring_hands_the_kernels_head_major_blocks():
    """``parallel/ring.py`` calls ``_flash_fwd`` itself, on [b, h, s, d]
    blocks it has transposed once for the whole ring: width 64 or not,
    it stays head-major."""
    from byteps_tpu.parallel.ring import _flash_blk_fwd
    q = jnp.zeros((2, 16, 256, 64), jnp.bfloat16)
    calls, _ = flash_calls(
        lambda q, k, v: _flash_blk_fwd(q, k, v, None, 0.125, True), q, q, q)
    assert [str(e.params["name"]) for e in calls] == ["bps_flash_fwd"]
    assert set(wide_operands(calls[0])) == {(2, 16, 256, 64)}


@pytest.mark.parametrize("h,d,nq,nk,bq,bk,mats,want", [
    (16, 64, 1, 1, 512, 512, 1, 4),       # BERT s512 forward
    (16, 64, 1, 1, 512, 512, 4, 2),       # its fused backward
    (16, 64, 1, 1, 128, 128, 4, 8),       # BERT s128
    (16, 64, 1, 1, 1024, 1024, 1, 2),     # GPT-2's forward
    (16, 64, 2, 2, 512, 512, 3, 2),       # its split backward: 1 -> 2
    (16, 32, 1, 1, 512, 512, 4, None),    # width 32 needs 4: over the budget
    (16, 32, 1, 1, 256, 256, 4, 8),
    (3, 64, 1, 1, 256, 256, 1, None),
    (12, 96, 1, 1, 128, 128, 1, None),    # a head of 96 lies across two tiles
    (16, 128, 1, 1, 128, 128, 1, None),   # wide enough already
], ids=["bert_fwd", "bert_bwd", "s128_bwd", "gpt2_fwd", "gpt2_split",
        "width32_s512", "width32_s256", "three_heads", "width96",
        "width128"])
def test_dense_tile_fills_whole_lane_tiles(h, d, nq, nk, bq, bk, mats, want):
    """The chip's head tile of a lane-dense call: ``_head_tile``'s where
    that fills whole 128-lane tiles, else the least that does, or none
    (the call then keeps the head-major path): no tile within the VMEM
    count, a head count none divides, a width that divides no lane tile."""
    got = fa._dense_tile(h, nq, nk, bq, bk, d, False, mats)
    assert got == want
    if got is not None:
        assert got * d % 128 == 0 and h % got == 0
        assert fa._tile_vmem(got, mats, bq, bk, d) < fa._HT_VMEM_BUDGET

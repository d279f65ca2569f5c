"""Ring attention: sequence/context parallelism over an ICI mesh axis.

Absent from the reference (SURVEY §5 "Long-context: entirely absent") but
first-class here: long sequences are sharded over the ``seq`` mesh axis;
each device computes blockwise attention for its query shard while K/V
shards rotate around the ring via ``ppermute``, overlapping the next
block's transfer with the current block's compute. Softmax is accumulated
online (flash-attention style running max / normalizer), so the full
[seq, seq] score matrix never materializes.

Two implementations behind one dispatcher:

  - **flash ring** (TPU default): each ring step runs the Pallas flash
    kernels on the local (q, k_blk) pair — scores stay in VMEM — and the
    per-block normalized partials are merged by log-sum-exp. The custom
    backward rotates k/v (and the dk/dv accumulators) around the ring
    again, calling the flash backward kernels with the FINAL lse and
    out: p = exp(s - lse_final) is the exact global softmax probability
    of that block, so each block's (dq, dk, dv) contribution is exact.
  - **pure-JAX ring** (CPU tests, unsupported shapes): same math with
    materialized [*, h, sq, sk] score blocks.

References (public techniques): Ring Attention (Liu et al. 2023),
blockwise online softmax (Milakov & Gimelshein 2018). Math below is the
standard log-sum-exp streaming update.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def _block_attn(q, k, v, bias, scale):
    """One block: scores [*, hq, sq, sk] → (unnormalized out, row max, row
    normalizer; the two statistics [*, hq, sq]). Inputs stay in their
    compute dtype (bf16 on the MXU); accumulation is fp32 via
    preferred_element_type."""
    s = jnp.einsum("...qhd,...khd->...hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                           # [..., h, sq]
    # guard fully-masked rows (all -inf)
    m = jnp.maximum(m, -1e30)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("...hqk,...khd->...qhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False,
                   scale: Optional[float] = None,
                   impl: str = "auto", interpret: bool = False) -> jnp.ndarray:
    """Attention with q/k/v sharded on the sequence axis.

    Args:
      q, k, v: local shards [batch, seq_local, heads, head_dim].
      axis_name: mesh axis holding the sequence shards.
      causal: apply a causal mask consistent with the *global* sequence
        order (shard i holds positions [i*seq_local, (i+1)*seq_local)).
      impl: "auto" (flash ring on TPU when shapes allow) | "flash" |
        "naive" (pure-JAX blocks).
      interpret: run the Pallas kernels in interpret mode (CPU tests).

    Returns the local output shard [batch, seq_local, heads, head_dim].
    """
    if impl not in ("auto", "flash", "naive"):
        raise ValueError(f"impl must be auto|flash|naive, got {impl!r}")
    if impl != "naive":
        from ..ops.flash_attention import supported
        on_tpu = jax.default_backend() == "tpu"
        if impl == "flash" or (on_tpu and supported(q.shape)):
            if scale is None:
                scale = q.shape[-1] ** -0.5
            return _ring_flash(q, k, v, axis_name, causal, scale, interpret)
    return _ring_naive(q, k, v, axis_name, causal, scale)


def _ring_naive(q, k, v, axis_name, causal, scale):
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    if scale is None:
        scale = d ** -0.5

    def make_bias(kv_rank):
        if not causal:
            return None
        q_pos = idx * sq + jnp.arange(sq)[:, None]        # global q positions
        k_pos = kv_rank * sq + jnp.arange(sq)[None, :]    # global k positions
        mask = q_pos >= k_pos
        return jnp.where(mask, 0.0, -jnp.inf)[None, None, :, :]

    # online softmax state
    o = jnp.zeros_like(q, dtype=jnp.float32)
    m = jnp.full((b, h, sq), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((b, h, sq), dtype=jnp.float32)

    def accumulate(step, o, m, l, k_blk, v_blk):
        kv_rank = (idx - step) % sp
        bias = make_bias(kv_rank)
        o_b, m_b, l_b = _block_attn(q, k_blk, v_blk, bias, scale)
        new_m = jnp.maximum(m, m_b)
        alpha = jnp.exp(m - new_m)        # rescale old accumulation
        beta = jnp.exp(m_b - new_m)       # rescale new block
        l_new = l * alpha + l_b * beta
        # alpha/beta are [b, h, sq]; o is [b, sq, h, d]
        a_t = jnp.swapaxes(alpha, 1, 2)[..., None]   # [b, sq, h, 1]
        b_t = jnp.swapaxes(beta, 1, 2)[..., None]
        o_new = o * a_t + o_b * b_t
        return o_new, new_m, l_new

    perm = _ring_perm(sp)

    def body(step, carry):
        o, m, l, k_blk, v_blk = carry
        o, m, l = accumulate(step, o, m, l, k_blk, v_blk)
        # rotate K/V one step around the ring (next-lower neighbor's shard
        # arrives; transfer overlaps the next iteration's compute)
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, m, l, k_next, v_next

    # sp-1 rotations suffice: the last block is consumed outside the loop
    # so no dead ppermute pair rides the critical path
    o, m, l, k_last, v_last = jax.lax.fori_loop(0, sp - 1, body,
                                                (o, m, l, k, v))
    o, m, l = accumulate(sp - 1, o, m, l, k_last, v_last)
    l = jnp.maximum(jnp.swapaxes(l, 1, 2), 1e-30)     # [b, sq, h]
    return (o / l[..., None]).astype(q.dtype)


# ------------------------------------------------------------- flash ring

def _ring_perm(sp):
    return [(i, (i + 1) % sp) for i in range(sp)]


def _blk_cases(causal, idx, kv_rank):
    """0 = hidden (future kv shard), 1 = diagonal, 2 = fully visible."""
    if not causal:
        return None
    return jnp.int32(jnp.sign(idx - kv_rank)) + 1


def _flash_blk_fwd(q_t, k_t, v_t, case, scale, interpret):
    """One ring step's flash forward. q_t/k_t/v_t: [b,h,s,d].
    Returns a normalized fp32 partial out [b,h,s,d] (fp32 so the
    per-step combine doesn't accumulate a bf16 rounding per ring step)
    and lse [b,h,s] fp32. ``case`` None → non-causal visible."""
    from ..ops.flash_attention import _flash_fwd, _pick_block

    b, h, s, d = q_t.shape
    bq = bk = _pick_block(s, 512)

    def visible(_):
        return _flash_fwd(q_t, k_t, v_t, False, scale, bq, bk, interpret,
                          out_dtype=jnp.float32)

    if case is None:
        return visible(None)

    def diagonal(_):
        return _flash_fwd(q_t, k_t, v_t, True, scale, bq, bk, interpret,
                          out_dtype=jnp.float32)

    def hidden(_):
        return (jnp.zeros(q_t.shape, jnp.float32),
                jnp.full((b, h, s), -1e30, jnp.float32))

    return jax.lax.switch(case, [hidden, diagonal, visible], None)


def _combine(o, lse, o_b, lse_b):
    """Merge two normalized partials ([b,h,s,d] fp32, [b,h,s] fp32)."""
    m = jnp.maximum(lse, lse_b)
    w = jnp.exp(lse - m)
    w_b = jnp.exp(lse_b - m)
    new_lse = m + jnp.log(w + w_b)
    return (o * jnp.exp(lse - new_lse)[..., None]
            + o_b * jnp.exp(lse_b - new_lse)[..., None]), new_lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, scale, interpret):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                  interpret)
    return out


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, interpret):
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    q_t = jnp.swapaxes(q, 1, 2)                       # [b,h,sq,d]
    perm = _ring_perm(sp)

    o = jnp.zeros((b, h, sq, d), jnp.float32)
    lse = jnp.full((b, h, sq), -1e30, jnp.float32)

    def accumulate(step, o, lse, k_blk, v_blk):
        kv_rank = (idx - step) % sp
        o_b, lse_b = _flash_blk_fwd(
            q_t, jnp.swapaxes(k_blk, 1, 2), jnp.swapaxes(v_blk, 1, 2),
            _blk_cases(causal, idx, kv_rank), scale, interpret)
        return _combine(o, lse, o_b, lse_b)

    def body(step, carry):
        o, lse, k_blk, v_blk = carry
        o, lse = accumulate(step, o, lse, k_blk, v_blk)
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, lse, k_next, v_next

    o, lse, k_last, v_last = jax.lax.fori_loop(0, sp - 1, body,
                                               (o, lse, k, v))
    o, lse = accumulate(sp - 1, o, lse, k_last, v_last)
    out = jnp.swapaxes(o, 1, 2).astype(q.dtype)       # [b,sq,h,d]
    return out, (q, k, v, out, lse)                   # lse [b,h,sq]


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale, interpret):
    return _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, interpret)


def _ring_flash_vjp_bwd(axis_name, causal, scale, interpret, res, g):
    from ..ops.flash_attention import _flash_bwd, _pick_block

    q, k, v, out, lse = res
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    bq = bk = _pick_block(sq, 512)
    q_t = jnp.swapaxes(q, 1, 2)
    out_t = jnp.swapaxes(out, 1, 2)
    do_t = jnp.swapaxes(g, 1, 2)
    # delta is loop-invariant (depends only on do and the final out):
    # compute it once instead of once per ring step inside _flash_bwd
    delta = jnp.sum(do_t.astype(jnp.float32) * out_t.astype(jnp.float32),
                    axis=-1)                          # [b,h,sq]
    perm = _ring_perm(sp)

    def blk_bwd(k_t, v_t, case):
        # flash bwd with the FINAL lse/out: p = exp(s - lse_final) is the
        # exact global softmax probability of this block, so the per-block
        # (dq, dk, dv) are exact contributions that just sum.
        def visible(_):
            return _flash_bwd(q_t, k_t, v_t, out_t, lse, do_t,
                              False, scale, bq, bk, interpret,
                              delta=delta)[:3]    # no bias on the ring

        if case is None:
            return visible(None)

        def diagonal(_):
            return _flash_bwd(q_t, k_t, v_t, out_t, lse, do_t,
                              True, scale, bq, bk, interpret,
                              delta=delta)[:3]

        def hidden(_):
            return (jnp.zeros_like(q_t), jnp.zeros_like(k_t),
                    jnp.zeros_like(v_t))

        return jax.lax.switch(case, [hidden, diagonal, visible], None)

    def accumulate(step, dq, k_blk, v_blk, dk_blk, dv_blk):
        kv_rank = (idx - step) % sp
        dq_b, dk_b, dv_b = blk_bwd(
            jnp.swapaxes(k_blk, 1, 2), jnp.swapaxes(v_blk, 1, 2),
            _blk_cases(causal, idx, kv_rank))
        return (dq + dq_b.astype(jnp.float32),
                dk_blk + jnp.swapaxes(dk_b, 1, 2).astype(jnp.float32),
                dv_blk + jnp.swapaxes(dv_b, 1, 2).astype(jnp.float32))

    def body(step, carry):
        dq, k_blk, v_blk, dk_blk, dv_blk = carry
        dq, dk_blk, dv_blk = accumulate(step, dq, k_blk, v_blk,
                                        dk_blk, dv_blk)
        # dk/dv accumulators travel WITH their k/v shard around the ring
        k_blk, v_blk, dk_blk, dv_blk = (
            jax.lax.ppermute(x, axis_name, perm)
            for x in (k_blk, v_blk, dk_blk, dv_blk))
        return dq, k_blk, v_blk, dk_blk, dv_blk

    dq = jnp.zeros((b, h, sq, d), jnp.float32)
    dkv0 = jnp.zeros((b, sq, h, d), jnp.float32)
    dq, k_blk, v_blk, dk_blk, dv_blk = jax.lax.fori_loop(
        0, sp - 1, body, (dq, k, v, dkv0, dkv0))
    dq, dk_blk, dv_blk = accumulate(sp - 1, dq, k_blk, v_blk,
                                    dk_blk, dv_blk)
    # sp-1 rotations happened; one more brings each dk/dv shard home
    dk = jax.lax.ppermute(dk_blk, axis_name, perm)
    dv = jax.lax.ppermute(dv_blk, axis_name, perm)
    return (jnp.swapaxes(dq, 1, 2).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)

"""The state-space scan of a Mamba-2 layer in its chunked form (the
"state-space duality" of Dao and Gu, 2024): ``models/mamba2.py``'s mixer.

A head ``h`` carries a state ``H`` in R^{P x N} (P its width, N the
state size) through the positions of a sequence::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T        y_t = H_t C_t + D x_t

with ``A`` (negative) and ``D`` a scalar a head, ``dt_t`` a positive step a
head and position, and ``B_t``, ``C_t`` in R^N shared by the heads of a
GROUP (head h reads group ``h // (heads / groups)``). One step at a time
that is ``s`` dependent updates of a [P, N] state. In chunks of ``Q``
positions it is products: with ``a_t = dt_t A`` and ``L`` its running sum
inside a chunk,

    Y_in  = ((C B^T) o exp(L_i - L_j) o [i >= j]) (dt x)       inside a chunk
    S_c   = sum_t exp(L_end - L_t) dt_t x_t B_t^T              the chunk's state
    H_c   = exp(sum a) H_{c-1} + S_c                           across chunks
    Y_out = exp(L_t) C_t H_{c-1}                               what came before
    y     = Y_in + Y_out + D x

``ssd`` is that, as XLA products under the caller's scope: differentiable
by JAX (no hand-written backward), a pure function (safe under
``jax.checkpoint``), the same code on the TPU and on the CPU. The decays,
their running sums and the carried state are float32; the four products
(``C B^T``, its weighted sum of ``dt x``, a chunk's state, ``C H``) take
operands in ``x``'s dtype and accumulate in float32. The states cross the
chunks by ONE float32 product with the lower-triangular matrix of the
chunks' decays (``exp`` of differences of the running sum of the chunks'
totals), not by a loop. A decay is ``exp`` of a sum of non-positive terms
wherever it is kept; above the diagonal the exponent is set to ``-inf``
BEFORE the ``exp``, so nothing overflows there in either pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 128


def _decay(upto, since, strict=False):
    """``exp(upto_i - since_j)`` where ``i >= j`` (``strict``: ``i > j``)
    along the last two axes, zero elsewhere."""
    diff = upto[..., :, None] - since[..., None, :]
    i = jax.lax.broadcasted_iota(jnp.int32, diff.shape[-2:], 0)
    j = jax.lax.broadcasted_iota(jnp.int32, diff.shape[-2:], 1)
    return jnp.exp(jnp.where(i > j if strict else i >= j, diff, -jnp.inf))


def ssd(x, dt, a, b, c, d, chunk: int = CHUNK):
    """``y`` [batch, s, heads, p] of the recurrence above.

    ``x`` [batch, s, heads, p]; ``dt`` [batch, s, heads] float32, the
    steps after their softplus; ``a`` [heads] float32, negative; ``b``,
    ``c`` [batch, s, groups, n]; ``d`` [heads]. ``s`` is any whole number
    of chunks of ``chunk`` positions; the state before the first position
    is zero."""
    bsz, s, heads, p = x.shape
    groups, n = b.shape[2:]
    if s % chunk or heads % groups:
        raise ValueError(f"{s} positions in chunks of {chunk}, {heads} "
                         f"heads over {groups} groups")
    nc, per = s // chunk, heads // groups
    dtype, f32 = x.dtype, jnp.float32
    x32 = x.astype(f32)
    dt = dt.astype(f32)
    # running sums of a_t = dt_t A inside each chunk, [batch, nc, heads, q]
    run = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, nc, chunk, heads),
                     axis=2).transpose(0, 1, 3, 2)
    total = run[..., -1]                                # [batch, nc, heads]
    by_group = (bsz, nc, groups, per, chunk)
    run_g = run.reshape(by_group)

    def chunks(t, *tail):       # [batch, s, ...] -> [batch, nc, q, *tail]
        return t.reshape(bsz, nc, chunk, *tail)

    bc, cc = chunks(b, groups, n), chunks(c, groups, n)
    xd = chunks(x32 * dt[..., None], groups, per, p)    # dt x, float32
    # inside a chunk
    cb = jnp.einsum("zcign,zcjgn->zcgij", cc, bc, preferred_element_type=f32)
    mix = (cb[:, :, :, None] * _decay(run_g, run_g)).astype(dtype)
    y = jnp.einsum("zcgrij,zcjgrp->zcigrp", mix, xd.astype(dtype),
                   preferred_element_type=f32)
    # each chunk's own state, [batch, nc, groups, per, p, n], float32
    to_end = jnp.exp(run_g[..., -1:] - run_g)           # [.., per, q]
    state = jnp.einsum(
        "zcjgrp,zcjgn->zcgrpn",
        (xd * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype), bc,
        preferred_element_type=f32)
    # across chunks: the state BEFORE chunk c is the sum over the chunks
    # z < c of exp(the totals of the chunks between them) S_z
    upto = jnp.cumsum(total, axis=1).transpose(0, 2, 1)  # [batch, heads, nc]
    since = jnp.concatenate(
        [jnp.zeros_like(upto[..., :1]), upto[..., :-1]], -1)
    # row c, column z: exp(upto[c - 1] - upto[z]) for z <= c - 1
    carry = _decay(since, upto, strict=True)
    before = jnp.einsum(
        "zgrcw,zwgrpn->zcgrpn", carry.reshape(bsz, groups, per, nc, nc),
        state, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=f32)
    y = y + jnp.einsum(
        "zcign,zcgrpn->zcigrp", cc, before.astype(dtype),
        preferred_element_type=f32) * jnp.moveaxis(
            jnp.exp(run_g), -1, 2)[..., None]
    y = y.reshape(bsz, s, heads, p) + d.astype(f32)[:, None] * x32
    return y.astype(dtype)


def ssd_steps(x, dt, a, b, c, d):
    """The same recurrence ONE position at a time, float32: what the
    tests hold ``ssd`` against."""
    bsz, s, heads, p = x.shape
    groups, n = b.shape[2:]
    per = heads // groups
    f32 = jnp.float32

    def step(state, at):
        xt, dtt, bt, ct = at            # [batch, heads, p], [batch, heads]
        bt = jnp.repeat(bt, per, axis=1)        # [batch, heads, n]
        ct = jnp.repeat(ct, per, axis=1)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, (state * ct[:, :, None, :]).sum(-1) + d[:, None] * xt

    first = jnp.zeros((bsz, heads, p, n), f32)
    _, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)

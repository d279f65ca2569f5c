"""The two elementwise stages on either side of a Mamba-2 mixer's scan
(``models/mamba2.py``) as Pallas kernels over the position-major
``[batch, s, channels]`` arrays, where the projections and the scan
(``ops/ssd.py``) leave them: operands and results cross HBM once a pass
and direction, and no float32 copy of an activation is made.

``conv_silu_kernels``: the depthwise causal convolution (``taps`` taps,
bias) and SiLU, ``bps_ssm_conv_fwd`` / ``bps_ssm_conv_bwd``.
``gated_norm_kernels``: ``y * silu(z)`` and the RMSNorm over each group of
channels, ``bps_ssm_norm_fwd`` / ``bps_ssm_norm_bwd``; with ``gate_first``
false the norm of ``y`` alone comes FIRST and the gate multiplies after it
(a Gated DeltaNet's, ``models/gated_delta_net.py``: a group is then a head
of 128 lanes), the same two kernels with the two lines in the other
order. Each a
``jax.custom_vjp`` over ONE ``pl.pallas_call`` a direction; what a
backward needs of the forward (the pre-activation, SiLU's derivative, a
row's statistic) it remakes in VMEM from the operands, nothing is saved.
``models/mamba2.py`` chooses them by what it sees in its operands
(``conv_supported``, ``norm_supported``, a TPU backend) and keeps the XLA
form (``causal_conv``, ``group_rmsnorm``) elsewhere; the tests hold the
kernels against that form in Pallas' interpreter
(``tests/test_mamba2_kernels.py``).

A grid step is one batch row, one run of whole lane tiles (at most
``LANES_MOST`` lanes of the convolution's channels; one group of the
norm's) and one block of positions (``block_rows``: the largest of
``ROWS`` that divides the sequence), the positions last and in order.
Inside it the block is worked a strip of positions at a time
(``CONV_STRIP``, ``NORM_STRIP``), so that a strip's float32 values stay
in registers. The sizes are the fastest of those tried on a v5e (PERF.md
section 6, PR 41): the kernels are bound by the vector unit, not by HBM.

The convolution's halo: position ``t`` reads ``t - (taps - 1) .. t``. The
positions before a block come as a second small block of the SAME array
(the ``HALO`` rows before it, of the same sequence: the index never
leaves the batch row), zeros before position 0 of each sequence by a
select, never by a product; inside a block a strip carries its last rows
to the next. A strip's shifted copies are sublane rotations of the strip
with those rows in front. The backward needs the cotangent of the
pre-activation at the positions AFTER a strip: it walks the strips in
order and writes ``d x`` of a strip when the next one's cotangent is
there; after the block's last strip that is remade from a small block of
the rows after it (zero past the sequence's end).

float32 inside: the convolution's sums and the weights' gradients, the
gate, the group's mean of squares and everything of the norm's backward;
operands and results in the arrays' dtype (docs/state-space.md). The
small gradients (``d conv_w``, ``d conv_b``, ``d gated_norm``) leave a
kernel as float32 partial sums a batch row and sublane, gathered in the
output block that stays in VMEM while the positions go by; XLA adds the
few rows up under the caller's scope.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUB = 8             # a float32 tile's sublanes: what a strip carries over
HALO = 16           # rows of a halo block: a whole bfloat16 tile
ROWS = (1024, 512, 256)     # positions a grid step: the first that divides s
CONV_STRIP = 32     # positions worked at once inside it: [32, 512] float32
NORM_STRIP = 64     # is 16 registers, and the convolution holds more arrays
LANES_MOST = 512    # lanes a grid step

# grid (batch, lanes, positions): the small gradients gather over the last
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_F32 = jnp.float32


def _lane_block(channels: int) -> int:
    """The widest run of whole lane tiles, at most ``LANES_MOST`` lanes,
    that divides ``channels``."""
    tiles = channels // LANES
    return LANES * max(t for t in range(1, LANES_MOST // LANES + 1)
                       if tiles % t == 0)


def block_rows(s: int) -> int:
    """The positions a grid step takes of a sequence of ``s``: the largest
    of ``ROWS`` that divides it, 0 if none does."""
    return next((r for r in ROWS if s % r == 0), 0)


def conv_supported(x_shape, w_shape) -> bool:
    """Shapes the convolution's kernels take: channels in whole lane
    tiles, positions in whole blocks, the taps inside a strip's carry."""
    return (len(x_shape) == 3 and x_shape[2] % LANES == 0
            and block_rows(x_shape[1]) > 0 and 1 <= w_shape[0] - 1 <= SUB)


def norm_supported(y_shape, groups: int) -> bool:
    """Shapes the gated norm's kernels take: a group's channels whole
    lane tiles, few enough for a block, positions in whole blocks."""
    width = y_shape[2] // groups
    return (len(y_shape) == 3 and y_shape[2] % groups == 0
            and width % LANES == 0 and width <= LANES_MOST
            and block_rows(y_shape[1]) > 0)


def _strips(rows, strip, body, carry, first=0):
    """``body(start, carry) -> carry`` over the strips of a block, from
    the ``first`` on."""
    def step(c, carry):
        return body(pl.multiple_of(c * strip, strip), carry)
    return jax.lax.fori_loop(first, rows // strip, step, carry)


def _fold(v):
    """[strip, lanes] -> [SUB, lanes]: the rows summed by sublane, adds of
    whole tiles alone."""
    out = v[:SUB]
    for r in range(SUB, v.shape[0], SUB):
        out = out + v[r:r + SUB]
    return out


def _sigmoid(v):
    return 1.0 / (1.0 + jnp.exp(-v))


# ------------------------------------------------------- the convolution
def _front(before_ref):
    """The ``SUB`` rows before a block, float32: zeros at a sequence's
    first block, whatever the clamped halo block holds there."""
    return jnp.where(pl.program_id(2) == 0, 0.0,
                     before_ref[0].astype(_F32)[HALO - SUB:])


def _taps_of(front, x32, w, bias):
    """The pre-activation of a strip and its shifted copies: ``front``
    [SUB, lanes] the rows before it, ``x32`` [strip, lanes], float32.
    ``shifted[k][t] = x[t - (taps - 1) + k]``."""
    taps = w.shape[0]
    ext = jnp.concatenate([front, x32], 0)
    shifted = [pltpu.roll(ext, taps - 1 - k, 0)[SUB:]
               for k in range(taps - 1)] + [x32]
    pre = bias
    for k in range(taps):
        pre = pre + w[k:k + 1] * shifted[k]
    return pre, shifted


def _conv_fwd_kernel(x_ref, before_ref, w_ref, b_ref, o_ref, *, strip):
    rows = x_ref.shape[1]
    w, bias = w_ref[...], b_ref[...]

    def body(at, front):
        x32 = x_ref[0, pl.ds(at, strip), :].astype(_F32)
        pre, _ = _taps_of(front, x32, w, bias)
        o_ref[0, pl.ds(at, strip), :] = (pre * _sigmoid(pre)).astype(
            o_ref.dtype)
        return x32[strip - SUB:]

    _strips(rows, strip, body, _front(before_ref))


def _conv_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     w_ref, b_ref, dx_ref, part_ref, *, strip):
    rows, taps = x_ref.shape[1], w_ref.shape[0]
    w, bias = w_ref[...], b_ref[...]

    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        part_ref[...] = jnp.zeros_like(part_ref)

    def dpre_of(pre, dy32):
        sig = _sigmoid(pre)
        return dy32 * (sig * (1.0 + pre * (1.0 - sig)))

    def write_dx(at, dpre, head):
        """``d x`` of the strip at ``at`` from its own ``dpre`` and the
        first rows of the next strip's: ``dx[t] = sum_k w[k]
        dpre[t + (taps - 1) - k]``."""
        ext = jnp.concatenate([dpre, head], 0)
        dx = w[taps - 1:taps] * dpre
        for k in range(taps - 1):
            up = taps - 1 - k
            dx = dx + w[k:k + 1] * pltpu.roll(
                ext, strip + SUB - up, 0)[:strip]
        dx_ref[0, pl.ds(at, strip), :] = dx.astype(dx_ref.dtype)

    def one(at, front):
        """A strip's ``dpre`` and the rows it hands on; its share of the
        weights' and the bias's gradients goes to ``part_ref``."""
        x32 = x_ref[0, pl.ds(at, strip), :].astype(_F32)
        pre, shifted = _taps_of(front, x32, w, bias)
        dpre = dpre_of(pre, dy_ref[0, pl.ds(at, strip), :].astype(_F32))
        for k in range(taps):
            part_ref[0, k * SUB:(k + 1) * SUB, :] += _fold(dpre * shifted[k])
        part_ref[0, taps * SUB:(taps + 1) * SUB, :] += _fold(dpre)
        return dpre, x32[strip - SUB:]

    def body(at, carry):
        held, front = carry
        dpre, front = one(at, front)
        write_dx(pl.multiple_of(at - strip, strip), held, dpre[:SUB])
        return dpre, front

    dpre, front = _strips(rows, strip, body, one(0, _front(before_ref)),
                          first=1)
    # the rows after the block: their dpre alone, nothing of theirs summed
    pre, _ = _taps_of(front, after_ref[0].astype(_F32)[:SUB], w, bias)
    head = jnp.where(pl.program_id(2) == pl.num_programs(2) - 1, 0.0,
                     dpre_of(pre, dy_after_ref[0].astype(_F32)[:SUB]))
    write_dx(rows - strip, dpre, head)


def _conv_specs(rows, lanes, halos):
    """A grid step's blocks: the positions' block, the ``HALO`` rows
    before and after it (of the same sequence, whatever they hold at a
    sequence's ends: the kernels select zeros there), a row of lanes."""
    per = rows // HALO

    def before(z, l, i):
        return z, jnp.maximum(i * per - 1, 0), l

    def after(z, l, i):
        return z, jnp.minimum((i + 1) * per, halos - 1), l

    return dict(
        block=pl.BlockSpec((1, rows, lanes), lambda z, l, i: (z, i, l)),
        before=pl.BlockSpec((1, HALO, lanes), before),
        after=pl.BlockSpec((1, HALO, lanes), after))


def _lane_row(height, lanes):
    return pl.BlockSpec((height, lanes), lambda z, l, i: (0, l))


def _part(height, lanes):
    return pl.BlockSpec((1, height, lanes), lambda z, l, i: (z, 0, l))


# each ONE jitted function, like the kernels of ops/ssd.py: the layers of a
# model share shapes, so each is traced and lowered once a step
@functools.partial(jax.jit, static_argnames=("rows", "strip", "interpret"))
def _conv_fwd_call(x, w, bias, rows, strip, interpret):
    bsz, s, channels = x.shape
    rows = rows or block_rows(s)
    lanes = _lane_block(channels)
    sp = _conv_specs(rows, lanes, s // HALO)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, strip=strip),
        grid=(bsz, channels // lanes, s // rows),
        in_specs=[sp["block"], sp["before"], _lane_row(w.shape[0], lanes),
                  _lane_row(1, lanes)],
        out_specs=sp["block"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_SEMANTICS, interpret=interpret,
        name="bps_ssm_conv_fwd",
    )(x, x, w, bias[None])


@functools.partial(jax.jit, static_argnames=("rows", "strip", "interpret"))
def _conv_bwd_call(x, w, bias, dy, rows, strip, interpret):
    """``d x`` and the float32 gradients of ``w`` and ``bias``."""
    bsz, s, channels = x.shape
    rows = rows or block_rows(s)
    taps, lanes = w.shape[0], _lane_block(channels)
    sp = _conv_specs(rows, lanes, s // HALO)
    height = (taps + 1) * SUB
    dx, part = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, strip=strip),
        grid=(bsz, channels // lanes, s // rows),
        in_specs=[sp["block"], sp["before"], sp["after"], sp["block"],
                  sp["after"], _lane_row(taps, lanes), _lane_row(1, lanes)],
        out_specs=[sp["block"], _part(height, lanes)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, height, channels), _F32)],
        compiler_params=_SEMANTICS, interpret=interpret,
        name="bps_ssm_conv_bwd",
    )(x, x, x, dy, dy, w, bias[None])
    part = part.reshape(bsz, taps + 1, SUB, channels).sum((0, 2))
    return dx, part[:taps], part[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def conv_silu_kernels(x, w, bias, rows=0, strip=CONV_STRIP,
                      interpret=False):
    """``silu(bias + sum_k w[k] x_{t - (taps - 1) + k})`` over ``x``
    [batch, s, channels], zeros before each sequence's first position, in
    ``x``'s dtype; ``w`` [taps, channels] and ``bias`` [channels] float32.
    By the kernels whatever the platform (``interpret``: in Pallas'
    interpreter, for the tests); the shapes are ``conv_supported``'s,
    ``rows`` 0 the block ``block_rows`` gives."""
    return _conv_fwd_call(x, w, bias, rows, strip, interpret)


def _conv_fwd(x, w, bias, rows, strip, interpret):
    return _conv_fwd_call(x, w, bias, rows, strip, interpret), (x, w, bias)


def _conv_bwd(rows, strip, interpret, res, dy):
    return _conv_bwd_call(*res, dy, rows, strip, interpret)


conv_silu_kernels.defvjp(_conv_fwd, _conv_bwd)


# -------------------------------------------------------- the gated norm
def _gated(y_ref, z_ref, at, strip, eps, gate_first=True):
    """A strip's float32 ``y``, ``z``, ``sigmoid(z)``, the value the norm
    takes (``y z sigmoid(z)``, or ``y`` alone where the gate comes after)
    and ``rsqrt`` of its mean of squares over the block's lanes (one
    group) plus ``eps``."""
    y32 = y_ref[0, pl.ds(at, strip), :].astype(_F32)
    z32 = z_ref[0, pl.ds(at, strip), :].astype(_F32)
    sig = _sigmoid(z32)
    g = y32 * (z32 * sig) if gate_first else y32
    inv = jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return y32, z32, sig, g, inv


def _norm_fwd_kernel(y_ref, z_ref, scale_ref, o_ref, *, strip, eps,
                     gate_first):
    scale = scale_ref[...]

    def body(at, carry):
        _, z32, sig, g, inv = _gated(y_ref, z_ref, at, strip, eps,
                                     gate_first)
        out = g * inv * scale
        if not gate_first:
            out = out * (z32 * sig)
        o_ref[0, pl.ds(at, strip), :] = out.astype(o_ref.dtype)
        return carry

    _strips(y_ref.shape[1], strip, body, 0)


def _norm_bwd_kernel(y_ref, z_ref, scale_ref, do_ref, dy_ref, dz_ref,
                     part_ref, *, strip, eps, gate_first):
    scale = scale_ref[...]

    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        part_ref[...] = jnp.zeros_like(part_ref)

    def body(at, carry):
        y32, z32, sig, g, inv = _gated(y_ref, z_ref, at, strip, eps,
                                       gate_first)
        do32 = do_ref[0, pl.ds(at, strip), :].astype(_F32)
        normed = g * inv
        if not gate_first:      # out = normed * scale * silu(z)
            dz_ref[0, pl.ds(at, strip), :] = (
                do32 * normed * scale
                * (sig * (1.0 + z32 * (1.0 - sig)))).astype(dz_ref.dtype)
            do32 = do32 * (z32 * sig)
        part_ref[0] += _fold(do32 * normed)
        dn = do32 * scale
        dg = inv * (dn - normed * jnp.mean(dn * normed, -1, keepdims=True))
        if not gate_first:
            dy_ref[0, pl.ds(at, strip), :] = dg.astype(dy_ref.dtype)
            return carry
        dy_ref[0, pl.ds(at, strip), :] = (dg * (z32 * sig)).astype(
            dy_ref.dtype)
        dz_ref[0, pl.ds(at, strip), :] = (
            dg * y32 * (sig * (1.0 + z32 * (1.0 - sig)))).astype(dz_ref.dtype)
        return carry

    _strips(y_ref.shape[1], strip, body, 0)


def _norm_specs(y, groups, rows):
    bsz, s, channels = y.shape
    rows, lanes = rows or block_rows(s), channels // groups
    return ((bsz, groups, s // rows),
            pl.BlockSpec((1, rows, lanes), lambda z, l, i: (z, i, l)),
            _lane_row(1, lanes), lanes)


@functools.partial(jax.jit, static_argnames=("groups", "eps", "rows", "strip",
                                              "interpret", "gate_first"))
def _norm_fwd_call(y, z, scale, groups, eps, rows, strip, interpret,
                   gate_first=True):
    grid, block, lane_row, _ = _norm_specs(y, groups, rows)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, strip=strip, eps=eps,
                          gate_first=gate_first),
        grid=grid, in_specs=[block, block, lane_row], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_SEMANTICS, interpret=interpret,
        name="bps_ssm_norm_fwd",
    )(y, z, scale[None])


@functools.partial(jax.jit, static_argnames=("groups", "eps", "rows", "strip",
                                              "interpret", "gate_first"))
def _norm_bwd_call(y, z, scale, do, groups, eps, rows, strip, interpret,
                   gate_first=True):
    """``d y``, ``d z`` and the float32 gradient of ``scale``."""
    grid, block, lane_row, lanes = _norm_specs(y, groups, rows)
    dy, dz, part = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, strip=strip, eps=eps,
                          gate_first=gate_first),
        grid=grid, in_specs=[block, block, lane_row, block],
        out_specs=[block, block, _part(SUB, lanes)],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((y.shape[0], SUB, y.shape[2]), _F32)],
        compiler_params=_SEMANTICS, interpret=interpret,
        name="bps_ssm_norm_bwd",
    )(y, z, scale[None], do)
    return dy, dz, part.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def gated_norm_kernels(y, z, scale, groups, eps, rows=0, strip=NORM_STRIP,
                       interpret=False, gate_first=True):
    """``rmsnorm_group(y * silu(z)) * scale`` over ``y``, ``z`` [batch, s,
    channels] in ``groups`` runs of channels, in ``y``'s dtype; ``scale``
    [channels] float32; with ``gate_first`` false ``rmsnorm_group(y) *
    scale * silu(z)``. By the kernels whatever the platform; the shapes
    are ``norm_supported``'s."""
    return _norm_fwd_call(y, z, scale, groups, eps, rows, strip, interpret,
                          gate_first)


def _norm_fwd(y, z, scale, groups, eps, rows, strip, interpret, gate_first):
    return (_norm_fwd_call(y, z, scale, groups, eps, rows, strip, interpret,
                           gate_first), (y, z, scale))


def _norm_bwd(groups, eps, rows, strip, interpret, gate_first, res, do):
    return _norm_bwd_call(*res, do, groups, eps, rows, strip, interpret,
                          gate_first)


gated_norm_kernels.defvjp(_norm_fwd, _norm_bwd)

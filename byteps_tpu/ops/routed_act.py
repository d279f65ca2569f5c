"""The experts' function of a routed feed-forward layer as Pallas TPU
kernels (``models/moe.py::routed_ffn``): what stands between the two
grouped products, and its backward, over the row tiles that hold rows.

The buffer of rows is sized for the worst routing and only its first
``num_tiles`` row tiles hold rows (``ops/grouped_matmul.py``). An XLA
fusion cannot know that and walks the whole buffer, forward, in the
recompute and backward. These two skip a row tile past ``num_tiles`` as
``bps_gmm`` does: the step's body does not run, and its index maps stay
on the last tile that ran, so it moves nothing either.

  - ``bps_moe_act_fwd``  a = act(h)                  [rows, m]
  - ``bps_moe_act_bwd``  d h = act'(h) * d a         [rows, h's width]

``act`` is ``gated_silu`` over a fused gate|up ``h`` [rows, 2 m]
(``silu(h[:, :m]) * h[:, m:]``; the backward writes ``d h`` WHOLE, both
halves of a row in one block, so no half-width cotangent is padded and
added) or ``relu2`` over a plain ``h`` [rows, m]. A grid step is a row
tile at its full width; inside it the rows go by strips and the lanes by
chunks, in float32, rounded once to the operands' dtype on the way out.
Rows of tiles that did not run hold whatever the buffer held: callers
read only the rows they routed.

Which widths run the kernels (``supported``): ``m`` in whole lane tiles
or ending in a half one (1856), as the grouped kernels take an expert's
width; under ``gated_silu`` in whole ones, so that the halves split on a
lane tile's border. ``routed_act`` is the differentiable entry: the
kernels on the TPU, the XLA function over the whole buffer elsewhere
(CPU tests), like ``grouped_matmul``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.setup_record import note_choice
from .grouped_matmul import HALF_LANES
from .mamba2_kernels import _sigmoid

_LANES = 128
_STRIP = 32         # rows worked at once inside a tile: [32, 256] float32
_CHUNK = 256        # lanes: 8 registers an array, and the backward holds six
_F32 = jnp.float32

# a step past the rows revisits the last tile's blocks: never "parallel"
_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def gated_silu(h):
    """``silu(gate) * up`` of a fused [.., 2 m] gate|up projection."""
    m = h.shape[-1] // 2
    return jax.nn.silu(h[..., :m]) * h[..., m:]


def relu2(h):
    """``relu(up)^2`` of a plain [.., m] up projection (nemotron_h)."""
    return jnp.square(jax.nn.relu(h))


# name -> (the XLA function, columns of ``h`` a column of the result)
FORMS = {"gated_silu": (gated_silu, 2), "relu2": (relu2, 1)}


def _over_tile(num_ref, shape, piece):
    """``piece(rows, first lane, lanes)`` over the strips of rows and the
    chunks of lanes of a [tile, m] block, where the tile holds rows."""
    tile, m = shape

    @pl.when(pl.program_id(0) < num_ref[0])
    def _tile():
        def strip(s, carry):
            rows = pl.ds(pl.multiple_of(s * _STRIP, _STRIP), _STRIP)
            for c in range(0, m, _CHUNK):
                piece(rows, c, min(_CHUNK, m - c))
            return carry

        jax.lax.fori_loop(0, tile // _STRIP, strip, 0)


def _fwd_kernel(num_ref, h_ref, a_ref, *, act):
    m = a_ref.shape[1]

    def piece(rows, c, w):
        x = h_ref[rows, c:c + w].astype(_F32)
        if act == "gated_silu":
            a = x * _sigmoid(x) * h_ref[rows, m + c:m + c + w].astype(_F32)
        else:
            a = jnp.square(jnp.maximum(x, 0.0))
        a_ref[rows, c:c + w] = a.astype(a_ref.dtype)

    _over_tile(num_ref, a_ref.shape, piece)


def _bwd_kernel(num_ref, h_ref, da_ref, dh_ref, *, act):
    m = da_ref.shape[1]

    def piece(rows, c, w):
        x = h_ref[rows, c:c + w].astype(_F32)
        da = da_ref[rows, c:c + w].astype(_F32)
        if act == "gated_silu":
            up = h_ref[rows, m + c:m + c + w].astype(_F32)
            sig = _sigmoid(x)
            dh_ref[rows, c:c + w] = (
                da * up * (sig * (1.0 + x * (1.0 - sig)))).astype(dh_ref.dtype)
            dh_ref[rows, m + c:m + c + w] = (da * (x * sig)).astype(
                dh_ref.dtype)
        else:
            dh_ref[rows, c:c + w] = (da * (2.0 * jnp.maximum(x, 0.0))).astype(
                dh_ref.dtype)

    _over_tile(num_ref, da_ref.shape, piece)


# jitted, like the grouped products: one traced and lowered function
# serves every call of a shape (forward, recompute, each layer)
@functools.partial(jax.jit, static_argnames=("tile", "act", "interpret"))
def _act(h, da, num_tiles, tile, act, interpret):
    """``act(h)``, or with ``da`` the cotangent of ``h``."""
    rows, width = h.shape
    m = width // FORMS[act][1]

    def a_tile(cols):   # a step past the rows stays on the last tile
        return pl.BlockSpec(
            (tile, cols), lambda t, num: (jnp.minimum(t, num[0] - 1), 0))

    forward = da is None
    return pl.pallas_call(
        functools.partial(_fwd_kernel if forward else _bwd_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // tile,),
            in_specs=[a_tile(width)] + ([] if forward else [a_tile(m)]),
            out_specs=a_tile(m if forward else width)),
        out_shape=jax.ShapeDtypeStruct((rows, m if forward else width),
                                       h.dtype),
        # d h takes d a's place where they are of one shape: the fusion
        # this stands for wrote in place, and a buffer is 0.4 GB
        input_output_aliases={2: 0} if not forward and m == width else {},
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="bps_moe_act_fwd" if forward else "bps_moe_act_bwd",
    )(num_tiles, *((h,) if forward else (h, da)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _act_vjp(h, num_tiles, tile, act, interpret):
    return _act(h, None, num_tiles, tile, act, interpret)


def _act_vjp_fwd(h, num_tiles, tile, act, interpret):
    return _act(h, None, num_tiles, tile, act, interpret), (h, num_tiles)


def _act_vjp_bwd(tile, act, interpret, res, da):
    h, num_tiles = res
    return _act(h, da, num_tiles, tile, act, interpret), None


_act_vjp.defvjp(_act_vjp_fwd, _act_vjp_bwd)


def supported(h_shape, tile: int, act: str) -> bool:
    """Shapes the kernels take: rows in whole row tiles of a multiple of
    128, the result's width in whole lane tiles or ending in a half one,
    and in whole ones where a row of ``h`` holds two halves."""
    rows, width = h_shape
    per = FORMS[act][1]
    m = width // per
    return (tile % _LANES == 0 and rows % tile == 0 and width == per * m
            and m % (HALF_LANES if per == 1 else _LANES) == 0)


def routed_act(h, num_tiles, tile: int, act: str, impl: str = "auto"):
    """``FORMS[act]`` of ``h`` [rows, m or 2 m] for the rows of the first
    ``num_tiles`` ([1] int32) row tiles of ``tile`` rows; with the kernels
    what lies behind them is neither read nor written, and the backward
    is one kernel that writes ``d h`` whole.

    impl: ``grouped_matmul``'s. "auto" (the kernels on the TPU), "gmm",
    "gmm_interpret" (the kernels in Pallas' interpreter: tests): where
    ``supported``, else, and under "ragged", the XLA function over the
    whole buffer."""
    if impl not in ("auto", "gmm", "gmm_interpret", "ragged"):
        raise ValueError(f"routed_act impl {impl!r}")
    asked = impl
    if impl == "auto":
        impl = "gmm" if jax.default_backend() == "tpu" else "ragged"
    if not supported(h.shape, tile, act):
        impl = "ragged"
    note_choice("routed_act", "xla" if impl == "ragged" else "kernels",
                (tuple(h.shape), tile, act),
                "XLA's fusions over the whole buffer: the kernels need the "
                "experts' width in whole or half lane tiles, whole ones "
                "where gate and up share a row, and rows in whole row tiles "
                "of a multiple of 128", asked=asked)
    if impl == "ragged":
        return FORMS[act][0](h)
    return _act_vjp(h, num_tiles, tile, act, impl == "gmm_interpret")

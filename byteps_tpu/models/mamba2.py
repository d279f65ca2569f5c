"""The Mamba-2 mixer of a ``nemotron_h`` decoder (``models/decoder.py``,
kind ``ssm``): what stands in a layer where attention would.

``in_proj`` [hidden, inner + (inner + 2 groups n) + heads] gives ``z``
(the gate), ``xBC`` and a head's ``dt`` from the normed input; ``xBC``
passes a depthwise causal convolution of ``conv_kernel`` taps with bias,
then SiLU, and splits into ``x`` [s, heads, p], ``B`` and ``C``
[s, groups, n]; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
recurrence runs in chunks (``ops/ssd.py``); ``y * silu(z)`` is RMS-normed
in ``groups`` groups of channels and ``out_proj`` [inner, hidden] ends
it. No biases but the convolution's. ``inner = heads * head_dim`` (the
published code takes it from there, not from ``expand``).

docs/state-space.md has the equations and what is float32: ``dt``, ``A``,
the decays, their running sums, the carried state and the group norm;
the projections, the convolution's operands and the scan's products are
in the compute dtype.

The two elementwise stages beside the scan are entries that choose as
``ops.ssd.ssd`` does, by the platform and the shapes alone:
``conv_silu`` and ``gated_norm`` take the kernels of
``ops/mamba2_kernels.py`` on the TPU where the shapes allow and the XLA
form (``causal_conv``, ``group_rmsnorm``) elsewhere.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..common.setup_record import note_choice
from ..ops import mamba2_kernels as K
from ..ops.ssd import CHUNK, ssd_packed

# the seeded dt_bias: the inverse softplus of a step drawn log-uniform in
# [DT_MIN, DT_MAX] and floored (the published time_step_min / _max / _floor)
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """A Mamba-2 mixer's sizes."""
    heads: int                    # mamba_num_heads
    head_dim: int                 # mamba_head_dim
    groups: int                   # n_groups: heads share B and C a group
    state: int                    # ssm_state_size
    conv_kernel: int = 4
    chunk: int = CHUNK

    def __post_init__(self):
        if self.heads % self.groups:
            raise ValueError(f"{self.heads} heads over {self.groups} groups")

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.groups * self.state


def init_mixer(key, hidden: int, cfg: SSMConfig, std: float = 0.02):
    """A mixer's leaves, float32: matrices N(0, ``std``); the
    convolution as ``torch.nn.Conv1d`` starts it (uniform within
    1 / sqrt(taps), weight and bias); ``dt_bias`` the inverse softplus of
    a step drawn log-uniform in [DT_MIN, DT_MAX] and floored; ``A_log`` the
    log of uniform [1, 16]; ``D`` one; unit norm scale."""
    k = jax.random.split(key, 6)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(k[3], (cfg.heads,), jnp.float32)
        * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)), DT_FLOOR)
    return {
        "in_proj": jax.random.normal(
            k[0], (hidden, cfg.inner + cfg.conv_dim + cfg.heads),
            jnp.float32) * std,
        "conv_w": jax.random.uniform(k[1], (cfg.conv_kernel, cfg.conv_dim),
                                     jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(k[2], (cfg.conv_dim,), jnp.float32,
                                     -bound, bound),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(k[4], (cfg.heads,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((cfg.heads,), jnp.float32),
        "gated_norm": jnp.ones((cfg.inner,), jnp.float32),
        "out_proj": jax.random.normal(k[5], (cfg.inner, hidden),
                                      jnp.float32) * std,
    }


def causal_conv(x, w, bias=None):
    """Depthwise: ``out_t = bias + sum_k w[k] x_{t - (taps - 1) + k}`` over
    [b, s, channels], zeros before the first position; float32 sums.
    ``bias`` None: a convolution without one."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = 0.0 if bias is None else bias.astype(jnp.float32)
    for k in range(taps):
        out = out + w[k].astype(jnp.float32) * padded[:, k:k + s]
    return out


def group_rmsnorm(x, scale, groups: int, eps: float):
    """RMSNorm over each of ``groups`` runs of channels, in float32."""
    g = x.astype(jnp.float32).reshape(x.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(x.shape) * scale


def conv_silu(x, w, bias=None):
    """``silu(causal_conv(x, w, bias))`` in ``x``'s dtype: on the TPU,
    for the shapes ``ops.mamba2_kernels.conv_supported`` takes, the
    kernels ``bps_ssm_conv_fwd`` / ``_bwd``; elsewhere the XLA form. A
    convolution without a bias (``bias`` None) hands the kernels a row of
    zeros, a constant whose gradient nobody asks for."""
    kernels = (jax.default_backend() == "tpu"
               and K.conv_supported(x.shape, w.shape))
    note_choice("ssm_conv", "kernels" if kernels else "xla",
                (tuple(x.shape), tuple(w.shape)),
                "XLA's fusions over float32 copies: the kernels need "
                f"channels in whole lane tiles, positions in blocks of "
                f"{K.ROWS[-1]} and 2 to {K.SUB + 1} taps")
    if kernels:
        return K.conv_silu_kernels(
            x, w.astype(jnp.float32), jnp.zeros(w.shape[1:], jnp.float32)
            if bias is None else bias.astype(jnp.float32))
    return jax.nn.silu(causal_conv(x, w, bias)).astype(x.dtype)


def gated_norm(y, z, scale, groups: int, eps: float, gate_first=True):
    """``group_rmsnorm(y * silu(z), scale)`` in ``y``'s dtype, or with
    ``gate_first`` false ``group_rmsnorm(y, scale) * silu(z)`` (the norm
    first, the gate after: a Gated DeltaNet's): on the TPU, for the shapes
    ``ops.mamba2_kernels.norm_supported`` takes, the kernels
    ``bps_ssm_norm_fwd`` / ``_bwd``; elsewhere the XLA form."""
    kernels = (jax.default_backend() == "tpu"
               and K.norm_supported(y.shape, groups))
    note_choice("ssm_norm", "kernels" if kernels else "xla",
                (tuple(y.shape), groups),
                "XLA's fusions over a [.., groups, width] relayout: the "
                "kernels need a group's channels in whole lane tiles, at "
                f"most {K.LANES_MOST}, and positions in blocks of "
                f"{K.ROWS[-1]}")
    if kernels:
        scale = scale.astype(jnp.float32)
        if gate_first:
            return K.gated_norm_kernels(y, z, scale, groups, eps)
        return K.gated_norm_kernels(y, z, scale, groups, eps, 0, K.NORM_STRIP,
                                    False, False)
    f32 = jnp.float32
    if not gate_first:
        return (group_rmsnorm(y, scale, groups, eps)
                * jax.nn.silu(z.astype(f32))).astype(y.dtype)
    return group_rmsnorm(y.astype(f32) * jax.nn.silu(z.astype(f32)), scale,
                         groups, eps).astype(y.dtype)


def mixer(a, blk, cfg: SSMConfig, eps: float):
    """[b, s, hidden] (normed) -> [b, s, hidden]."""
    dt_, inner = a.dtype, cfg.inner
    w = blk["in_proj"].astype(dt_)
    with jax.named_scope("bps.ssm.proj"):       # three products of slices
        z = a @ w[:, :inner]                    # of the weight: no copy of
        xbc = a @ w[:, inner:inner + cfg.conv_dim]          # an activation
        step = jnp.dot(a, w[:, inner + cfg.conv_dim:],
                       preferred_element_type=jnp.float32)
    with jax.named_scope("bps.ssm.conv"):
        xbc = conv_silu(xbc, blk["conv_w"], blk["conv_b"])
    with jax.named_scope("bps.ssm.scan"):   # x, B and C where they lie
        y = ssd_packed(xbc, jax.nn.softplus(step + blk["dt_bias"]),
                       -jnp.exp(blk["A_log"]), blk["D"], cfg.groups,
                       cfg.state, cfg.chunk)
    with jax.named_scope("bps.ssm.norm"):
        y = gated_norm(y, z, blk["gated_norm"], cfg.groups, eps)
    with jax.named_scope("bps.ssm.proj"):
        return y @ blk["out_proj"].astype(dt_)

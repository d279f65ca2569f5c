"""The Gated DeltaNet mixer of a ``qwen3_next`` decoder
(``models/decoder.py``, kind ``gdn_moe``): what stands in three layers of
four where attention would.

``in_proj_qkvz`` [hidden, 2 key_dim + 2 value_dim] gives ``q``, ``k``
(``key_heads`` heads of ``head_dim``), ``v`` and the gate ``z``
(``value_heads`` heads of ``head_dim``) from the normed input,
``in_proj_ba`` [hidden, 2 value_heads] a value head's ``b`` and ``a``;
``q``, ``k`` and ``v`` side by side pass a depthwise causal convolution
of ``conv_kernel`` taps WITHOUT a bias, then SiLU; ``beta = sigmoid(b)``,
``g = -exp(A_log) softplus(a + dt_bias)`` (the log of the decay); ``q``
and ``k`` are l2-normalised a head, ``q`` scaled by ``head_dim ** -0.5``;
the gated delta rule runs in chunks (``ops/gated_delta.py``); the result
is RMS-normed a head of ``head_dim`` lanes (one weight shared by the
heads, starting at one and NOT zero-centred) and THEN multiplied by
``silu(z)``; ``out_proj`` [value_dim, hidden] ends it. No biases.

docs/linear-attention.md has the equations and what is float32: ``b``,
``a``, ``beta``, ``g``, its running sums and their ``exp``, the l2 norms,
the triangular inverse, the carried state and the head norm; the
projections, the convolution's operands and the chunks' products are in
the compute dtype. The convolution and the norm are ``mamba2.py``'s
entries (``conv_silu`` without a bias, ``gated_norm`` with the gate after
the norm) and choose kernels or XLA as they do there.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops.gated_delta import CHUNK, gated_delta
from .mamba2 import conv_silu, gated_norm

A_FLOOR = 1e-4      # the seeded decay rate, uniform (0, 16], floored
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GDNConfig:
    """A Gated DeltaNet mixer's sizes."""
    key_heads: int                # linear_num_key_heads
    value_heads: int              # linear_num_value_heads
    head_dim: int                 # linear_key_head_dim = linear_value_head_dim
    conv_kernel: int = 4
    chunk: int = CHUNK

    def __post_init__(self):
        if self.value_heads % self.key_heads:
            raise ValueError(f"{self.value_heads} value heads over "
                             f"{self.key_heads} key heads")

    @property
    def key_dim(self) -> int:
        return self.key_heads * self.head_dim

    @property
    def value_dim(self) -> int:
        return self.value_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim


def init_mixer(key, hidden: int, cfg: GDNConfig, std: float = 0.02):
    """A mixer's leaves, float32: matrices N(0, ``std``); the convolution
    as ``torch.nn.Conv1d`` starts it (uniform within 1 / sqrt(taps));
    ``A_log`` the log of uniform (0, 16] floored at ``A_FLOOR``; ``dt_bias``
    one; the head norm's weight one."""
    k = jax.random.split(key, 5)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    return {
        "in_proj_qkvz": jax.random.normal(
            k[0], (hidden, cfg.conv_dim + cfg.value_dim), jnp.float32) * std,
        "in_proj_ba": jax.random.normal(
            k[1], (hidden, 2 * cfg.value_heads), jnp.float32) * std,
        "conv_w": jax.random.uniform(k[2], (cfg.conv_kernel, cfg.conv_dim),
                                     jnp.float32, -bound, bound),
        "dt_bias": jnp.ones((cfg.value_heads,), jnp.float32),
        "A_log": jnp.log(jnp.maximum(jax.random.uniform(
            k[3], (cfg.value_heads,), jnp.float32, 0.0, 16.0), A_FLOOR)),
        "gdn_norm": jnp.ones((cfg.head_dim,), jnp.float32),
        "out_proj": jax.random.normal(k[4], (cfg.value_dim, hidden),
                                      jnp.float32) * std,
    }


def l2norm(x, scale: float = 1.0):
    """``scale * x / |x|`` over the last axis in float32, ``x``'s dtype
    back."""
    x32 = x.astype(jnp.float32)
    return (x32 * (scale * jax.lax.rsqrt(
        jnp.sum(x32 * x32, -1, keepdims=True) + L2_EPS))).astype(x.dtype)


def mixer(a, blk, cfg: GDNConfig, eps: float):
    """[b, s, hidden] (normed) -> [b, s, hidden]."""
    dt_, f32 = a.dtype, jnp.float32
    b, s, _ = a.shape
    hk, hv, d = cfg.key_heads, cfg.value_heads, cfg.head_dim
    w = blk["in_proj_qkvz"].astype(dt_)
    with jax.named_scope("bps.gdn.proj"):       # products of slices of the
        qkv = a @ w[:, :cfg.conv_dim]           # weight: no copy of an
        z = a @ w[:, cfg.conv_dim:]             # activation
        ba = jnp.dot(a, blk["in_proj_ba"].astype(dt_),
                     preferred_element_type=f32)
    with jax.named_scope("bps.gdn.conv"):
        qkv = conv_silu(qkv, blk["conv_w"])
    with jax.named_scope("bps.gdn.scan"):
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(blk["A_log"]) * jax.nn.softplus(
            ba[..., hv:] + blk["dt_bias"])
        q = l2norm(qkv[..., :cfg.key_dim].reshape(b, s, hk, d), d ** -0.5)
        k = l2norm(qkv[..., cfg.key_dim:2 * cfg.key_dim].reshape(b, s, hk, d))
        v = qkv[..., 2 * cfg.key_dim:].reshape(b, s, hv, d)
        o = gated_delta(q, k, v, g, beta, cfg.chunk)
    with jax.named_scope("bps.gdn.norm"):
        y = gated_norm(o.reshape(b, s, cfg.value_dim), z,
                       jnp.tile(blk["gdn_norm"], hv), hv, eps,
                       gate_first=False)
    with jax.named_scope("bps.gdn.proj"):
        return y @ blk["out_proj"].astype(dt_)

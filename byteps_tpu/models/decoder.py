"""A causal decoder whose layers are a LIST OF KINDS, and the four
families built on it: ``afmoe`` (arcee-ai Trinity), ``nemotron_h``
(NVIDIA Nemotron 3), ``deepseek_v3`` (latent attention: Kanana-2) and
``qwen3_next`` (gated delta-rule linear attention: Qwen3-Next).

``transformer.py`` is one kind of block under one ``lax.scan``. Here each
layer is data, and a kind says everything about its layer:

* ``dense_sliding``, ``dense_full``, ``moe_sliding``, ``moe_full``
  (afmoe): an attention half and a feed-forward half. The attention is a
  causal band (``window``) that rotates q and k (RoPE), or the whole
  triangle without positions; the feed-forward a dense gated-SiLU MLP or
  the routed layer of ``moe.py::routed_ffn`` (experts held here, a shared
  expert). Four RMSNorms (before and after each half), RMSNorm on q and
  k a head, grouped kv heads, a sigmoid gate on the attention output.
* ``ssm``, ``attn``, ``moe`` (nemotron_h): ONE mixer a layer,
  ``x + mixer(RMSNorm(x))``, one norm. ``ssm``: the Mamba-2 mixer of
  ``mamba2.py`` (``cfg.ssm``); ``attn``: q, k, v and the output
  projection over grouped kv heads, causal, with no positions, no norm
  on q or k and no gate; ``moe``: the routed layer, its experts' function
  and shared width ``cfg.routed``'s.

* ``mla_dense``, ``mla_moe`` (deepseek_v3): two halves with TWO norms,
  one before each half. The attention is ``mla.py``'s latent attention
  (``cfg.mla``: q·k over 192 lanes a head, values of 128, rotary
  positions on every layer, no gate, no norm on q or k a head); the
  feed-forward the dense gated-SiLU MLP or the routed layer, the same
  code as afmoe's without its norm after.

* ``gdn_moe``, ``gattn_moe`` (qwen3_next): two halves with a norm before
  each and none after, every norm ZERO-CENTRED (``cfg.zero_centred``:
  ``x * rsqrt(mean x^2 + eps) * (1 + w)``, the leaf ``w`` starting at
  zero, so that weight decay pulls the scale toward one). ``gdn_moe``:
  the Gated DeltaNet mixer of ``gated_delta_net.py`` (``cfg.gdn``: a
  causal convolution without a bias, the gated delta rule in chunks, a
  head norm with the gate after it); ``gattn_moe``: afmoe's gated
  attention over grouped kv heads without a window and without its norm
  after, rotary positions on the first ``cfg.rotary_dim`` lanes of a head
  on EVERY such layer. The second half of both is the routed layer with
  softmax scores and a sigmoid gate a token on the shared expert's output.

The embedding is scaled by sqrt(hidden) where ``scale_embedding`` (afmoe)
and the head is untied.

One chip's SHARE of a model is a configuration like any other: ``heads``
and ``kv_heads`` are the heads held here, ``vocab_size`` the rows of the
embedding and of the head held here, ``held`` the experts held here of
``router_outputs``. Nothing stands in for the chips that hold the rest.

The parameter tree (``init_params``) is a list of per-layer dicts (the
layers differ in shape, so they are not stacked); the layers run as a
Python loop, each under ``jax.checkpoint``. A layer's checkpoint keeps
what costs more to remake than to hold (the layers are not scanned, so a
kept value is copied nowhere):

* its input;
* the flash kernel's output and row statistics (``flash_out``,
  ``flash_lse``: [b, s, heads * head_dim] bf16 + [b, heads, s] fp32, 34 MB
  at 2 x 8192 x 8 heads of 128, 135 MB at 32 heads);
* the routed layer's plan (``moe.PLAN_NAME``: the choice, its scores and
  the int32 arrays of the chosen pairs and the buffer's rows, 3 MB at
  16,384 tokens x 8);
* the held experts' weights in the compute dtype (``moe.WEIGHTS_NAME``:
  2 bytes a held expert parameter, 201 MB a layer at 16 experts of 1024
  over a hidden of 2048, 151 MB at 16 of 768, 160 MB at 8 ungated of 1856
  over 2688): the float32 parameters are cast once a layer and step;
* the sum a norm AFTER a half reads (``POST_NORM_NAME``: [b, s, hidden]
  bf16, 67 MB at 2 x 8192 x 2048, two a layer): only afmoe's halves end
  in a norm, whose backward needs its input. Without the name the
  recompute runs everything that makes that input: the attention's output
  projection, the routed layer's second grouped product, combine and
  shared expert, the dense feed-forward's second product. A family
  without such a norm names nothing and keeps nothing;
* the gated delta rule's in-chunk inverse (``gated_delta.INVERSE_NAME``:
  ``T = (I + A)^-1``, [b, chunks, heads, c, c] in the compute dtype, 134 MB
  a ``gdn_moe`` layer at 2 x 8192 x 32 heads in chunks of 128), which is
  what the rule's other three kernels read: the series and the merges
  (36 passes of the MXU a matrix) run once a layer and step. Of the rule
  NOTHING else: the layer's backward runs ``bps_gdn_fwd`` again (the
  chunks' other operands, ``U``, ``W``, the masked ``q k^T``, exist in
  VMEM alone), which then writes the state before each chunk, [b, chunks,
  heads, 128, 128] float32, 268 MB, held for as long as that one layer's
  backward runs. Keeping those from the forward would hold every such
  layer's at once (3 GB at three layers).

Everything else is recomputed in the backward, so the kernel's forward,
the plan's top-k and sort, the weights' cast and, in afmoe, the combine
run once a layer (the grouped products are still recomputed: their
outputs are the backward's operands, 570 MB a layer). The embedding
lookup, the chunked head and the target convention are
``transformer.py``'s.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.flash_attention import SAVED_NAMES, attention
from ..ops.gated_delta import INVERSE_NAME
from .gated_delta_net import (GDNConfig, init_mixer as init_gdn,
                              mixer as gdn_mixer)
from .mamba2 import SSMConfig, init_mixer, mixer as ssm_mixer
from .mla import MLAConfig, attention_half as mla_half, init_attention
from .moe import (PLAN_NAME, WEIGHTS_NAME, RoutedConfig, gated_silu,
                  routed_ffn)
from .transformer import _chunked_nll_sum, embed_lookup

# a layer of two halves (attention, feed-forward) ...
HALVES = ("dense_sliding", "dense_full", "moe_sliding", "moe_full")
# ... or of one mixer: state-space, attention, routed feed-forward
MIXERS = ("ssm", "attn", "moe")
# ... or of two halves with a norm before each, the attention latent
LATENT = ("mla_dense", "mla_moe")
# ... or of two halves with a norm before each, the first a Gated DeltaNet
# or gated attention with partial rotary positions, the second routed
GATED = ("gdn_moe", "gattn_moe")
KINDS = HALVES + MIXERS + LATENT + GATED

# the name a layer's checkpoint keeps the input of a norm AFTER a half
# under (afmoe's ``norm_post``, both halves): the half's whole output
# before its norm, which the norm's backward reads
POST_NORM_NAME = "post_norm_in"


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden: int
    heads: int                    # query heads held here
    kv_heads: int                 # kv heads held here
    head_dim: int
    mlp_dim: int                  # a dense layer's feed-forward width
    layer_kinds: Tuple[str, ...]  # one of KINDS a layer
    window: int                   # keys a ``*_sliding`` layer attends
    moe_dim: int = 0              # an expert's width
    shared_experts: int = 0       # shared experts, each of moe_dim
    routed: Optional[RoutedConfig] = None
    ssm: Optional[SSMConfig] = None     # an ``ssm`` layer's mixer
    mla: Optional[MLAConfig] = None     # an ``mla_*`` layer's attention
    gdn: Optional[GDNConfig] = None     # a ``gdn_moe`` layer's mixer
    rotary_dim: int = 0           # lanes of a head RoPE rotates; 0: all
    zero_centred: bool = False    # norms scale by 1 + w (w starts at 0)
    scale_embedding: bool = True  # the embedding times sqrt(hidden)
    max_seq: int = 1 << 17        # positions RoPE is defined for
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"       # compute dtype (params stay fp32)
    remat: bool = True            # checkpoint each layer
    lm_head_chunk: int = 0        # >0: the head a chunk of positions at
    # a time (transformer._chunked_nll_sum: O(chunk x vocab) live, the
    # chunk's gradient formed beside its logits, d h [b, s, hidden] and
    # d head [vocab, hidden] kept from the forward to the backward);
    # 0: all positions as one chunk

    def __post_init__(self):
        bad = [k for k in self.layer_kinds if k not in KINDS]
        if bad:
            raise ValueError(
                f"layer kinds {bad} are none of {KINDS}: a layer of two "
                f"halves is one of {HALVES}, of {LATENT} or of {GATED}, a "
                f"layer of one mixer one of {MIXERS}")
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} heads over {self.kv_heads} kv")
        if any("moe" in k for k in self.layer_kinds) and (
                self.routed is None or not self.moe_dim):
            raise ValueError("a routed layer needs `routed` and `moe_dim`")
        if "ssm" in self.layer_kinds and self.ssm is None:
            raise ValueError("a state-space layer needs `ssm`")
        if set(LATENT) & set(self.layer_kinds) and self.mla is None:
            raise ValueError("a latent attention layer needs `mla`")
        if "gdn_moe" in self.layer_kinds and self.gdn is None:
            raise ValueError("a Gated DeltaNet layer needs `gdn`")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"{self.rotary_dim} rotary lanes of a head of "
                             f"{self.head_dim}")


def afmoe_config(vocab_size, hidden, heads, kv_heads, head_dim, mlp_dim,
                 moe_dim, layer_kinds: Sequence[str], window, top_k,
                 router_outputs, held: Sequence[int], shared_experts=1,
                 route_scale=1.0, max_seq=1 << 17, rope_theta=10000.0,
                 norm_eps=1e-5, balanced=False, routed_kw=None,
                 **kw) -> DecoderConfig:
    """The afmoe family (arcee-ai Trinity) from its sizes, as the
    benchmark's configuration gives them (``benchmark/configs``).
    ``balanced``: the routed layers choose on standardised outputs
    (``moe.route``). ``routed_kw``: further fields of the routed layers' ``RoutedConfig``
    (the tests' row tile and kernel choice); further keywords (``dtype``,
    ``lm_head_chunk``, ...) are DecoderConfig's."""
    return DecoderConfig(
        vocab_size=vocab_size, hidden=hidden, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, mlp_dim=mlp_dim, moe_dim=moe_dim,
        layer_kinds=tuple(layer_kinds), window=window,
        shared_experts=shared_experts, max_seq=max_seq,
        rope_theta=rope_theta, norm_eps=norm_eps,
        routed=RoutedConfig(router_outputs, tuple(held), top_k, route_scale,
                            balanced=balanced, **(routed_kw or {})),
        **kw)


def nemotron_h_config(vocab_size, hidden, heads, kv_heads, head_dim,
                      moe_dim, shared_dim, layer_kinds: Sequence[str], top_k,
                      router_outputs, held: Sequence[int], ssm_heads,
                      ssm_head_dim, ssm_groups, ssm_state, conv_kernel=4,
                      chunk=128, route_scale=1.0, max_seq=1 << 18,
                      norm_eps=1e-5, balanced=False, routed_kw=None,
                      **kw) -> DecoderConfig:
    """The nemotron_h family (NVIDIA Nemotron 3) from its sizes, as the
    benchmark's configuration gives them: layers of ONE mixer each
    (``layer_kinds`` of ``ssm`` / ``attn`` / ``moe``, the published
    pattern's ``M`` / ``*`` / ``E``), experts that are not gated
    (``down(relu(up x)^2)``, width ``moe_dim``) with one shared expert of
    ``shared_dim``, attention without positions, an embedding that is not
    scaled. ``balanced``, ``routed_kw`` and further keywords as
    ``afmoe_config``'s."""
    return DecoderConfig(
        vocab_size=vocab_size, hidden=hidden, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, mlp_dim=0, moe_dim=moe_dim,
        layer_kinds=tuple(layer_kinds), window=0, max_seq=max_seq,
        norm_eps=norm_eps, scale_embedding=False,
        ssm=SSMConfig(ssm_heads, ssm_head_dim, ssm_groups, ssm_state,
                      conv_kernel, chunk),
        routed=RoutedConfig(router_outputs, tuple(held), top_k, route_scale,
                            balanced=balanced, act="relu2",
                            shared_dim=shared_dim, **(routed_kw or {})),
        **kw)


def deepseek_v3_config(vocab_size, hidden, heads, kv_lora_rank,
                       qk_nope_dim, qk_rope_dim, v_head_dim, mlp_dim,
                       moe_dim, layer_kinds: Sequence[str], top_k,
                       router_outputs, held: Sequence[int], shared_experts=1,
                       route_scale=1.0, max_seq=1 << 15, rope_theta=10000.0,
                       norm_eps=1e-6, balanced=False, routed_kw=None,
                       **kw) -> DecoderConfig:
    """The deepseek_v3 family without a query latent (Kanana-2) from its
    sizes, as the benchmark's configuration gives them: layers of
    ``mla_dense`` / ``mla_moe``, latent attention over ``heads`` heads
    (q·k ``qk_nope_dim + qk_rope_dim`` wide, which is ``head_dim`` here;
    values ``v_head_dim``), the routed layers afmoe's (sigmoid scores, the
    chosen ones normalised and scaled, gated-SiLU experts, a shared expert
    of ``shared_experts * moe_dim``), an embedding that is not scaled.
    ``balanced``, ``routed_kw`` and further keywords as ``afmoe_config``'s."""
    return DecoderConfig(
        vocab_size=vocab_size, hidden=hidden, heads=heads, kv_heads=heads,
        head_dim=qk_nope_dim + qk_rope_dim, mlp_dim=mlp_dim, moe_dim=moe_dim,
        layer_kinds=tuple(layer_kinds), window=0,
        shared_experts=shared_experts, max_seq=max_seq,
        rope_theta=rope_theta, norm_eps=norm_eps, scale_embedding=False,
        mla=MLAConfig(kv_lora_rank, qk_nope_dim, qk_rope_dim, v_head_dim),
        routed=RoutedConfig(router_outputs, tuple(held), top_k, route_scale,
                            balanced=balanced, **(routed_kw or {})),
        **kw)


def qwen3_next_config(vocab_size, hidden, heads, kv_heads, head_dim,
                      moe_dim, shared_dim, layer_kinds: Sequence[str], top_k,
                      router_outputs, held: Sequence[int], gdn_key_heads,
                      gdn_value_heads, gdn_head_dim, conv_kernel=4, chunk=128,
                      rotary_dim=0, route_scale=1.0, max_seq=1 << 18,
                      rope_theta=1e7, norm_eps=1e-6, balanced=False,
                      routed_kw=None, **kw) -> DecoderConfig:
    """The qwen3_next family (Qwen3-Next) from its sizes, as the
    benchmark's configuration gives them: layers of ``gdn_moe`` /
    ``gattn_moe`` (three Gated DeltaNet layers to every gated
    full-attention layer in the published pattern), zero-centred norms,
    rotary positions on ``rotary_dim`` lanes of a head, softmax scores
    over all ``router_outputs`` with the chosen ones normalised,
    gated-SiLU experts of ``moe_dim`` and one shared expert of
    ``shared_dim`` under a sigmoid gate a token, an embedding that is not
    scaled. ``chunk``: positions a chunk of the delta rule. ``balanced``,
    ``routed_kw`` and further keywords as ``afmoe_config``'s."""
    return DecoderConfig(
        vocab_size=vocab_size, hidden=hidden, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, mlp_dim=0, moe_dim=moe_dim,
        layer_kinds=tuple(layer_kinds), window=0, max_seq=max_seq,
        rope_theta=rope_theta, norm_eps=norm_eps, scale_embedding=False,
        rotary_dim=rotary_dim, zero_centred=True,
        gdn=GDNConfig(gdn_key_heads, gdn_value_heads, gdn_head_dim,
                      conv_kernel, chunk),
        routed=RoutedConfig(router_outputs, tuple(held), top_k, route_scale,
                            balanced=balanced, score="softmax",
                            shared_dim=shared_dim, **(routed_kw or {})),
        **kw)


def qwen3_next_tiny(**kw) -> DecoderConfig:
    """Test-sized: one published period (three Gated DeltaNet layers and a
    gated attention layer), 2 value heads a key head, 2 query heads a kv
    head, a quarter of a head's lanes rotated, 4 of 8 experts held."""
    sizes = dict(vocab_size=128, hidden=64, heads=4, kv_heads=2, head_dim=16,
                 moe_dim=24, shared_dim=24, top_k=3, router_outputs=8,
                 held=(0, 1, 2, 3), gdn_key_heads=2, gdn_value_heads=4,
                 gdn_head_dim=8, chunk=16, rotary_dim=4, rope_theta=1e7,
                 routed_kw={"row_tile": 8},
                 layer_kinds=("gdn_moe", "gdn_moe", "gdn_moe", "gattn_moe"),
                 dtype="float32", remat=False)
    return qwen3_next_config(**{**sizes, **kw})


def deepseek_v3_tiny(**kw) -> DecoderConfig:
    """Test-sized: both kinds of latent layer, q·k of 16 + 8 over values
    of 16, a latent of 32, 4 of 8 experts held, two shared experts."""
    sizes = dict(vocab_size=128, hidden=64, heads=4, kv_lora_rank=32,
                 qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, mlp_dim=96,
                 moe_dim=24, top_k=2, router_outputs=8, held=(0, 1, 2, 3),
                 shared_experts=2, route_scale=2.5, rope_theta=1e6,
                 routed_kw={"row_tile": 8},
                 layer_kinds=("mla_dense", "mla_moe", "mla_moe"),
                 dtype="float32", remat=False)
    return deepseek_v3_config(**{**sizes, **kw})


def nemotron_h_tiny(**kw) -> DecoderConfig:
    """Test-sized: every kind of one-mixer layer, 2 query heads a kv head,
    2 state-space heads a group, 4 of 8 experts held, a shared expert of
    a width of its own."""
    sizes = dict(vocab_size=128, hidden=64, heads=4, kv_heads=2, head_dim=16,
                 moe_dim=24, shared_dim=40, top_k=2, router_outputs=8,
                 held=(0, 1, 2, 3), route_scale=2.5, ssm_heads=4,
                 ssm_head_dim=8, ssm_groups=2, ssm_state=16, chunk=8,
                 routed_kw={"row_tile": 8},
                 layer_kinds=("ssm", "moe", "ssm", "attn", "moe"),
                 dtype="float32", remat=False)
    return nemotron_h_config(**{**sizes, **kw})


def afmoe_tiny(**kw) -> DecoderConfig:
    """Test-sized: every kind of layer, 2 query heads a kv head, a window
    shorter than the sequence, 4 of 8 experts held."""
    sizes = dict(vocab_size=128, hidden=64, heads=4, kv_heads=2, head_dim=16,
                 mlp_dim=96, moe_dim=32, window=8, top_k=2, router_outputs=8,
                 held=(0, 1, 2, 3), route_scale=2.0,
                 routed_kw={"row_tile": 8},
                 layer_kinds=("dense_sliding", "moe_sliding", "moe_full"),
                 dtype="float32", remat=False)
    return afmoe_config(**{**sizes, **kw})


# ----------------------------------------------------------------- params

def init_params(rng, cfg: DecoderConfig):
    """The parameter tree: N(0, 0.02) matrices, unit norm scales (zero
    where ``cfg.zero_centred``: the scale is then ``1 + w``), fp32; a
    state-space mixer's leaves as ``mamba2.init_mixer`` seeds them, a
    Gated DeltaNet's as ``gated_delta_net.init_mixer``."""
    h, d = cfg.hidden, cfg.head_dim
    keys = iter(jax.random.split(rng, 16 * len(cfg.layer_kinds) + 2))

    def unit(n):        # a norm's leaf at a scale of one
        return jnp.zeros((n,)) if cfg.zero_centred else jnp.ones((n,))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    def mlp(width, *lead):
        return {"gate_up": normal(*lead, h, 2 * width),
                "down": normal(*lead, width, h)}

    def mixer_layer(kind):
        blk = {"norm": jnp.ones((h,))}
        if kind == "ssm":
            blk.update(init_mixer(next(keys), h, cfg.ssm))
        elif kind == "attn":
            blk.update(q=normal(h, cfg.heads, d), k=normal(h, cfg.kv_heads, d),
                       v=normal(h, cfg.kv_heads, d), o=normal(cfg.heads, d, h))
        else:
            held = len(cfg.routed.held)
            blk["router"] = normal(h, cfg.routed.num_experts)
            blk["experts"] = {"up": normal(held, h, cfg.moe_dim),
                              "down": normal(held, cfg.moe_dim, h)}
            if cfg.routed.shared_dim:
                blk["shared"] = {"up": normal(h, cfg.routed.shared_dim),
                                 "down": normal(cfg.routed.shared_dim, h)}
        return blk

    def routed():
        blk = {"router": normal(h, cfg.routed.num_experts),
               "experts": mlp(cfg.moe_dim, len(cfg.routed.held))}
        shared = cfg.routed.shared_dim or cfg.shared_experts * cfg.moe_dim
        if shared:
            blk["shared"] = mlp(shared)
        return blk

    def gated_layer(kind):
        if kind == "gdn_moe":
            attn = init_gdn(next(keys), h, cfg.gdn)
        else:
            attn = {"q": normal(h, cfg.heads, d),
                    "k": normal(h, cfg.kv_heads, d),
                    "v": normal(h, cfg.kv_heads, d),
                    "gate": normal(h, cfg.heads, d), "q_norm": unit(d),
                    "k_norm": unit(d), "o": normal(cfg.heads, d, h)}
        return {"attn": {"norm": unit(h), **attn},
                "ffn": {"norm": unit(h), **routed(),
                        "shared_gate": normal(h, 1)}}

    def layer(kind):
        if kind in MIXERS:
            return mixer_layer(kind)
        if kind in GATED:
            return gated_layer(kind)
        if kind in LATENT:
            attn = init_attention(normal, h, cfg.heads, cfg.mla)
            ffn = routed() if kind == "mla_moe" else mlp(cfg.mlp_dim)
            return {"attn": attn, "ffn": {"norm": jnp.ones((h,)), **ffn}}
        attn = {"norm_in": jnp.ones((h,)), "q": normal(h, cfg.heads, d),
                "k": normal(h, cfg.kv_heads, d),
                "v": normal(h, cfg.kv_heads, d),
                "gate": normal(h, cfg.heads, d), "q_norm": jnp.ones((d,)),
                "k_norm": jnp.ones((d,)), "o": normal(cfg.heads, d, h),
                "norm_post": jnp.ones((h,))}
        ffn = {"norm_pre": jnp.ones((h,)), "norm_post": jnp.ones((h,))}
        if kind.startswith("dense"):
            ffn.update(mlp(cfg.mlp_dim))
        else:
            ffn.update(routed())
        return {"attn": attn, "ffn": ffn}

    return {"embed": normal(cfg.vocab_size, h),
            "layers": [layer(kind) for kind in cfg.layer_kinds],
            "final_norm": unit(h),
            "head": normal(cfg.vocab_size, h)}


# ----------------------------------------------------------------- layers

def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (out * scale).astype(x.dtype)


def rope(x, theta: float, width: int = 0):
    """Rotary positions on [b, s, heads, d], position = row of ``s``, the
    halves of ``d`` paired (i, i + d/2) as the published code pairs them.
    ``width`` > 0: on the first ``width`` lanes of a head alone (their
    halves paired, the frequencies a head of ``width`` would have), the
    lanes after them as they are."""
    if 0 < width < x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :width], theta), x[..., width:]], -1)
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _norm(x, w, cfg: "DecoderConfig"):
    """The family's RMSNorm of ``x`` by the leaf ``w``: the scale is
    ``1 + w`` where ``cfg.zero_centred``."""
    return rmsnorm(x, 1.0 + w if cfg.zero_centred else w, cfg.norm_eps)


def _gated_attention(a, blk, cfg: DecoderConfig, positions: bool,
                     window=None):
    """Gated attention of a normed input over grouped kv heads: RMSNorm
    on q and k a head, rotary ``positions`` or none, a causal band or the
    whole triangle, a sigmoid gate on the output, the output projection."""
    dt = a.dtype
    q = jnp.einsum("bsh,hnd->bsnd", a, blk["q"].astype(dt))
    k = jnp.einsum("bsh,hnd->bsnd", a, blk["k"].astype(dt))
    v = jnp.einsum("bsh,hnd->bsnd", a, blk["v"].astype(dt))
    gate = jnp.einsum("bsh,hnd->bsnd", a, blk["gate"].astype(dt))
    q = _norm(q, blk["q_norm"], cfg)
    k = _norm(k, blk["k_norm"], cfg)
    if positions:
        q = rope(q, cfg.rope_theta, cfg.rotary_dim)
        k = rope(k, cfg.rope_theta, cfg.rotary_dim)
    out = attention(q, k, v, causal=True, window=window)
    return jnp.einsum("bsnd,ndh->bsh", out * jax.nn.sigmoid(gate),
                      blk["o"].astype(dt))


def _attention_half(x, blk, cfg: DecoderConfig, sliding: bool):
    # rotary positions on the window layers only
    out = _gated_attention(rmsnorm(x, blk["norm_in"], cfg.norm_eps), blk, cfg,
                           sliding, cfg.window if sliding else None)
    return rmsnorm(checkpoint_name(out, POST_NORM_NAME), blk["norm_post"],
                   cfg.norm_eps)


def _ffn(f, blk, cfg: DecoderConfig, routed: bool):
    """The feed-forward of a normed input: the routed layer or the dense
    gated-SiLU MLP."""
    if routed:
        b, s, h = f.shape
        return routed_ffn(f.reshape(b * s, h), blk, cfg.routed,
                          sequences=b).reshape(b, s, h)
    dt = f.dtype
    return gated_silu(f @ blk["gate_up"].astype(dt)) @ blk["down"].astype(dt)


def _ffn_half(x, blk, cfg: DecoderConfig, routed: bool):
    m = _ffn(rmsnorm(x, blk["norm_pre"], cfg.norm_eps), blk, cfg, routed)
    return rmsnorm(checkpoint_name(m, POST_NORM_NAME), blk["norm_post"],
                   cfg.norm_eps)


def _latent_layer(x, blk, cfg: DecoderConfig, kind: str):
    """A deepseek_v3 layer: a norm before each half and none after."""
    attn, ffn = blk["attn"], blk["ffn"]
    with jax.named_scope("bps.attn"):
        x = x + mla_half(rmsnorm(x, attn["norm"], cfg.norm_eps), attn,
                         cfg.mla, cfg.norm_eps, cfg.rope_theta)
    with jax.named_scope("bps.mlp"):
        return x + _ffn(rmsnorm(x, ffn["norm"], cfg.norm_eps), ffn, cfg,
                        kind == "mla_moe")


def _gated_layer(x, blk, cfg: DecoderConfig, kind: str):
    """A qwen3_next layer: a norm before each half and none after; a
    Gated DeltaNet or gated attention, then the routed layer."""
    attn, ffn = blk["attn"], blk["ffn"]
    if kind == "gdn_moe":
        with jax.named_scope("bps.gdn"):
            x = x + gdn_mixer(_norm(x, attn["norm"], cfg), attn, cfg.gdn,
                              cfg.norm_eps)
    else:
        with jax.named_scope("bps.attn"):
            x = x + _gated_attention(_norm(x, attn["norm"], cfg), attn, cfg,
                                     True)
    with jax.named_scope("bps.mlp"):
        return x + _ffn(_norm(x, ffn["norm"], cfg), ffn, cfg, True)


def _mixer_layer(x, blk, cfg: DecoderConfig, kind: str):
    """A layer that is one mixer: ``x + mixer(RMSNorm(x))``."""
    dt = x.dtype
    scope = {"ssm": "bps.ssm", "attn": "bps.attn", "moe": "bps.mlp"}[kind]
    with jax.named_scope(scope):
        a = rmsnorm(x, blk["norm"], cfg.norm_eps)
        if kind == "ssm":
            return x + ssm_mixer(a, blk, cfg.ssm, cfg.norm_eps)
        if kind == "moe":
            b, s, h = a.shape
            return x + routed_ffn(a.reshape(b * s, h), blk, cfg.routed,
                                  sequences=b).reshape(b, s, h)
        q = jnp.einsum("bsh,hnd->bsnd", a, blk["q"].astype(dt))
        k = jnp.einsum("bsh,hnd->bsnd", a, blk["k"].astype(dt))
        v = jnp.einsum("bsh,hnd->bsnd", a, blk["v"].astype(dt))
        return x + jnp.einsum("bsnd,ndh->bsh", attention(q, k, v, causal=True),
                              blk["o"].astype(dt))


def _layer(x, blk, cfg: DecoderConfig, kind: str):
    if kind in MIXERS:
        return _mixer_layer(x, blk, cfg, kind)
    if kind in LATENT:
        return _latent_layer(x, blk, cfg, kind)
    if kind in GATED:
        return _gated_layer(x, blk, cfg, kind)
    with jax.named_scope("bps.attn"):
        x = x + _attention_half(x, blk["attn"], cfg,
                                kind.endswith("sliding"))
    with jax.named_scope("bps.mlp"):
        return x + _ffn_half(x, blk["ffn"], cfg, kind.startswith("moe"))


def apply(params, cfg: DecoderConfig, tokens) -> jnp.ndarray:
    """Forward to the final hidden states [b, s, hidden], normed."""
    dt = jnp.dtype(cfg.dtype)
    if tokens.shape[1] > cfg.max_seq:
        raise ValueError(f"{tokens.shape[1]} positions, the model has "
                         f"{cfg.max_seq}")
    with jax.named_scope("bps.embed"):
        x = embed_lookup(params["embed"], tokens, dt, math.sqrt(cfg.hidden)
                         if cfg.scale_embedding else None)
    # a layer's checkpoint keeps its input, the flash kernel's output and
    # row statistics, the routed layer's plan and bf16 weights, what a
    # norm after a half reads and the delta rule's in-chunk inverse; the
    # rest is recomputed
    policy = jax.checkpoint_policies.save_only_these_names(
        *SAVED_NAMES, PLAN_NAME, WEIGHTS_NAME, POST_NORM_NAME, INVERSE_NAME)
    for kind, blk in zip(cfg.layer_kinds, params["layers"]):
        layer = functools.partial(_layer, cfg=cfg, kind=kind)
        if cfg.remat:
            layer = jax.checkpoint(layer, policy=policy)
        x = layer(x, blk)
    with jax.named_scope("bps.head"):    # the final norm feeds the head
        return _norm(x, params["final_norm"], cfg)


def causal_lm_loss(params, cfg: DecoderConfig, batch) -> jnp.ndarray:
    """batch = tokens [b, s]; mean negative log-likelihood of the next
    token in fp32. The full sequence is the input and the last target is
    masked (``gpt2.causal_lm_loss``'s convention: s stays a multiple of
    128 for the kernels)."""
    tokens = batch
    with jax.named_scope("bps.head"):
        targets = jnp.concatenate(
            [tokens[:, 1:],
             jnp.full((tokens.shape[0], 1), -1, tokens.dtype)], axis=1)
    h = apply(params, cfg, tokens)
    mask = targets >= 0
    s = h.shape[1]
    chunk = cfg.lm_head_chunk
    if not (chunk and s > chunk and s % chunk == 0):
        chunk = s
    nll_sum = _chunked_nll_sum(h, params["head"], targets, mask, chunk,
                               jnp.dtype(cfg.dtype))
    return nll_sum / jnp.maximum(mask.sum().astype(jnp.float32), 1.0)

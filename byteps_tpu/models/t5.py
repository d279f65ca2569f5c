"""Encoder-decoder (T5-style) transformer — seq2seq model family.

Additive beyond the reference's zoo (its examples cover CV + BERT/GPT;
no seq2seq anywhere in `/root/reference/example/`): a full
encoder-decoder with causal decoder self-attention plus cross-attention
over the encoder's memory, reusing this framework's building blocks —
`transformer`'s layernorm/MLP/embedding (MXU-backward embed), the flash
kernels for self-attention, and the same Megatron-style tensor-parallel
sharding (column-parallel QKV over heads, row-parallel projections with
one psum per sublayer).

Round 4 fidelity upgrades (the two signature T5 mechanisms):

- **Relative position bias** (`pos_encoding="relative"`, the default):
  no absolute position embedding; each stack owns ONE learned
  [num_buckets, heads] table (shared across its layers, exactly T5's
  weight sharing) — bidirectional buckets in the encoder, causal in
  the decoder. The table rides the flash kernels' IN-KERNEL rel-bias
  input: each (q-block, kv-block) derives its bucket map from block
  offsets and folds the table into the scores inside VMEM, dtable
  accumulated in kernel scratch — no [heads, s, s] bias ever
  materializes in HBM, so relative-bias self-attention stays O(s)
  memory at ANY length (a materialized bias is 34 GB at s=32k, h=8;
  the in-kernel form runs it in ~0.85 s fwd+bwd on one chip). T5's
  no-1/√d-scaling convention applies in this mode.
  ``pos_encoding="absolute"`` keeps the learned-positions variant.
- **Flash cross-attention**: the kernels' tiling contract is per-axis
  (q and kv lengths independent), so decoder-over-encoder attention
  runs the same Pallas path as self-attention — the O(sq·sk) score
  matrix never leaves VMEM, which is what makes LONG-encoder seq2seq
  (e.g. summarization at 8k+ source tokens) feasible.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .transformer import _layernorm, _mlp, embed_lookup

__all__ = ["T5Config", "t5_tiny", "t5_small", "init_t5_params",
           "t5_param_specs", "encode", "decode", "seq2seq_loss",
           "synth_seq2seq_batch", "relative_position_bucket",
           "relative_bias"]


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32000
    hidden: int = 512
    enc_layers: int = 6
    dec_layers: int = 6
    heads: int = 8
    mlp_dim: int = 2048
    max_seq: int = 512
    dtype: str = "bfloat16"
    remat: bool = True
    attn_impl: str = "auto"
    tp_axis: Optional[str] = None
    # T5's signature position scheme (see module docstring); "absolute"
    # restores the learned position table
    pos_encoding: str = "relative"
    rel_buckets: int = 32
    rel_max_distance: int = 128

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def relative(self) -> bool:
        return self.pos_encoding == "relative"


def t5_tiny(**kw) -> T5Config:
    return T5Config(vocab_size=128, hidden=64, enc_layers=2, dec_layers=2,
                    heads=4, mlp_dim=128, max_seq=64, **kw)


def t5_small(**kw) -> T5Config:
    return T5Config(**kw)


# ------------------------------------------------------------------ params

def _enc_block_init(key, h, m, heads, hd, sd=0.02):
    k = jax.random.split(key, 4)
    n = lambda kk, shape: jax.random.normal(kk, shape, jnp.float32) * sd
    return {
        "ln1": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
        "qkv": n(k[0], (h, 3, heads, hd)),
        "attn_out": n(k[1], (h, h)),
        "ln2": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
        "mlp_in": n(k[2], (h, m)), "mlp_in_b": jnp.zeros((m,)),
        "mlp_out": n(k[3], (m, h)), "mlp_out_b": jnp.zeros((h,)),
    }


def _dec_block_init(key, h, m, heads, hd, sd=0.02):
    k = jax.random.split(key, 7)
    n = lambda kk, shape: jax.random.normal(kk, shape, jnp.float32) * sd
    blk = _enc_block_init(key, h, m, heads, hd, sd)
    blk.update({
        # cross-attention: q from the decoder stream, k/v from memory
        "lnx": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
        "xq": n(k[4], (h, heads, hd)),
        "xkv": n(k[5], (h, 2, heads, hd)),
        "x_out": n(k[6], (h, h)),
    })
    return blk


def init_t5_params(rng, cfg: T5Config):
    h, m, hd = cfg.hidden, cfg.mlp_dim, cfg.head_dim
    keys = jax.random.split(rng, cfg.enc_layers + cfg.dec_layers + 3)
    enc = [_enc_block_init(keys[i + 2], h, m, cfg.heads, hd)
           for i in range(cfg.enc_layers)]
    dec = [_dec_block_init(keys[cfg.enc_layers + i + 2], h, m, cfg.heads,
                           hd)
           for i in range(cfg.dec_layers)]
    stack = lambda blocks: jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *blocks)
    sd = 0.02
    if cfg.relative:
        # one bucket table PER STACK, shared by its layers (T5's
        # weight sharing; reference T5 holds it in layer 0)
        k1, k2 = jax.random.split(keys[1])
        embed = {"tok": jax.random.normal(keys[0], (cfg.vocab_size, h),
                                          jnp.float32) * sd}
        rel = {
            "enc_rel_bias": jax.random.normal(
                k1, (cfg.rel_buckets, cfg.heads), jnp.float32) * sd,
            "dec_rel_bias": jax.random.normal(
                k2, (cfg.rel_buckets, cfg.heads), jnp.float32) * sd,
        }
    else:
        embed = {
            "tok": jax.random.normal(keys[0], (cfg.vocab_size, h),
                                     jnp.float32) * sd,
            "pos": jax.random.normal(keys[1], (cfg.max_seq, h),
                                     jnp.float32) * sd,
        }
        rel = {}
    return {
        "embed": embed,
        **rel,
        "enc_blocks": stack(enc),
        "dec_blocks": stack(dec),
        "enc_final_ln": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
        "dec_final_ln": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
    }


def t5_param_specs(cfg: T5Config):
    """Megatron TP layout (column-parallel over heads / mlp columns,
    row-parallel back): same convention as transformer.param_specs."""
    tp = cfg.tp_axis
    rep = P()
    lead = P(None)
    enc = {
        "ln1": {"scale": lead, "bias": lead},
        "qkv": P(None, None, None, tp, None),
        "attn_out": P(None, tp, None),
        "ln2": {"scale": lead, "bias": lead},
        "mlp_in": P(None, None, tp), "mlp_in_b": P(None, tp),
        "mlp_out": P(None, tp, None), "mlp_out_b": lead,
    }
    dec = dict(enc)
    dec.update({
        "lnx": {"scale": lead, "bias": lead},
        "xq": P(None, None, tp, None),
        "xkv": P(None, None, None, tp, None),
        "x_out": P(None, tp, None),
    })
    specs = {
        "embed": ({"tok": rep} if cfg.relative
                  else {"tok": rep, "pos": rep}),
        "enc_blocks": enc,
        "dec_blocks": dec,
        "enc_final_ln": {"scale": rep, "bias": rep},
        "dec_final_ln": {"scale": rep, "bias": rep},
    }
    if cfg.relative:
        # bucket tables shard over HEADS like qkv's head axis, so each
        # TP rank computes the bias for exactly its local heads
        specs["enc_rel_bias"] = P(None, tp)
        specs["dec_rel_bias"] = P(None, tp)
    return specs


# ------------------------------------------------------ relative positions
# (shared with the Pallas kernels — byteps_tpu/ops/relpos.py; re-exported
# here for the model-facing API and backward compatibility)

from ..ops.relpos import relative_bias, relative_position_bucket  # noqa: E402,F401


# ------------------------------------------------------------------ layers

def _self_attention(x, blk, cfg: T5Config, causal: bool, rel_table=None):
    # local sibling of transformer._attention rather than a reuse: the
    # encoder/decoder pair varies ``causal`` per stack (the shared fn
    # reads it from its config) and T5 has no sp_axis/ring branch
    b, s, _ = x.shape
    qkv = jnp.einsum("bsh,hcnd->bscnd", x, blk["qkv"].astype(x.dtype))
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    from ..ops.flash_attention import attention
    # T5's convention: no 1/sqrt(d) score scaling in relative mode;
    # the [nb, heads] stack table rides the flash kernels' in-kernel
    # rel-bias input ([heads, nb] layout) — no [h, s, s] bias in HBM,
    # so relative-bias self-attention stays O(s) memory at any length
    scale = 1.0 if cfg.relative else None
    out = attention(q, k, v, causal=causal, impl=cfg.attn_impl,
                    scale=scale,
                    rel_table=None if rel_table is None else rel_table.T,
                    rel_bidirectional=not causal,
                    rel_max_distance=cfg.rel_max_distance)
    out = out.reshape(b, s, -1)
    out = out @ blk["attn_out"].astype(x.dtype)
    if cfg.tp_axis is not None:
        out = jax.lax.psum(out, cfg.tp_axis)
    return out


def _cross_attention(x, memory, blk, cfg: T5Config):
    """q from the decoder stream [b, sq, h]; k/v from the encoder
    memory [b, sk, h] — MISMATCHED lengths on the flash path (the
    kernels' tiling contract is per-axis), so a long encoder never
    materializes the O(sq·sk) score matrix in HBM. T5 applies no
    position bias to cross-attention."""
    dt = x.dtype
    q = jnp.einsum("bsh,hnd->bsnd", x, blk["xq"].astype(dt))
    kv = jnp.einsum("bth,hcnd->btcnd", memory.astype(dt),
                    blk["xkv"].astype(dt))
    k, v = kv[:, :, 0], kv[:, :, 1]
    from ..ops.flash_attention import attention
    out = attention(q, k, v, causal=False, impl=cfg.attn_impl,
                    scale=(1.0 if cfg.relative else None))
    out = out.reshape(*x.shape[:2], -1) @ blk["x_out"].astype(dt)
    if cfg.tp_axis is not None:
        out = jax.lax.psum(out, cfg.tp_axis)
    return out


def _enc_block(x, blk, cfg: T5Config, rel_table=None):
    x = x + _self_attention(
        _layernorm(x, blk["ln1"]["scale"], blk["ln1"]["bias"]),
        blk, cfg, False, rel_table=rel_table)
    # transformer._mlp reads only cfg.tp_axis, which T5Config has
    return x + _mlp(_layernorm(x, blk["ln2"]["scale"], blk["ln2"]["bias"]),
                    blk, cfg)


def _dec_block(x, memory, blk, cfg: T5Config, rel_table=None):
    x = x + _self_attention(
        _layernorm(x, blk["ln1"]["scale"], blk["ln1"]["bias"]),
        blk, cfg, True, rel_table=rel_table)
    x = x + _cross_attention(
        _layernorm(x, blk["lnx"]["scale"], blk["lnx"]["bias"]),
        memory, blk, cfg)
    return x + _mlp(_layernorm(x, blk["ln2"]["scale"], blk["ln2"]["bias"]),
                    blk, cfg)


# ------------------------------------------------------------------ model

def _embed(params, cfg: T5Config, tokens):
    dt = jnp.dtype(cfg.dtype)
    s = tokens.shape[1]
    x = embed_lookup(params["embed"]["tok"], tokens, dt)
    if not cfg.relative:
        x = x + params["embed"]["pos"][:s].astype(dt)
    return x


def encode(params, cfg: T5Config, src_tokens: jnp.ndarray) -> jnp.ndarray:
    """Encoder memory [b, s_src, hidden]."""
    x = _embed(params, cfg, src_tokens)
    # the [nb, heads] table is closed over by every scan step — T5's
    # shared-across-layers bias; the kernels expand it per block
    rel = params["enc_rel_bias"] if cfg.relative else None
    fn = partial(_enc_block, cfg=cfg, rel_table=rel)
    if cfg.remat:
        fn = jax.checkpoint(fn)

    def body(carry, blk):
        return fn(carry, blk), None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return _layernorm(x, params["enc_final_ln"]["scale"],
                      params["enc_final_ln"]["bias"])


def decode(params, cfg: T5Config, tgt_tokens: jnp.ndarray,
           memory: jnp.ndarray) -> jnp.ndarray:
    """Decoder hidden states [b, s_tgt, hidden] (teacher forcing)."""
    x = _embed(params, cfg, tgt_tokens)
    rel = params["dec_rel_bias"] if cfg.relative else None
    fn = partial(_dec_block, cfg=cfg, rel_table=rel)
    if cfg.remat:
        fn = jax.checkpoint(fn)
    x, _ = jax.lax.scan(lambda c, b: (fn(c, memory, b), None), x,
                        params["dec_blocks"])
    return _layernorm(x, params["dec_final_ln"]["scale"],
                      params["dec_final_ln"]["bias"])


def seq2seq_loss(params, cfg: T5Config, batch: Tuple) -> jnp.ndarray:
    """Teacher-forced next-token CE: ``batch = (src, tgt)``; the decoder
    sees tgt[:-1] and predicts tgt[1:] (position 0 acts as BOS).
    Tied-embedding head, fp32 log-softmax."""
    src, tgt = batch
    memory = encode(params, cfg, src)
    hidden = decode(params, cfg, tgt[:, :-1], memory)
    dt = jnp.dtype(cfg.dtype)
    logits = jnp.einsum("bsh,vh->bsv", hidden.astype(dt),
                        params["embed"]["tok"].astype(dt),
                        preferred_element_type=jnp.float32)
    labels = tgt[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return nll.mean()


def synth_seq2seq_batch(rng: np.random.RandomState, batch: int,
                        src_len: int, tgt_len: int, vocab: int):
    """Synthetic copy-task data: target = source prefix (learnable
    structure, so convergence tests mean something)."""
    src = rng.randint(1, vocab, size=(batch, src_len)).astype(np.int32)
    tgt = np.concatenate(
        [np.zeros((batch, 1), np.int32),                 # BOS
         src[:, : tgt_len - 1]], axis=1).astype(np.int32)
    return src, tgt

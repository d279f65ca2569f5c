"""BERT model family — the flagship benchmark config (reference headline:
BERT-large scaling on 256 GPUs, README.md:37-44).

MLM objective on the shared transformer core. ``bert_large()`` matches the
reference benchmark's geometry (24×1024×16, seq 512, mixed precision).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .transformer import (TransformerConfig, apply, init_params, lm_loss,
                          logits, param_specs)


def bert_config(hidden=1024, layers=24, heads=16, vocab_size=30522,
                max_seq=512, dtype="bfloat16", **kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=vocab_size, hidden=hidden,
                             layers=layers, heads=heads, mlp_dim=4 * hidden,
                             max_seq=max_seq, causal=False, dtype=dtype, **kw)


def bert_large(**kw) -> TransformerConfig:
    return bert_config(hidden=1024, layers=24, heads=16, **kw)


def bert_base(**kw) -> TransformerConfig:
    return bert_config(hidden=768, layers=12, heads=12, **kw)


def bert_tiny(**kw) -> TransformerConfig:
    """Test-sized config."""
    return bert_config(hidden=64, layers=2, heads=4, vocab_size=128,
                       max_seq=64, dtype="float32", remat=False, **kw)


def mlm_loss(params, cfg: TransformerConfig, batch,
             max_predictions: Optional[int] = None):
    """batch = (masked_tokens, targets) with targets < 0 at unmasked
    positions (standard MLM convention).

    ``max_predictions``: gather up to K masked positions per sequence and
    run the LM head only on those (the standard max_predictions_per_seq
    trick) — with 15% masking the full-sequence head is ~6× wasted MXU
    work and a [b, s, vocab] fp32 activation. Exact as long as no
    sequence has more than K masked positions; sequences over the cap
    drop their latest-position extras. None = full-sequence head (used
    under SP/PP, where hidden states are sequence-sharded)."""
    if max_predictions is None or cfg.sp_axis is not None \
            or cfg.pp_axis is not None:
        return lm_loss(params, cfg, batch)
    tokens, targets = batch
    b, s = tokens.shape
    k = min(max_predictions, s)
    h = apply(params, cfg, tokens)                      # [b, s, hid]
    return _mlm_head(params, cfg, h, targets, k)


@jax.named_scope("bps.head")
def _mlm_head(params, cfg: TransformerConfig, h, targets, k: int):
    """The LM head and masked NLL on the ``k`` gathered positions."""
    s = h.shape[1]
    mask = targets >= 0
    # masked positions first; earlier positions win ties/cap overflow
    score = mask.astype(jnp.float32) * 2.0 - jnp.arange(s) / s
    _, idx = jax.lax.top_k(score, k)                    # [b, k]
    sel_h = jnp.take_along_axis(h, idx[..., None], axis=1)
    sel_t = jnp.take_along_axis(targets, idx, axis=1)
    w = jnp.take_along_axis(mask, idx, axis=1)
    lg = logits(params, cfg, sel_h)                     # [b, k, vocab]
    logp = jax.nn.log_softmax(lg, axis=-1)
    tgt = jnp.where(w, sel_t, 0)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    nll_sum = (nll * w).sum()
    cnt = w.sum().astype(jnp.float32)
    return nll_sum / jnp.maximum(cnt, 1.0)


def synth_mlm_batch(rng: np.random.RandomState, batch: int, seq: int,
                    vocab: int, mask_frac: float = 0.15, mask_id: int = 0):
    """Synthetic MLM data (the reference benchmarks use synthetic inputs,
    example/pytorch/benchmark_byteps.py)."""
    tokens = rng.randint(1, vocab, size=(batch, seq)).astype(np.int32)
    mask = rng.rand(batch, seq) < mask_frac
    targets = np.where(mask, tokens, -1).astype(np.int32)
    masked = np.where(mask, mask_id, tokens).astype(np.int32)
    return masked, targets

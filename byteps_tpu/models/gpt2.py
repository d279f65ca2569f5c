"""GPT-2 model family (reference benchmark config: GPT-2 medium with
DistributedDataParallel + cross-barrier, BASELINE.json configs)."""

from __future__ import annotations

import numpy as np

from .transformer import TransformerConfig, lm_loss


def gpt2_config(hidden=1024, layers=24, heads=16, vocab_size=50257,
                max_seq=1024, dtype="bfloat16", **kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=vocab_size, hidden=hidden,
                             layers=layers, heads=heads, mlp_dim=4 * hidden,
                             max_seq=max_seq, causal=True, dtype=dtype, **kw)


def gpt2_medium(**kw) -> TransformerConfig:
    return gpt2_config(hidden=1024, layers=24, heads=16, **kw)


def gpt2_small(**kw) -> TransformerConfig:
    return gpt2_config(hidden=768, layers=12, heads=12, **kw)


def gpt2_tiny(**kw) -> TransformerConfig:
    return gpt2_config(hidden=64, layers=2, heads=4, vocab_size=128,
                       max_seq=64, dtype="float32", remat=False, **kw)


def causal_lm_loss(params, cfg: TransformerConfig, batch):
    """batch = tokens [b, s]; next-token prediction.

    Under sequence parallelism the local shard must NOT be shifted in
    isolation (that would drop one target per shard boundary and misalign
    global positions). Instead each shard keeps its full token block as
    input and borrows the next shard's first token as its final target via
    ppermute; the globally-last position is masked out.
    """
    import jax

    tokens = batch
    if cfg.sp_axis is None:
        # Keep the FULL sequence as input and mask the last target instead
        # of shifting to s-1: identical loss (positions < s-1 attend only
        # backwards, position s-1's prediction is ignored either way), but
        # s stays a multiple of 128 so the flash-attention kernels stay
        # eligible — a s-1 shift silently fell back to the O(s²) naive
        # path (28x slower at seq 8k, OOM at 16k).
        import jax.numpy as jnp
        with jax.named_scope("bps.head"):      # the head's targets
            targets = jnp.concatenate(
                [tokens[:, 1:],
                 jnp.full((tokens.shape[0], 1), -1, tokens.dtype)], axis=1)
        return lm_loss(params, cfg, (tokens, targets))

    sp = jax.lax.axis_size(cfg.sp_axis)
    idx = jax.lax.axis_index(cfg.sp_axis)
    # first token of the *next* shard arrives from rank r+1
    perm = [(i, (i - 1) % sp) for i in range(sp)]
    next_first = jax.lax.ppermute(tokens[:, :1], cfg.sp_axis, perm)
    targets = jax.numpy.concatenate([tokens[:, 1:], next_first], axis=1)
    # globally-last position has no next token: mask it on the last rank
    is_last = (idx == sp - 1)
    last_col_masked = jax.numpy.where(is_last, -1, targets[:, -1:])
    targets = jax.numpy.concatenate([targets[:, :-1], last_col_masked], axis=1)
    return lm_loss(params, cfg, (tokens, targets))


def synth_lm_batch(rng: np.random.RandomState, batch: int, seq: int, vocab: int):
    return rng.randint(1, vocab, size=(batch, seq)).astype(np.int32)

"""Long-context training benchmark: tokens/sec vs sequence length.

Additive scope over the reference (SURVEY §5: long-context entirely
absent there): GPT-style causal LM training at long sequence lengths via
the Pallas flash-attention kernels, with ring attention over a ``seq``
mesh axis when one is present (--sp N).

Usage:
  python examples/long_context_bench.py --model gpt2-small \
      --seqs 2048,8192,32768
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/long_context_bench.py --model gpt2-tiny --sp 4 \
      --seqs 256,512 --tokens-per-step 512
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from functools import partial

import jax
import numpy as np
import optax

import _bootstrap  # noqa: F401

MODELS = {"gpt2-small": "gpt2_small", "gpt2-medium": "gpt2_medium",
          "gpt2-tiny": "gpt2_tiny"}


def measure(model: str, seq: int, tokens_per_step: int, sp: int,
            iters: int) -> float:
    from byteps_tpu.models import gpt2, transformer

    cfg = dataclasses.replace(
        getattr(gpt2, MODELS[model])(), max_seq=seq,
        sp_axis="seq" if sp > 1 else None,
        # past 16k the [s, vocab] logits dominate HBM (13 GB at 64k) —
        # chunked cross-entropy keeps the head at O(chunk·vocab)
        lm_head_chunk=2048 if seq > 16384 else 0)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    batch = max(1, tokens_per_step // seq)
    data = gpt2.synth_lm_batch(np.random.RandomState(0), batch, seq,
                               cfg.vocab_size)
    tx = optax.adamw(1e-4)

    if sp > 1:
        from byteps_tpu.models.transformer import param_specs
        from byteps_tpu.parallel.mesh import make_mesh
        from byteps_tpu.training import ShardedTrainer
        mesh = make_mesh({"seq": sp}, devices=jax.devices()[:sp])
        tr = ShardedTrainer(lambda p, b: gpt2.causal_lm_loss(p, cfg, b),
                            params, param_specs(cfg), tx, mesh=mesh)
        step = lambda b: tr.step(b)
    else:
        @partial(jax.jit, donate_argnums=(0, 1))
        def _step(p, s, b):
            l, g = jax.value_and_grad(
                lambda p, b: gpt2.causal_lm_loss(p, cfg, b))(p, b)
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s, l

        state = [tx.init(params), params]

        def step(b):
            state[1], state[0], l = _step(state[1], state[0], b)
            return l

    for _ in range(2):
        l = step(data)
    float(l)
    t0 = time.perf_counter()
    for _ in range(iters):
        l = step(data)
    float(l)
    return batch * seq * iters / (time.perf_counter() - t0)


def measure_t5(enc_len: int, dec_len: int, iters: int,
               naive_cap: int) -> dict:
    """T5-small seq2seq TRAINING step, long source document -> short
    target (the summarization regime): tokens/sec with the in-kernel
    relative-position flash path vs the materialized-bias baseline
    (``attn_impl="naive"`` computes relative_bias as an [h, s, s]
    array — 2.1 GB at 8k, 34 GB at 32k, the form the O(s) in-kernel
    path exists to avoid; VERDICT r4 #8)."""
    from byteps_tpu.models import t5 as t5m

    row = {"enc_len": enc_len, "dec_len": dec_len}
    for arm, impl in (("flash", "auto"), ("naive", "naive")):
        if arm == "naive" and enc_len > naive_cap:
            continue                      # materialized bias blows HBM
        cfg = t5m.t5_small(max_seq=max(enc_len, dec_len),
                           attn_impl=impl)
        params = t5m.init_t5_params(jax.random.PRNGKey(0), cfg)
        data = t5m.synth_seq2seq_batch(np.random.RandomState(0), 1,
                                       enc_len, dec_len + 1,
                                       cfg.vocab_size)
        tx = optax.adamw(1e-4)

        @partial(jax.jit, donate_argnums=(0, 1))
        def _step(p, s, b, cfg=cfg):
            l, g = jax.value_and_grad(
                lambda p, b: t5m.seq2seq_loss(p, cfg, b))(p, b)
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s, l

        state = tx.init(params)
        try:
            for _ in range(2):
                params, state, l = _step(params, state, data)
            float(l)
            t0 = time.perf_counter()
            for _ in range(iters):
                params, state, l = _step(params, state, data)
            float(l)
            tps = (enc_len + dec_len) * iters / (time.perf_counter() - t0)
            row[f"{arm}_tokens_per_s"] = round(tps, 1)
        except Exception as e:   # noqa: BLE001 — OOM is a data point
            row[f"{arm}_error"] = f"{type(e).__name__}"[:80]
        del params, state
        import gc
        gc.collect()
    if "flash_tokens_per_s" in row and "naive_tokens_per_s" in row:
        row["speedup"] = round(row["flash_tokens_per_s"]
                               / row["naive_tokens_per_s"], 2)
    return row


def measure_cross(enc_len: int, dec_len: int, heads: int, d: int,
                  iters: int, naive_cap: int) -> dict:
    """T5-style cross-attention (round 4): ``dec_len`` queries over an
    ``enc_len`` encoder memory, fwd+bwd, flash vs naive einsum. The
    flash path never materializes the [sq, sk] scores in HBM — the
    long-encoder seq2seq enabler (summarization at 8k+ source)."""
    import jax.numpy as jnp

    from byteps_tpu.ops.flash_attention import (flash_attention,
                                                local_attention)

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, dec_len, heads, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, enc_len, heads, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (1, enc_len, heads, d), jnp.bfloat16)

    def bench(fn) -> float:
        g = jax.jit(jax.grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2)))
        r = g(q, k, v)
        float(r[0].sum())                    # real readback
        t0 = time.perf_counter()
        for _ in range(iters):
            r = g(q, k, v)
        float(r[0].sum())
        return (time.perf_counter() - t0) / iters * 1e3

    row = {"enc_len": enc_len, "dec_len": dec_len,
           "flash_ms": round(bench(flash_attention), 2)}
    if enc_len <= naive_cap:                 # [h, sq, sk] fp32 blowup
        row["naive_ms"] = round(bench(local_attention), 2)
        row["speedup"] = round(row["naive_ms"] / row["flash_ms"], 2)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2-small", choices=sorted(MODELS))
    ap.add_argument("--seqs", default="2048,4096,8192,16384,32768")
    ap.add_argument("--tokens-per-step", type=int, default=8192)
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel (ring) shards")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--cross-encoder", action="store_true",
                    help="bench T5 cross-attention: --dec-len queries "
                         "over encoder memories of --seqs lengths")
    ap.add_argument("--t5", action="store_true",
                    help="bench the full T5 seq2seq TRAIN step: long "
                         "source (--seqs) -> --dec-len target, in-kernel "
                         "relative bias vs materialized-bias baseline")
    ap.add_argument("--dec-len", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--naive-cap", type=int, default=16384,
                    help="skip the naive einsum arm above this encoder "
                         "length (its [sq,sk] scores blow HBM)")
    args = ap.parse_args()

    if args.t5:
        rows = []
        for enc in (int(s) for s in args.seqs.split(",")):
            row = measure_t5(enc, args.dec_len, args.iters,
                             args.naive_cap)
            rows.append(row)
            f = row.get("flash_tokens_per_s")
            n = row.get("naive_tokens_per_s")
            print(f"enc={enc:7d} dec={args.dec_len}  "
                  f"flash={f if f is not None else row.get('flash_error')}"
                  f"  naive={n if n is not None else row.get('naive_error', '—')}"
                  f"  tokens/s", flush=True)
        ok = [r["flash_tokens_per_s"] for r in rows
              if "flash_tokens_per_s" in r]
        print(json.dumps({"metric": "t5_long_seq2seq_tokens_per_sec",
                          "value": ok[-1] if ok else None,
                          "unit": "tokens/sec", "rows": rows}))
        return

    if args.cross_encoder:
        rows = []
        for enc in (int(s) for s in args.seqs.split(",")):
            row = measure_cross(enc, args.dec_len, args.heads,
                                args.head_dim, args.iters, args.naive_cap)
            rows.append(row)
            print(f"enc={enc:7d} dec={args.dec_len}  "
                  f"flash={row['flash_ms']:8.2f} ms  "
                  f"naive={row.get('naive_ms', float('nan')):8.2f} ms")
        print(json.dumps({"metric": "t5_cross_attention_flash_ms",
                          "value": rows[-1]["flash_ms"], "unit": "ms",
                          "rows": rows}))
        return

    rows = {}
    for seq in (int(s) for s in args.seqs.split(",")):
        tps = measure(args.model, seq, args.tokens_per_step, args.sp,
                      args.iters)
        rows[str(seq)] = round(tps)
        print(f"seq={seq:7d}  tokens/sec={tps:12.0f}")
    print(json.dumps({"metric": f"{args.model}_long_context_tokens_per_sec",
                      "value": rows[max(rows, key=int)], "unit": "tokens/sec",
                      "by_seq": rows, "sp": args.sp}))


if __name__ == "__main__":
    main()

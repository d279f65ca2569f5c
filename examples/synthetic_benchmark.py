"""Synthetic throughput benchmark (reference:
example/pytorch/benchmark_byteps.py, example/tensorflow/synthetic_benchmark.py
— train a benchmark model on synthetic data, print img/sec or samples/sec).

Usage:
  python examples/synthetic_benchmark.py --model bert-large --batch 8
  python examples/synthetic_benchmark.py --model resnet50 --batch 32
  python examples/synthetic_benchmark.py --model mlp --compression onebit
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np
import optax

import _bootstrap  # noqa: F401  (repo-root sys.path shim)
import byteps_tpu as bps
from byteps_tpu.training import DistributedTrainer


def build(model: str, batch: int):
    rng = np.random.RandomState(0)
    if model.startswith("bert"):
        from byteps_tpu.models import bert, transformer
        cfg = {"bert-large": bert.bert_large, "bert-base": bert.bert_base,
               "bert-tiny": bert.bert_tiny}[model]()
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        seq = min(cfg.max_seq, 512)
        data = bert.synth_mlm_batch(rng, batch, seq, cfg.vocab_size)
        loss_fn = lambda p, b: bert.mlm_loss(p, cfg, b)
    elif model.startswith("gpt2"):
        from byteps_tpu.models import gpt2, transformer
        cfg = {"gpt2-medium": gpt2.gpt2_medium, "gpt2-small": gpt2.gpt2_small,
               "gpt2-tiny": gpt2.gpt2_tiny}[model]()
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        data = gpt2.synth_lm_batch(rng, batch, min(cfg.max_seq, 512),
                                   cfg.vocab_size)
        loss_fn = lambda p, b: gpt2.causal_lm_loss(p, cfg, b)
    elif model == "resnet50":
        from byteps_tpu.models import resnet
        params = resnet.init_resnet50(jax.random.PRNGKey(0))
        data = resnet.synth_imagenet_batch(rng, batch)
        loss_fn = resnet.resnet_loss
    elif model == "vgg16":
        from byteps_tpu.models import resnet, vgg
        params = vgg.init_vgg16(jax.random.PRNGKey(0))
        data = resnet.synth_imagenet_batch(rng, batch)
        loss_fn = vgg.vgg_loss
    elif model == "mlp":
        from byteps_tpu.models.mlp import mlp_init, mlp_loss
        params = mlp_init(jax.random.PRNGKey(0), 2048, 8)
        data = (rng.randn(batch, 2048).astype(np.float32),
                rng.randn(batch, 2048).astype(np.float32))
        loss_fn = mlp_loss
    elif model == "moe":
        from byteps_tpu.models import moe
        # GPT-2-small-sized backbone with 8 experts: the largest MoE whose
        # params + adam state fit one v5e chip (24-layer/1024-hidden x8
        # experts needs ~30 GB)
        cfg = moe.MoEConfig(num_experts=8, top_k=2, hidden=768, layers=12,
                            heads=12, mlp_dim=3072, causal=True)
        params = moe.init_moe_params(jax.random.PRNGKey(0), cfg)
        from byteps_tpu.models import gpt2 as _gpt2
        seq = min(cfg.max_seq, 512)
        tokens = _gpt2.synth_lm_batch(rng, batch, seq, cfg.vocab_size)
        targets = np.concatenate(
            [tokens[:, 1:], np.full((batch, 1), -1, np.int32)], axis=1)
        data = (tokens, targets)
        loss_fn = lambda p, b: moe.moe_lm_loss(p, cfg, b)
    elif model.startswith("t5"):
        from byteps_tpu.models import t5
        cfg = {"t5-small": t5.t5_small, "t5-tiny": t5.t5_tiny}[model]()
        params = t5.init_t5_params(jax.random.PRNGKey(0), cfg)
        src_len = min(cfg.max_seq, 256)
        data = t5.synth_seq2seq_batch(rng, batch, src_len,
                                      src_len // 2, cfg.vocab_size)
        loss_fn = lambda p, b: t5.seq2seq_loss(p, cfg, b)
    else:
        raise SystemExit(f"unknown model {model}")
    return params, data, loss_fn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="bert-tiny")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--compression", default=None,
                    help="onebit|topk|randomk|dithering")
    ap.add_argument("--ef", action="store_true", help="error feedback")
    ap.add_argument("--barrier", action="store_true",
                    help="force a host readback every step (no async "
                         "dispatch overlap — the reference's pre-"
                         "cross-barrier behavior, docs/cross-barrier.md)")
    args = ap.parse_args()

    bps.init()
    params, data, loss_fn = build(args.model, args.batch)
    compression = None
    if args.compression:
        compression = {"compressor_type": args.compression,
                       "compressor_k": "0.01", "seed": "42"}
        if args.ef:
            compression["ef_type"] = "vanilla"

    trainer = DistributedTrainer(loss_fn, params, optax.adamw(1e-4),
                                 compression=compression)
    # Pre-place the batch: this benchmark measures model+sync throughput;
    # input upload overlaps via data.prefetch_to_mesh in real training
    # (and would dominate artificially on a slow host link).
    data = trainer.shard_batch(data)
    float(trainer.step(data))   # compile + sync
    for _ in range(2):
        trainer.step(data)      # wash out first-launch slow path
    float(trainer.step(data))
    t0 = time.perf_counter()
    for _ in range(args.iters):
        loss = trainer.step(data)
        if args.barrier:
            float(loss)         # per-step sync barrier
    final = float(loss)         # readback: the timed window ends when
                                # the last step's loss is on the host
    dt = time.perf_counter() - t0
    print(f"model={args.model} batch={args.batch} world={bps.size()} "
          f"compression={args.compression or 'none'}: "
          f"{args.batch * args.iters / dt:.1f} samples/sec  loss={final:.4f}")
    bps.shutdown()


if __name__ == "__main__":
    main()

"""Analytic model-FLOP accounting for MFU reporting.

MFU (model FLOPs utilization) follows the standard convention (PaLM
appendix B): count only the FLOPs the MODEL requires — matmuls of the
forward pass, ×3 for training (backward ≈ 2× forward) — and divide by
chip peak. Rematerialization recompute, embedding gathers, and
elementwise ops are excluded, so MFU is comparable across
implementations and honest about recompute overhead (a fully-rematted
step executes ~4/3× the counted FLOPs and its MFU shows that cost).

The reference never reports absolute efficiency (its benchmarks are
ratios vs Horovod, README.md:37-46, docs/performance.md); BENCH JSON
lines here carry ``tflops``/``mfu`` alongside the throughput so "1.0×
vs baseline" can't hide an underutilized chip.
"""

from __future__ import annotations

from typing import Optional


def transformer_fwd_flops_per_sample(cfg, seq: int,
                                     lm_positions: Optional[int] = None
                                     ) -> float:
    """Matmul FLOPs of one forward pass of one sample.

    Per layer: QKV 6·s·h², attn-out 2·s·h², scores+AV 4·s²·h (causal
    models still count the full square — the standard convention, and our
    flash kernel computes it for the bidirectional case anyway), MLP
    2·s·h·m×2. LM head: 2·p·h·vocab over ``lm_positions`` p (MLM: only
    masked positions go through the head; LM: p = s).
    """
    h, m, s = cfg.hidden, cfg.mlp_dim, seq
    p = s if lm_positions is None else lm_positions
    per_layer = 8 * s * h * h + 4 * s * h * m + 4 * s * s * h
    return float(cfg.layers * per_layer + 2 * p * h * cfg.vocab_size)


def transformer_train_flops_per_sample(cfg, seq: int,
                                       lm_positions: Optional[int] = None
                                       ) -> float:
    """fwd + bwd ≈ 3× fwd (backward is two matmuls per forward matmul)."""
    return 3.0 * transformer_fwd_flops_per_sample(cfg, seq, lm_positions)


# bf16 peak matmul throughput per chip, FLOP/s. Sources: public TPU
# system specs (cloud.google.com/tpu/docs/system-architecture).
_CHIP_PEAK = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,     # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,          # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,     # v6e / Trillium
    "TPU v6e": 918e12,
}


def chip_peak_flops(device=None) -> float:
    """Peak bf16 FLOP/s of ``device`` (default: first JAX device). A
    ``device_kind`` that is not in the table is an error, not a default:
    MFU against a guessed peak is not a measurement."""
    import jax
    d = device if device is not None else jax.devices()[0]
    kind = d.device_kind
    if kind in _CHIP_PEAK:
        return _CHIP_PEAK[kind]
    for name, peak in _CHIP_PEAK.items():   # prefix match ("TPU v5 lite …")
        if kind.startswith(name):
            return peak
    raise ValueError(
        f"device_kind {kind!r} ({d.platform}) is not in the peak-FLOPs "
        f"table of models/flops.py; add it with its published source")

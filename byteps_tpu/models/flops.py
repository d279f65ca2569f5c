"""Peak bf16 FLOP/s of the chips this package may meet (``chip_smoke.py``
reports it with the device). What a step requires is counted with the
instrument: ``benchmark/flops.py``.
"""

from __future__ import annotations

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

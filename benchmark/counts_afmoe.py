"""Required operations of the ``afmoe`` share (configuration
``trinity_mini_lm``), by the benchmark's own count: a training step's
operations a token (``flops_per_token``: the configuration's
``flops_rule``) and each kernel's operations and bytes a call
(``kernel_counts``). ``flops.py`` has the rules of what counts: the
forward's matrix products times 3, no recomputation, no gathers.

The routed rows are counted at their MEAN. A token chooses ``top_k`` of
``router_outputs`` experts, of which ``experts_held`` are held here, so a
token sends ``top_k * experts_held / router_outputs`` rows to the experts
held (one, in ``trinity_mini_lm``). The configuration's balanced choice
(``sizes["balanced"]``) holds a step's real count at that mean to a few
per cent, whatever the seed and the step (PERF.md section 6 has the rows
counted on the chip); a reader of a trace is given neither the seed nor
the trainer, so it cannot count a traced step's own rows. The grouped
products' calls are held to that many rows and to each expert's weights
moved once.
"""

from __future__ import annotations

from benchmark import kernel_counts as flash

GMM_KERNELS = ("bps_gmm", "bps_gmm_dx", "bps_gmm_dw")


def window_keys(seq: int, window: int) -> float:
    """Keys a query of a causal band attends, on average over a row:
    ``(w (w + 1) / 2 + (s - w) w) / s = w - w (w - 1) / (2 s)``."""
    w = min(window, seq)
    return w - w * (w - 1) / (2.0 * seq)


def routed_rows_per_token(sizes: dict) -> float:
    return sizes["top_k"] * sizes["experts_held"] / sizes["router_outputs"]


def layer_forward(sizes: dict, kind: str, seq: int) -> float:
    """FLOPs a token of one layer's forward: q, k, v, the gate and the
    output projection; scores and weighted values over the keys attended;
    the dense gated MLP (3 products of h x m), or the router, the shared
    expert and the mean routed rows' experts."""
    h, d = sizes["hidden"], sizes["head_dim"]
    heads, kv = sizes["heads"], sizes["kv_heads"]
    keys = (window_keys(seq, sizes["window"]) if kind.endswith("sliding")
            else (seq + 1) / 2)
    attention = 2 * h * d * (3 * heads + 2 * kv) + 4 * heads * d * keys
    if kind.startswith("dense"):
        return attention + 6 * h * sizes["mlp_dim"]
    expert = 6 * h * sizes["moe_dim"]
    return (attention + 2 * h * sizes["router_outputs"]
            + expert * (sizes["shared_experts"]
                        + routed_rows_per_token(sizes)))


def flops_per_token(sizes: dict, seq: int, targets_per_row: int) -> float:
    """FLOPs of one training step per token: 3 x (the layers' forward +
    the head, 2 h x the vocabulary rows held, on the targeted
    positions)."""
    head = 2 * sizes["hidden"] * sizes["vocab_size"] * targets_per_row / seq
    return 3.0 * (sum(layer_forward(sizes, kind, seq)
                      for kind in sizes["layer_kinds"]) + head)


def gmm_call(kernel: str, rows: float, k: int, n: int, groups: int,
             itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one grouped product over ``rows`` rows
    of [.., k] against ``groups`` weights [k, n]: 2 rows k n operations
    whichever of the three it is; its two operands and its result moved
    once (``bps_gmm``: rows k, weights, rows n; ``bps_gmm_dx``: rows n,
    weights, rows k; ``bps_gmm_dw``: rows k, rows n, weights)."""
    if kernel not in GMM_KERNELS:
        raise ValueError(f"no grouped product {kernel!r}")
    return {"flops": 2.0 * rows * k * n,
            "bytes": float((rows * (k + n) + groups * k * n) * itemsize)}


def kernel_counts(sizes: dict, mix: dict) -> dict:
    """Each kernel's kinds of call in a step, one entry a kind with how
    many of it a period of the layer pattern holds. Flash: a call a layer,
    a band on the ``*_sliding`` layers, the triangle on the others (the
    forward kernel twice each: forward and recompute, which doubles both
    and keeps the ratio). Grouped products, a routed layer: up [h, 2 m]
    and down [m, h]; ``bps_gmm`` runs both again as the recompute (the
    backward of the weighted sum reads the experts' results)."""
    batch, seq = mix["batch_per_chip"], mix["seq"]
    sliding = sum(k.endswith("sliding") for k in sizes["layer_kinds"])
    full = len(sizes["layer_kinds"]) - sliding

    def flash_kinds(kernel):
        def call(window):
            return flash.flash_call(
                kernel, batch, sizes["heads"], seq, sizes["head_dim"], True,
                kv_heads=sizes["kv_heads"], window=window)
        return [dict(call(sizes["window"]), calls=sliding),
                dict(call(None), calls=full)]

    counts = {kernel: flash_kinds(kernel) for kernel in flash.KERNELS}
    rows = batch * seq * routed_rows_per_token(sizes)
    h, m, held = sizes["hidden"], sizes["moe_dim"], sizes["experts_held"]
    for kernel in GMM_KERNELS:
        counts[kernel] = [
            dict(gmm_call(kernel, rows, h, 2 * m, held), calls=1),
            dict(gmm_call(kernel, rows, m, h, held), calls=1)]
    return counts

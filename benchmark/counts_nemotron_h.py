"""Required operations of the ``nemotron_h`` share (configuration
``nemotron3_nano_lm``), by the benchmark's own count: a training step's
operations a token (``flops_per_token``: the configuration's
``flops_rule``), each flash kernel's and grouped product's operations
and bytes a call (``kernel_counts``) and the state-space scan's a step
(``scan_count``).
``flops.py`` has the rules of what counts: the forward's matrix products
times 3, no recomputation, no gathers, no elementwise work.

The state-space scan is counted at what the recurrence itself needs a
head and position, whatever form a program gives it: the step's outer
product into the state, ``dt x B^T`` (2 p n), and the state's product
with ``C`` (2 p n). The chunked form does more (``C B^T`` and its
weighted sum over a chunk besides): that is the program's choice, not
the model's requirement, so a share made from this count cannot pass
100 %. The decays are elementwise and not counted.

The routed rows are counted at their MEAN, ``top_k * experts_held /
router_outputs`` rows a token (0.375 here), which the configuration's
balanced choice holds a step to (``counts_afmoe.py`` has why a reader of
a trace cannot count a step's own).
"""

from __future__ import annotations

from benchmark import kernel_counts as flash
from benchmark.counts_afmoe import GMM_KERNELS, gmm_call


def routed_rows_per_token(sizes: dict) -> float:
    return sizes["top_k"] * sizes["experts_held"] / sizes["router_outputs"]


def scan_flops_per_token(sizes: dict) -> float:
    """The recurrence's own operations a position, forward."""
    return 4.0 * sizes["ssm_heads"] * sizes["ssm_head_dim"] * sizes[
        "ssm_state"]


def layer_forward(sizes: dict, kind: str, seq: int) -> float:
    """FLOPs a token of one layer's forward. ``ssm``: in_proj [h, inner +
    (inner + 2 groups n) + heads], out_proj [inner, h], the recurrence.
    ``attn``: q, k, v and the output projection; scores and weighted
    values over the triangle, (seq + 1) / 2 keys a query. ``moe``: the
    router, the shared expert and the mean routed rows' experts, each two
    products (not gated)."""
    h = sizes["hidden"]
    if kind == "ssm":
        inner = sizes["ssm_heads"] * sizes["ssm_head_dim"]
        into = 2 * inner + 2 * sizes["ssm_groups"] * sizes[
            "ssm_state"] + sizes["ssm_heads"]
        return 2 * h * into + 2 * inner * h + scan_flops_per_token(sizes)
    if kind == "attn":
        d, heads, kv = sizes["head_dim"], sizes["heads"], sizes["kv_heads"]
        return (2 * h * d * (2 * heads + 2 * kv)
                + 4 * heads * d * (seq + 1) / 2)
    if kind != "moe":
        raise ValueError(f"no count for a layer of kind {kind!r}")
    return (2 * h * sizes["router_outputs"]
            + 4 * h * sizes["shared_dim"] * sizes["shared_experts"]
            + 4 * h * sizes["moe_dim"] * routed_rows_per_token(sizes))


def flops_per_token(sizes: dict, seq: int, targets_per_row: int) -> float:
    """FLOPs of one training step per token: 3 x (the layers' forward +
    the head, 2 h x the vocabulary rows held, on the targeted
    positions)."""
    head = 2 * sizes["hidden"] * sizes["vocab_size"] * targets_per_row / seq
    return 3.0 * (sum(layer_forward(sizes, kind, seq)
                      for kind in sizes["layer_kinds"]) + head)


def kernel_counts(sizes: dict, mix: dict) -> dict:
    """Each kernel's kinds of call in a step. Flash: one kind, the causal
    triangle over grouped kv heads (32 query heads over 2), one call an
    ``attn`` layer. Grouped products, a ``moe`` layer: the experts are
    not gated, so up is [h, m] and down [m, h], at the mean routed rows
    and each held expert's weights moved once, as ``counts_afmoe`` counts
    a call (``bps_gmm`` runs both forward and again as the recompute,
    ``bps_gmm_dx`` and ``bps_gmm_dw`` once each backward: every kernel's
    calls a step are a whole multiple of the two kinds)."""
    batch, seq = mix["batch_per_chip"], mix["seq"]
    layers = sum(kind == "attn" for kind in sizes["layer_kinds"])
    counts = {kernel: [dict(flash.flash_call(
        kernel, batch, sizes["heads"], seq, sizes["head_dim"], True,
        kv_heads=sizes["kv_heads"]), calls=layers)]
        for kernel in flash.KERNELS}
    rows = batch * seq * routed_rows_per_token(sizes)
    h, m, held = sizes["hidden"], sizes["moe_dim"], sizes["experts_held"]
    for kernel in GMM_KERNELS:
        counts[kernel] = [dict(gmm_call(kernel, rows, h, m, held), calls=1),
                          dict(gmm_call(kernel, rows, m, h, held), calls=1)]
    return counts


def scan_count(sizes: dict, mix: dict, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` the step's state-space scans require: 3 x
    the recurrence's forward operations (a backward is two products for
    each one), and each operand and result crossing HBM once a pass.
    Forward: x [inner], B, C [groups n] and y [inner] in the compute
    dtype, dt [heads] float32. Backward: the same operands and y's
    cotangent in, the four cotangents out. No recomputation."""
    tokens = mix["batch_per_chip"] * mix["seq"]
    layers = sum(kind == "ssm" for kind in sizes["layer_kinds"])
    inner = sizes["ssm_heads"] * sizes["ssm_head_dim"]
    operands = (inner + 2 * sizes["ssm_groups"] * sizes["ssm_state"]
                ) * itemsize + sizes["ssm_heads"] * 4
    result = inner * itemsize
    return {"flops": 3.0 * scan_flops_per_token(sizes) * tokens * layers,
            "bytes": float((operands + result            # forward
                            + operands + result + operands  # backward
                            ) * tokens * layers)}

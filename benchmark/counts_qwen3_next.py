"""Required operations of the ``qwen3_next`` share (configuration
``qwen3_next_80b_a3b_lm``), by the benchmark's own count: a training
step's operations a token (``flops_per_token``: the configuration's
``flops_rule``), each flash kernel's and grouped product's operations and
bytes a call (``kernel_counts``) and the gated delta rule's a step
(``delta_count``).
``flops.py`` has the rules of what counts: the forward's matrix products
times 3, no recomputation, no gathers, no elementwise work.

The gated delta rule is counted at what the recurrence itself needs a
value head and position, whatever form a program gives it: the state's
product with the key, ``S^T k`` (2 dk dv), the rank-one update ``k d^T``
(2 dk dv) and the state's product with the query, ``S^T q`` (2 dk dv).
The chunked form does more (``k k^T`` and ``q k^T`` a chunk, the
triangular inverse, its products into ``U`` and ``W``): that is the
program's choice, not the model's requirement, so a share made from this
count cannot pass 100 %. The decays, the l2 norms and the gates are
elementwise and not counted.

The routed rows are counted at their MEAN, ``top_k * experts_held /
router_outputs`` rows a token (0.3125 here), which the configuration's
balanced choice holds a step to (``counts_afmoe.py`` has why a reader of
a trace cannot count a step's own).
"""

from __future__ import annotations

from benchmark import kernel_counts as flash
from benchmark.counts_afmoe import (GMM_KERNELS, gmm_call,
                                    routed_rows_per_token)


def delta_flops_per_token(sizes: dict) -> float:
    """The recurrence's own operations a position, forward."""
    return 6.0 * sizes["gdn_value_heads"] * sizes["gdn_head_dim"] ** 2


def routed_forward(sizes: dict) -> float:
    """FLOPs a token of a layer's routed half: the router, the shared
    expert (a gated MLP: three products) and its gate, the mean routed
    rows' experts."""
    h = sizes["hidden"]
    return (2 * h * sizes["router_outputs"] + 6 * h * sizes["shared_dim"]
            + 2 * h + 6 * h * sizes["moe_dim"] * routed_rows_per_token(sizes))


def layer_forward(sizes: dict, kind: str, seq: int) -> float:
    """FLOPs a token of one layer's forward. ``gdn_moe``: in_proj_qkvz
    [h, 2 key_dim + 2 value_dim], in_proj_ba [h, 2 value heads], out_proj
    [value_dim, h], the recurrence. ``gattn_moe``: q, the gate, k, v and
    the output projection; scores and weighted values over the triangle,
    (seq + 1) / 2 keys a query. Both: the routed half."""
    h = sizes["hidden"]
    if kind == "gdn_moe":
        d, hv = sizes["gdn_head_dim"], sizes["gdn_value_heads"]
        key_dim, value_dim = sizes["gdn_key_heads"] * d, hv * d
        mixer = (2 * h * (2 * key_dim + 2 * value_dim) + 2 * h * 2 * hv
                 + 2 * value_dim * h + delta_flops_per_token(sizes))
    elif kind == "gattn_moe":
        d, heads, kv = sizes["head_dim"], sizes["heads"], sizes["kv_heads"]
        mixer = (2 * h * d * (3 * heads + 2 * kv)
                 + 4 * heads * d * (seq + 1) / 2)
    else:
        raise ValueError(f"no count for a layer of kind {kind!r}")
    return mixer + routed_forward(sizes)


def flops_per_token(sizes: dict, seq: int, targets_per_row: int) -> float:
    """FLOPs of one training step per token: 3 x (the layers' forward +
    the head, 2 h x the vocabulary rows held, on the targeted
    positions)."""
    head = 2 * sizes["hidden"] * sizes["vocab_size"] * targets_per_row / seq
    return 3.0 * (sum(layer_forward(sizes, kind, seq)
                      for kind in sizes["layer_kinds"]) + head)


def kernel_counts(sizes: dict, mix: dict) -> dict:
    """Each kernel's kinds of call in a step. Flash: one kind, the causal
    triangle over grouped kv heads (16 query heads of 256 over 2), one
    call a ``gattn_moe`` layer. Grouped products, every layer's routed
    half: up [h, 2 m] and down [m, h] at the mean routed rows, each held
    expert's weights moved once, as ``counts_afmoe`` counts a call."""
    batch, seq = mix["batch_per_chip"], mix["seq"]
    layers = sum(kind == "gattn_moe" for kind in sizes["layer_kinds"])
    counts = {kernel: [dict(flash.flash_call(
        kernel, batch, sizes["heads"], seq, sizes["head_dim"], True,
        kv_heads=sizes["kv_heads"]), calls=layers)]
        for kernel in flash.KERNELS}
    rows = batch * seq * routed_rows_per_token(sizes)
    h, m, held = sizes["hidden"], sizes["moe_dim"], sizes["experts_held"]
    for kernel in GMM_KERNELS:
        counts[kernel] = [
            dict(gmm_call(kernel, rows, h, 2 * m, held), calls=1),
            dict(gmm_call(kernel, rows, m, h, held), calls=1)]
    return counts


def delta_count(sizes: dict, mix: dict, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` the step's gated delta rules require: 3 x
    the recurrence's forward operations (a backward is two products for
    each one), and each operand and result crossing HBM once a pass.
    Forward: q, k [key_dim], v and o [value_dim] in the compute dtype, g
    and beta [value heads] float32. Backward: the same operands and o's
    cotangent in, the five cotangents out. No recomputation; the same work
    whether kernels or XLA products do it."""
    tokens = mix["batch_per_chip"] * mix["seq"]
    layers = sum(kind == "gdn_moe" for kind in sizes["layer_kinds"])
    d, hv = sizes["gdn_head_dim"], sizes["gdn_value_heads"]
    key_dim, value_dim = sizes["gdn_key_heads"] * d, hv * d
    operands = (2 * key_dim + value_dim) * itemsize + 2 * hv * 4
    result = value_dim * itemsize
    return {"flops": 3.0 * delta_flops_per_token(sizes) * tokens * layers,
            "bytes": float((operands + result            # forward
                            + operands + result + operands  # backward
                            ) * tokens * layers)}

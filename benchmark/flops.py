"""Required operations of a training step, by the benchmark's own count.

Only what the model requires: the matrix products of the forward pass,
times 3 for training (the backward pass is two products for each one of
the forward). Recomputation, gathers and elementwise work are not
counted, a causal model's attention counts the lower triangle only, and
the head counts the targeted positions only. A share of the peak made
from this count cannot pass 100 %: no program can do the work in fewer
operations.

A configuration names its rule under ``flops_rule``: ``transformer_lm``
here, or ``module:function`` of a file of its own under the manifest's
``paths`` with the same arguments (``harness.named_count``).
"""

from __future__ import annotations


def transformer_lm(sizes: dict, seq: int, targets_per_row: int) -> float:
    """FLOPs of one training step per token of a pre-LN transformer LM.

    Per token and layer: qkv 6h², attention output 2h², MLP 4hm, scores
    and weighted values 4·h·(keys attended): ``seq`` keys, or the mean
    (seq + 1) / 2 of the lower triangle where the model is causal. The
    head: 2·h·vocab for each targeted position."""
    h, m = sizes["hidden"], sizes["mlp_dim"]
    keys = (seq + 1) / 2 if sizes["causal"] else seq
    layer = 8 * h * h + 4 * h * m + 4 * h * keys
    head = 2 * h * sizes["vocab_size"] * targets_per_row / seq
    return 3.0 * (sizes["layers"] * layer + head)


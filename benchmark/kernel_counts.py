"""Required operations and HBM bytes of ONE call of each flash-attention
kernel, by the benchmark's own count, from a cell's sizes.

Only what a call must do whatever its tiling: the matrix products that
lead from its inputs to its outputs, and each input and output moved
between HBM and the chip once. A causal call counts the lower triangle
only (``(seq + 1) / 2`` keys a query, as ``flops.py`` counts it), although
the kernels compute their diagonal blocks whole. Sizes are one chip's:
``batch`` is the rows a chip holds.

With ``u = 2 * batch * heads * seq * keys * head_dim`` (one product):

========================  ============================  =================
kernel                    products                      big tensors moved
========================  ============================  =================
``bps_flash_fwd``         s = q k', o = p v       (2u)  q k v, out
``bps_flash_bwd_fused``   s, dp, dv, dq, dk       (5u)  q k v do, dq dk dv
``bps_flash_bwd_dq``      s, dp, dq               (3u)  q k v do, dq
``bps_flash_bwd_dkv``     s, dp, dv, dk           (4u)  q k v do, dk dv
========================  ============================  =================

and the rows' statistics in float32 (``lse`` out of the forward, ``lse``
into every backward call, ``delta`` into the split ones). The split
backward's two calls come to 7u where the fused one needs 5u: each is
held to what it alone must compute, so the split's second ``s`` and ``dp``
count as work of those calls, not as waste.

A share of a roofline made from these counts cannot pass 100 %: no kernel
can do a call's work in fewer operations or bytes.
"""

from __future__ import annotations

# (matrix products, big tensors, float32 row statistics) of one call
KERNELS = {
    "bps_flash_fwd": (2, 4, 1),
    "bps_flash_bwd_fused": (5, 7, 1),
    "bps_flash_bwd_dq": (3, 5, 2),
    "bps_flash_bwd_dkv": (4, 6, 2),
}


def flash_call(kernel: str, batch: int, heads: int, seq: int, head_dim: int,
               causal: bool, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one call of ``kernel`` on
    ``[batch, heads, seq, head_dim]`` operands of ``itemsize`` bytes."""
    products, tensors, stats = KERNELS[kernel]
    keys = (seq + 1) / 2 if causal else seq
    rows = batch * heads * seq
    return {"flops": products * 2.0 * rows * keys * head_dim,
            "bytes": float(tensors * rows * head_dim * itemsize
                           + stats * rows * 4)}


def of_cell(sizes: dict, mix: dict) -> dict:
    """The counts of every kernel at a cell's sizes (its configuration's
    ``sizes`` and its traffic mix), on one chip, in bfloat16."""
    return {kernel: flash_call(kernel, mix["batch_per_chip"], sizes["heads"],
                               mix["seq"], sizes["hidden"] // sizes["heads"],
                               sizes["causal"])
            for kernel in KERNELS}


def least_seconds(count: dict, peaks: dict) -> tuple:
    """(the least seconds a chip with ``peaks`` could take for a call,
    which bound sets it: ``"flops"`` or ``"hbm"``)."""
    by_flops = count["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = count["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "hbm")

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

What a call's least work depends on besides: ``head_dim`` is given, not
derived from a hidden size; with ``kv_heads`` fewer than ``heads`` the
tensors on the key side (k, v, dk, dv) move once a kv head; under a causal
band of ``window`` keys a query attends ``window`` keys where its row is
past the band and the triangle before, ``w*s - w*(w-1)/2`` pairs a head.

A count function (``of_cell`` here, or the one a configuration names under
``kernel_counts``) returns for each kernel one ``{"flops", "bytes"}`` where
every call of a step is alike, or a list of ``{"flops", "bytes", "calls"}``,
one entry for each kind of call in a step (a windowed layer and a full
one): ``trace/program.py::roofline`` reads both.

A share of a roofline made from these counts cannot pass 100 %: no kernel
can do a call's work in fewer operations or bytes.
"""

from __future__ import annotations

# (matrix products, big tensors a query head: q out do dq, big tensors a
# kv head: k v dk dv, float32 row statistics) of one call
KERNELS = {
    "bps_flash_fwd": (2, 2, 2, 1),
    "bps_flash_bwd_fused": (5, 3, 4, 1),
    "bps_flash_bwd_dq": (3, 3, 2, 2),
    "bps_flash_bwd_dkv": (4, 2, 4, 2),
}


def flash_call(kernel: str, batch: int, heads: int, seq: int, head_dim: int,
               causal: bool, itemsize: int = 2, kv_heads: int = None,
               window: int = None) -> dict:
    """``{"flops", "bytes"}`` of one call of ``kernel`` on
    ``[batch, heads, seq, head_dim]`` queries of ``itemsize`` bytes, keys
    and values of ``kv_heads`` heads (as many as ``heads`` unless given),
    every query attending all keys, the lower triangle (``causal``) or a
    causal band of ``window`` keys."""
    products, q_tensors, kv_tensors, stats = KERNELS[kernel]
    if window is not None and not (causal and 1 <= window):
        raise ValueError("a window is a causal band of at least one key")
    if window is None or window >= seq:
        keys = (seq + 1) / 2 if causal else seq
    else:       # pairs a head: the triangle up to the band, the band after
        keys = (window * seq - window * (window - 1) / 2) / seq
    rows = batch * heads * seq
    kv_rows = batch * (heads if kv_heads is None else kv_heads) * seq
    return {"flops": products * 2.0 * rows * keys * head_dim,
            "bytes": float((q_tensors * rows + kv_tensors * kv_rows)
                           * head_dim * itemsize + stats * rows * 4)}


def of_cell(sizes: dict, mix: dict) -> dict:
    """The counts of every kernel at a cell's sizes (its configuration's
    ``sizes`` and its traffic mix), on one chip, in bfloat16, for the dense
    block: as many kv heads as heads of ``hidden // heads``, every layer's
    calls alike. The count of a configuration that names no other."""
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

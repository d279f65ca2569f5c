"""Transformer encoder/decoder core, TPU-first.

The reference has no model code at all (it wraps torch/tf/mxnet models);
its benchmark configs are BERT-large / GPT-2 style transformers
(reference: README.md:37-44, example/pytorch/benchmark_byteps.py). Here
the model zoo is part of the framework, built for the MXU:

  - matmul-heavy blocks in bfloat16, fp32 accumulation for softmax/LN
  - optional **tensor parallelism** over the ``model`` mesh axis,
    Megatron-style: QKV and MLP-in are column-parallel (no comm), attn-out
    and MLP-out are row-parallel (one psum each); heads divide across TP
    ranks
  - optional **sequence parallelism** over the ``seq`` axis via ring
    attention (byteps_tpu.parallel.ring)
  - ``param_specs`` returns the PartitionSpec tree so pjit/shard_map can
    lay the weights out without a wrapper class
  - ``jax.checkpoint`` on each block to trade FLOPs for HBM when training
    deep configs. By default the checkpoint keeps the block's input and
    the flash kernel's output and row statistics (``remat_policy``): the
    backward reads them, the kernel is the dearest op to run again and
    its output the cheapest to hold (2 bytes an element of the block's
    output, 69 MB a layer at batch 64 x seq 512, 1.66 GB over BERT-large)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..common.setup_record import note_choice
from ..ops import grouped_matmul as gm
from ..ops.flash_attention import SAVED_NAMES
from ..ops.routed_rows import take_xla
from ..parallel.ring import ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden: int = 1024
    layers: int = 24
    heads: int = 16
    mlp_dim: int = 4096
    max_seq: int = 512
    causal: bool = False          # False: BERT-style encoder; True: GPT
    dtype: str = "bfloat16"       # compute dtype (params stay fp32)
    remat: bool = True            # checkpoint each block
    remat_policy: Optional[str] = "save_attn"
    # "save_attn" (the default): checkpoint the whole block, save its
    #   input and the flash kernel's out + lse (named in
    #   ops/flash_attention._fwd_rule), recompute the rest: the backward
    #   never re-runs the kernel. [b, s, hidden] bf16 + [b, heads, s]
    #   fp32 a layer (69 MB at batch 64 seq 512); without the kernel
    #   (naive attention) nothing carries the names and it is None's.
    # None: checkpoint the whole block, save only its input (min memory;
    #   the flash forward runs twice a layer).
    # "dots": save MXU outputs, recompute elementwise (measured slower —
    #   the saved activations' HBM traffic beats the recompute).
    # "mlp_only": checkpoint only the MLP half; attention residuals
    #   (qkv, flash out+lse) are kept so the backward never re-runs the
    #   attention forward. ~300MB/layer at batch 64 seq 512.
    remat_layers: int = -1        # how many of the layers to checkpoint
    # (-1 = all). Layers beyond the first ``remat_layers`` keep their
    # activations resident and skip the backward's forward-recompute —
    # full remat executes ~4/3× the model FLOPs, so un-rematting the k
    # layers that fit in leftover HBM buys back k/L of that 33% overhead
    # (the single biggest MFU lever on one chip; see docs/performance.md).
    attn_impl: str = "auto"       # auto | flash (Pallas) | naive
    tp_axis: Optional[str] = None # mesh axis for tensor parallelism
    sp_axis: Optional[str] = None # mesh axis for ring-attention seq shards
    pp_axis: Optional[str] = None # mesh axis for pipeline (layer) stages
    pp_microbatches: int = 0      # GPipe microbatches (0 → pipeline size)
    pp_interleave: int = 1        # virtual chunks per pipeline rank (>1 =
    # interleaved/circular schedule: bubble shrinks interleave-fold; the
    # stacked layer params must be laid out with
    # parallel.pipeline.interleave_permutation)
    pp_remat_chunk: bool = True   # interleaved PP: checkpoint each tick's
    # chunk (10× less scan-residual memory, ~1/3 extra compute; overrides
    # remat_policy inside the chunk). False keeps per-tick residuals and
    # honors remat_policy (e.g. "mlp_only") at full memory cost.
    scan_unroll: int = 1          # lax.scan unroll factor over layers
    lm_head_chunk: int = 0        # >0: chunked cross-entropy — the LM
    # head + softmax run per sequence chunk (_chunked_nll_sum), so the
    # [s, vocab] logits never materialize (13 GB at GPT-2 seq 64k; the
    # enabler for very long contexts on one chip). 0 = full head.

    def __post_init__(self):
        if self.remat_policy not in (None, "dots", "mlp_only", "save_attn"):
            raise ValueError(f"remat_policy must be None|'dots'|'mlp_only'|"
                             f"'save_attn', got {self.remat_policy!r}")
        if self.remat_policy not in (None, "save_attn") and not self.remat:
            raise ValueError("remat_policy set but remat=False — the policy "
                             "would be silently ignored")
        if self.remat_layers != -1 and not (0 <= self.remat_layers
                                            <= self.layers):
            raise ValueError(f"remat_layers must be -1 or 0..{self.layers}, "
                             f"got {self.remat_layers}")
        if self.remat_layers != -1 and not self.remat:
            raise ValueError("remat_layers set but remat=False — the knob "
                             "would be silently ignored")
        if self.pp_interleave < 1:
            raise ValueError(f"pp_interleave must be >= 1, "
                             f"got {self.pp_interleave}")
        if self.pp_interleave > 1 and self.pp_axis is None:
            raise ValueError("pp_interleave > 1 needs pp_axis")

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


# ----------------------------------------------------------------- params

def init_params(rng, cfg: TransformerConfig):
    """Full (unsharded) parameter pytree; shard with param_specs."""
    keys = jax.random.split(rng, cfg.layers + 3)
    h, m = cfg.hidden, cfg.mlp_dim
    sd = 0.02

    def norm(key, shape):
        return jax.random.normal(key, shape, dtype=jnp.float32) * sd

    def one_block(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "ln1": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
            # [h, 3, heads, head_dim] so TP shards whole heads, not a
            # contiguous slice of the fused [q|k|v] columns
            "qkv": norm(k1, (h, 3, cfg.heads, cfg.head_dim)),
            "attn_out": norm(k2, (h, h)),
            "ln2": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
            "mlp_in": norm(k3, (h, m)),
            "mlp_in_b": jnp.zeros((m,)),
            "mlp_out": norm(k4, (m, h)),
            "mlp_out_b": jnp.zeros((h,)),
        }

    blocks = [one_block(keys[i + 2]) for i in range(cfg.layers)]
    # stack per-layer params on a leading layer axis: the whole depth runs
    # as one lax.scan, so compile time is O(1) in layer count
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embed": {
            "tok": norm(keys[0], (cfg.vocab_size, h)),
            "pos": norm(keys[1], (cfg.max_seq, h)),
        },
        "blocks": stacked,
        "final_ln": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
    }


def param_specs(cfg: TransformerConfig):
    """PartitionSpec tree matching init_params: column-parallel weights
    shard their output dim on tp_axis, row-parallel their input dim."""
    tp = cfg.tp_axis
    pp = cfg.pp_axis  # stacked layer axis shards across pipeline stages
    rep = P()
    lead = P(pp)
    block = {
        "ln1": {"scale": lead, "bias": lead},
        "qkv": P(pp, None, None, tp, None),    # column parallel over heads
        "attn_out": P(pp, tp, None),           # row parallel
        "ln2": {"scale": lead, "bias": lead},
        "mlp_in": P(pp, None, tp),
        "mlp_in_b": P(pp, tp),
        "mlp_out": P(pp, tp, None),
        "mlp_out_b": lead,
    }
    return {
        "embed": {"tok": rep, "pos": rep},
        "blocks": block,
        "final_ln": {"scale": rep, "bias": rep},
    }


# ----------------------------------------------------------------- layers

def embed_lookup(table, tokens, dtype=None, scale=None):
    """``(table[tokens] * scale).astype(dtype)``: the token embedding, with
    a backward that costs what the tokens cost.

    Forward is the plain gather (``scale``, a Python number, in the
    table's dtype before the cast, as a caller's own product would be).
    The backward is ``scale * sum of the cotangent's rows by id``, summed
    in float32 and scaled once. Three forms of that sum have been read
    on the v5e:

    - autodiff's scatter-add of [b*s, hid] rows into [vocab, hid], which
      serialises: 115 ms a step in BERT-large (batch 64, seq 512; read
      before PR 1, commit 7373b59);
    - ``one_hot(tokens, vocab)^T @ ct`` on the MXU, tokens x vocab x hid
      of which one term in vocab is not zero: 29 ms there then, 11.6 ms
      since (2.05 TFLOP near the bf16 peak), and 27.5 ms in Trinity,
      whose float32 scale outside this function made the cotangent a
      float32 that is no bf16 (six passes; PERF.md, PR 36). It is the
      form off the TPU (``embed_grad``);
    - the grouped form (``ops.grouped_matmul.embed_dw``, PR 42): the ids
      sorted, the cotangent's rows gathered into that order, and each
      block of 256 vocabulary rows summed from its own run of tokens by
      a one-hot made in VMEM: tokens x 256 x hid. PERF.md (PR 42) has its
      readings at the cells' shapes.

    ``dtype`` and ``scale`` are inside so that the backward sees the
    cotangent in the dtype the model computes in: bf16 rows sum exactly
    in one pass."""
    out = jnp.dtype(dtype or table.dtype)
    return _embed_lookup(table.shape[0], str(table.dtype), str(out), scale,
                         table, tokens)


def _embed_rows(out: str, scale, table, tokens):
    x = table[tokens]
    return (x if scale is None else x * scale).astype(out)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _embed_lookup(vocab: int, dt: str, out: str, scale, table, tokens):
    return _embed_rows(out, scale, table, tokens)


def _embed_lookup_fwd(vocab, dt, out, scale, table, tokens):
    return _embed_rows(out, scale, table, tokens), tokens


def _embed_lookup_bwd(vocab, dt, out, scale, tokens, ct):
    return embed_grad(tokens.reshape(-1), ct.reshape(-1, ct.shape[-1]),
                      vocab, scale, dt), None


_embed_lookup.defvjp(_embed_lookup_fwd, _embed_lookup_bwd)


def embed_grad(ids, ct, vocab: int, scale=None, dtype="float32",
               impl: str = "auto"):
    """[vocab, hid] in ``dtype``: ``scale * sum of ct[t] over the tokens t
    with ids[t] == v``, for ``ids`` [T] and ``ct`` [T, hid]. An id outside
    the vocabulary adds to no row.

    impl: "auto": the grouped form on the TPU where the hidden size is
    whole lane tiles (what the kernel's blocks take), else the one-hot
    product; "kernels", "kernels_interpret" (Pallas' interpreter: tests),
    "xla"."""
    if impl not in ("auto", "kernels", "kernels_interpret", "xla"):
        raise ValueError(f"embed_grad impl {impl!r}")
    asked = impl
    if impl == "auto":
        impl = ("kernels" if jax.default_backend() == "tpu"
                and ct.shape[1] % 128 == 0 else "xla")
    note_choice("embed_bwd", "xla" if impl == "xla" else "kernels",
                (tuple(ct.shape), vocab),
                "the one-hot product over tokens x vocabulary: the grouped "
                "form needs a hidden size in whole lane tiles", asked=asked)
    if impl == "xla":
        onehot = jax.nn.one_hot(ids, vocab, dtype=ct.dtype)
        # fp32 cotangents keep scatter-add exactness (TPU fp32 dots default
        # to bf16 MXU passes); bf16 cotangents take the fast default
        prec = (jax.lax.Precision.HIGHEST
                if ct.dtype == jnp.float32 else None)
        grad = jax.lax.dot_general(onehot, ct, (((0,), (0,)), ((), ())),
                                   precision=prec,
                                   preferred_element_type=jnp.float32)
        return (grad if scale is None else grad * scale).astype(dtype)
    tile = gm.EMBED_TILE
    rows = -(-ids.shape[0] // tile) * tile
    # a stable sort: a row's tokens are summed in the same order every
    # run; rows past the tokens sort last, under no id of the vocabulary
    ids = jnp.pad(ids.astype(jnp.int32), (0, rows - ids.shape[0]),
                  constant_values=jnp.iinfo(jnp.int32).max)
    ids, order = jax.lax.sort((ids, jnp.arange(rows, dtype=jnp.int32)),
                              num_keys=1, is_stable=True)
    # a row of no token of the vocabulary is the gather's fill (an index
    # past the tokens), not a product of what it holds with the one-hot's
    order = jnp.where((ids >= 0) & (ids < vocab), order, rows)
    moved = take_xla(ct, order)
    return gm.embed_dw(ids, moved, vocab, scale, jnp.dtype(dtype).name,
                       impl == "kernels_interpret")


def _layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(x.dtype)


def _attention(x, blk, cfg: TransformerConfig, tp_size: int):
    """Self-attention of a block. q, k and v each leave their own product
    as [b, s, heads*head_dim], heads side by side on the minor axis, and
    attention's output enters ``attn_out`` so: the layout in which the
    flash kernels read and write HBM where a head is narrower than a
    lane tile (``ops/flash_attention.py``, the note on narrow heads), so
    that nothing is copied between a product and a kernel. The
    [b, s, heads, head_dim] the kernels' API, the ring and tensor
    parallelism see is a reshape of it. (One product to
    [b, s, 3, heads, head_dim] and three slices, as before PR 33, left
    every q, k, v a strided slice to copy out.) The weight is stored
    [h, 3, heads, head_dim] as ever."""
    b, s, _ = x.shape
    local_heads = cfg.heads // tp_size
    w = blk["qkv"].astype(x.dtype)
    q, k, v = ((x @ w[:, c].reshape(w.shape[0], -1))
               .reshape(b, s, local_heads, cfg.head_dim) for c in range(3))
    if cfg.sp_axis is not None:
        out = ring_attention(q, k, v, cfg.sp_axis, causal=cfg.causal,
                             impl=cfg.attn_impl)
    else:
        from ..ops.flash_attention import attention
        out = attention(q, k, v, causal=cfg.causal, impl=cfg.attn_impl)
    out = out.reshape(b, s, local_heads * cfg.head_dim)
    out = out @ blk["attn_out"].astype(x.dtype)   # row-parallel: partial sum
    if cfg.tp_axis is not None:
        out = jax.lax.psum(out, cfg.tp_axis)
    return out


def _mlp(x, blk, cfg: TransformerConfig):
    hdt = x.dtype
    h = x @ blk["mlp_in"].astype(hdt) + blk["mlp_in_b"].astype(hdt)
    h = jax.nn.gelu(h)
    out = h @ blk["mlp_out"].astype(hdt)          # row-parallel: partial sum
    if cfg.tp_axis is not None:
        out = jax.lax.psum(out, cfg.tp_axis)
    return out + blk["mlp_out_b"].astype(hdt)


def _block(x, blk, cfg: TransformerConfig, tp_size: int,
           remat_mlp: bool = False):
    """Transformer block; remat_mlp checkpoints only the MLP half
    (remat_policy="mlp_only": attention residuals kept, MLP recomputed)."""
    with jax.named_scope("bps.attn"):
        x = x + _attention(
            _layernorm(x, blk["ln1"]["scale"], blk["ln1"]["bias"]),
            blk, cfg, tp_size)

    def mlp_half(y, b):
        return _mlp(_layernorm(y, b["ln2"]["scale"], b["ln2"]["bias"]),
                    b, cfg)

    if remat_mlp:
        mlp_half = jax.checkpoint(mlp_half)
    with jax.named_scope("bps.mlp"):
        return x + mlp_half(x, blk)


def apply(params, cfg: TransformerConfig, tokens: jnp.ndarray,
          positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Forward to final hidden states [b, s_local, hidden].

    Call inside shard_map when tp/sp/pp axes are set. With sp_axis,
    ``tokens`` is the local sequence shard and ``positions`` must be the
    global positions of that shard (defaults assume shard-contiguous
    layout). With pp_axis, the returned hidden states are only valid on
    the LAST pipeline stage — finite zeros-fed garbage elsewhere; mask
    any derived quantity with ``parallel.pipeline.last_stage_value`` (as
    ``lm_loss`` does) before use.
    """
    dt = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    if positions is None:
        if cfg.sp_axis is not None:
            offset = jax.lax.axis_index(cfg.sp_axis) * s
        else:
            offset = 0
        positions = offset + jnp.arange(s)
    tp_size = jax.lax.axis_size(cfg.tp_axis) if cfg.tp_axis else 1
    with jax.named_scope("bps.embed"):
        x = embed_lookup(params["embed"]["tok"], tokens, dt)
        x = x + params["embed"]["pos"][positions].astype(dt)

    plain_fn = partial(_block, cfg=cfg, tp_size=tp_size)
    if cfg.remat and cfg.remat_policy == "mlp_only":
        blk_fn = partial(_block, cfg=cfg, tp_size=tp_size, remat_mlp=True)
    else:
        blk_fn = plain_fn
        if cfg.remat:
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            elif cfg.remat_policy == "save_attn":
                # pin ONLY the flash kernel's residuals (out + squeezed
                # lse, named in ops/flash_attention._fwd_rule); everything
                # else recomputes
                policy = jax.checkpoint_policies.save_only_these_names(
                    *SAVED_NAMES)
            else:
                policy = None
            blk_fn = jax.checkpoint(blk_fn, policy=policy)

    def body(carry, blk):
        return blk_fn(carry, blk), None

    def plain_body(carry, blk):
        return plain_fn(carry, blk), None

    def stack_fn(blocks, h):
        k = cfg.remat_layers
        if not cfg.remat or k == -1 or k >= cfg.layers or cfg.pp_axis:
            # uniform policy across the stack (pp stages keep it uniform
            # too: their layer shard sizes vary with the stage count)
            out, _ = jax.lax.scan(body, h, blocks, unroll=cfg.scan_unroll)
            return out
        # partial remat: first k layers checkpointed, the rest keep
        # activations resident (two scans; compile time stays O(1))
        rem = jax.tree_util.tree_map(lambda x: x[:k], blocks)
        res = jax.tree_util.tree_map(lambda x: x[k:], blocks)
        if k:
            h, _ = jax.lax.scan(body, h, rem, unroll=cfg.scan_unroll)
        out, _ = jax.lax.scan(plain_body, h, res, unroll=cfg.scan_unroll)
        return out

    if cfg.pp_axis is not None:
        # Pipeline over the pipe axis: params["blocks"] arrives as this
        # stage's layer shard; microbatch the batch dim and stream.
        from ..parallel.pipeline import pipeline, pipeline_interleaved
        pn = jax.lax.axis_size(cfg.pp_axis)
        V = cfg.pp_interleave
        if cfg.layers % (pn * V):
            raise ValueError(f"{cfg.layers} layers not divisible by "
                             f"{pn} stages x {V} chunks")
        n_micro = cfg.pp_microbatches or pn
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
        xm = x.reshape(n_micro, b // n_micro, *x.shape[1:])
        if V > 1:
            # interleaved layout contract: the caller permuted the stacked
            # layers with interleave_permutation, so this rank's [L/pn]
            # shard reshapes to [V, Lc] chunks in ring order
            chunked = jax.tree_util.tree_map(
                lambda p: p.reshape(V, p.shape[0] // V, *p.shape[1:]),
                params["blocks"])
            xm = pipeline_interleaved(stack_fn, chunked, xm, cfg.pp_axis,
                                      remat_chunk=cfg.pp_remat_chunk)
        else:
            xm = pipeline(stack_fn, params["blocks"], xm, cfg.pp_axis)
        x = xm.reshape(b, *x.shape[1:])   # valid on the last stage only
    else:
        x = stack_fn(params["blocks"], x)
    with jax.named_scope("bps.head"):    # the final norm feeds the head
        x = _layernorm(x, params["final_ln"]["scale"],
                       params["final_ln"]["bias"])
    return x


@jax.custom_vjp
def _own_gradient(w):
    """``w``, with a cotangent that is an op of its own: XLA may not fuse
    what produces it into what consumes it. The tied head's gradient to
    the table is consumed after the whole backward, where the embedding's
    is added: fused into that sum (and Adam's update behind it) the
    product waits there, and its operands with it: BERT-large's
    ``f32[64, 80, 30522]`` logits' cotangent, 1.15 GB more at the step's
    peak (compiled for the v5e, PR 42). While the embedding's backward
    was a product itself the fusion's one product was taken."""
    return w


_own_gradient.defvjp(lambda w: (w, None),
                     lambda _, ct: (jax.lax.optimization_barrier(ct),))


def logits(params, cfg: TransformerConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    """Tied-embedding LM head → [b, s, vocab] in fp32.

    The matmul runs at the compute dtype (bf16 on the MXU — at fp32 this
    one op dominates the step) with fp32 accumulation."""
    dt = jnp.dtype(cfg.dtype)
    return jnp.einsum("bsh,vh->bsv", hidden.astype(dt),
                      _own_gradient(params["embed"]["tok"].astype(dt)),
                      preferred_element_type=jnp.float32)


_warned_chunk: set = set()


def _by_chunk(n: int, *arrays):
    """Each ``[b, n * chunk, ...]`` as the ``n`` chunks a scan takes in
    turn."""
    return tuple(
        jnp.moveaxis(a.reshape(a.shape[0], n, -1, *a.shape[2:]), 1, 0)
        for a in arrays)


def _chunk_logits(hb, w):
    """A chunk's logits in fp32 from compute-dtype operands, and their
    log-sum-exp ``[b, chunk, 1]``."""
    lg = jnp.einsum("bch,vh->bcv", hb.astype(w.dtype), w,
                    preferred_element_type=jnp.float32)
    return lg, jax.nn.logsumexp(lg, axis=-1, keepdims=True)


def _chunk_nll(lg, lse, tb, mb):
    """The masked NLL sum of a chunk from its logits."""
    pick = jnp.take_along_axis(lg, jnp.where(mb, tb, 0)[..., None], axis=-1)
    return ((lse - pick)[..., 0] * mb).sum()


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _chunked_nll_sum(h, emb, targets, mask, chunk: int, dt) -> jnp.ndarray:
    """Masked NLL sum with the LM head applied per sequence chunk.

    A chunk's logits, softmax and pick live only inside one turn of a
    ``lax.scan``: O(chunk x vocab) live, never an [s, vocab] tensor, one
    product a chunk. Under differentiation (``_chunked_nll_fwd``) the same
    turn forms the chunk's gradient while it holds the logits, and the
    forward leaves two residuals, d h ``[b, s, hidden]`` and d head
    ``[vocab, hidden]``, which the backward scales: three products a
    chunk, nothing computed twice."""
    n = h.shape[1] // chunk
    with jax.named_scope("bps.head"):
        w = emb.astype(dt)

        def body(total, xs):
            hb, tb, mb = xs
            return total + _chunk_nll(*_chunk_logits(hb, w), tb, mb), None

        return jax.lax.scan(body, jnp.float32(0.0),
                            _by_chunk(n, h, targets, mask))[0]


def _chunked_nll_fwd(h, emb, targets, mask, chunk: int, dt):
    """The sum, and its gradients to ``h`` and ``emb`` for a unit
    cotangent: the loss is a sum of per-position terms, so d logits is
    ``(softmax - one_hot(target)) * mask`` whatever the cotangent, formed
    here from the chunk's live logits. The two gradient products take it
    at the compute dtype (what the chip's default precision makes of the
    fp32 cotangent of a ``preferred_element_type=float32`` product of
    compute-dtype operands) and give fp32; d head adds up over the chunks
    in fp32. Softmax, log-sum-exp, pick and sum are fp32."""
    b, s, hid = h.shape
    n = s // chunk
    with jax.named_scope("bps.head"):
        w = emb.astype(dt)

        def body(carry, xs):
            total, dw = carry
            hb, tb, mb = xs
            lg, lse = _chunk_logits(hb, w)
            total = total + _chunk_nll(lg, lse, tb, mb)
            with jax.named_scope("bps.head.grad"):
                hit = (jax.lax.broadcasted_iota(jnp.int32, lg.shape, 2)
                       == tb[..., None])
                p = jnp.exp(lg - lse)
                g = jnp.where(mb[..., None], jnp.where(hit, p - 1.0, p),
                              0.0).astype(dt)
                dh = jnp.einsum("bcv,vh->bch", g, w,
                                preferred_element_type=jnp.float32)
                dw = dw + jnp.einsum("bcv,bch->vh", g, hb.astype(dt),
                                     preferred_element_type=jnp.float32)
            return (total, dw), dh.astype(h.dtype)

        (total, dw), dh = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.zeros(emb.shape, jnp.float32)),
            _by_chunk(n, h, targets, mask))
        dh = jnp.moveaxis(dh, 0, 1).reshape(b, s, hid)
    return total, (dh, dw.astype(emb.dtype))


def _chunked_nll_bwd(chunk, dt, residuals, ct):
    dh, dw = residuals
    with jax.named_scope("bps.head"):
        return ((ct * dh).astype(dh.dtype), (ct * dw).astype(dw.dtype),
                None, None)


_chunked_nll_sum.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


def lm_loss(params, cfg: TransformerConfig, batch) -> jnp.ndarray:
    """Cross-entropy LM loss. batch = (tokens, targets); targets < 0 are
    ignored (the MLM mask convention).

    Under sequence parallelism the nll-sum and mask-count are psum'd over
    the sp axis *before* dividing, so every rank holds the true global
    loss — local-mean losses would weight shards with different mask
    counts unevenly and bias the gradient."""
    tokens, targets = batch
    h = apply(params, cfg, tokens)
    mask = (targets >= 0)
    s = h.shape[1]
    chunk = cfg.lm_head_chunk
    if chunk and s > chunk and s % chunk:
        # silent fallback would materialize the [s, vocab] logits the
        # user configured the chunking to avoid — warn once per shape
        if (s, chunk) not in _warned_chunk:
            _warned_chunk.add((s, chunk))
            from ..common.logging import get_logger
            get_logger().warning(
                "lm_head_chunk=%d does not divide seq %d — falling back "
                "to the FULL [s, vocab] head (O(s·vocab) memory); pick a "
                "divisor of the sequence length", chunk, s)
    if chunk and s > chunk and s % chunk == 0:
        nll_sum = _chunked_nll_sum(h, params["embed"]["tok"], targets,
                                   mask, chunk, jnp.dtype(cfg.dtype))
    else:
        with jax.named_scope("bps.head"):
            lg = logits(params, cfg, h)
            logp = jax.nn.log_softmax(lg, axis=-1)
            tgt = jnp.where(mask, targets, 0)
            nll = -jnp.take_along_axis(logp, tgt[..., None],
                                       axis=-1)[..., 0]
            nll_sum = (nll * mask).sum()
    cnt = mask.sum().astype(jnp.float32)
    if cfg.sp_axis is not None:
        nll_sum = jax.lax.psum(nll_sum, cfg.sp_axis)
        cnt = jax.lax.psum(cnt, cfg.sp_axis)
    if cfg.pp_axis is not None:
        # Only the last pipeline stage holds real hidden states; mask the
        # other ranks' (finite, zero-init) dummy outputs and replicate —
        # the psum's n× grad factor matches the trainer's uniform rescale
        # convention (see ShardedTrainer.step).
        from ..parallel.pipeline import last_stage_value
        nll_sum = last_stage_value(nll_sum, cfg.pp_axis)
        cnt = last_stage_value(cnt, cfg.pp_axis)
    return nll_sum / jnp.maximum(cnt, 1.0)

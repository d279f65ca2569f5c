"""Mixture-of-Experts transformer with expert parallelism.

Additive scope vs the reference (SURVEY §2.5: "Expert parallelism (EP/MoE):
Absent"). TPU-first design:

  - GShard/Switch-style top-k routing with **static-shape capacity
    buffers**: dispatch/combine are one-hot einsums, so everything stays
    MXU-shaped and jit-compatible (no dynamic token counts).
  - Experts shard over the ``expert`` mesh axis; tokens travel to their
    experts via ``lax.all_to_all`` over ICI and back — the canonical EP
    exchange.
  - The ``expert`` axis doubles as a batch axis (batch sharded over
    data × expert), so every rank routes its own token shard: EP adds no
    idle ranks, and gradient rescale in ShardedTrainer treats ``expert``
    exactly like a data axis (per-leaf psum + uniform 1/n).
  - Load-balance auxiliary loss (Switch: E · Σ_e f_e·p_e) accumulated
    through the block scan carry.

References (public techniques): GShard (Lepikhin et al. 2020), Switch
Transformer (Fedus et al. 2021).

Beside it, ``routed_ffn``: routing WITHOUT drops over the experts THIS
chip holds of a layer that is shared by several chips (the expert
layer of ``models/decoder.py``; docs/routed-experts.md). It scores and
chooses over all the router's outputs, sorts the rows chosen for held
experts into per-expert runs of whole row tiles (static shapes sized
for the worst routing, no capacity), runs the grouped products over the
runs that are there (``ops/grouped_matmul.py``) and gathers the weighted
results back; rows travel both ways in time that follows the rows routed
(``ops/routed_rows.py``). What the experts held elsewhere would add is
left out.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.grouped_matmul import grouped_matmul
from ..ops.routed_act import gated_silu, relu2, routed_act
from ..ops.routed_rows import (combine_rows, resolve as resolve_rows,
                               take_rows, tile_bounds)
from .transformer import (TransformerConfig, _attention, _layernorm,
                          embed_lookup)


@dataclasses.dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25   # per-expert buffer = cf·k·T/E tokens
    ep_axis: Optional[str] = None   # mesh axis holding expert shards
    aux_weight: float = 1e-2        # load-balance loss coefficient


# ----------------------------------------------------------------- params

def init_moe_params(rng, cfg: MoEConfig):
    """Parameter pytree: transformer attention + per-expert FFN weights,
    per-layer leaves stacked on a leading layer axis (lax.scan depth)."""
    keys = jax.random.split(rng, cfg.layers + 3)
    h, m, e = cfg.hidden, cfg.mlp_dim, cfg.num_experts
    sd = 0.02

    def norm(key, shape):
        return jax.random.normal(key, shape, dtype=jnp.float32) * sd

    def one_block(key):
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        return {
            "ln1": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
            "qkv": norm(k1, (h, 3, cfg.heads, cfg.head_dim)),
            "attn_out": norm(k2, (h, h)),
            "ln2": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
            "router": norm(k3, (h, e)),
            "w_in": norm(k4, (e, h, m)),
            "w_in_b": jnp.zeros((e, m)),
            "w_out": norm(k5, (e, m, h)),
            "w_out_b": jnp.zeros((e, h)),
        }

    blocks = [one_block(keys[i + 2]) for i in range(cfg.layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embed": {
            "tok": norm(keys[0], (cfg.vocab_size, h)),
            "pos": norm(keys[1], (cfg.max_seq, h)),
        },
        "blocks": stacked,
        "final_ln": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
    }


def moe_param_specs(cfg: MoEConfig):
    """PartitionSpec tree: expert-indexed weights shard on ep_axis; the
    router and attention stay replicated across it."""
    ep = cfg.ep_axis
    rep = P()
    lead = P(None)
    block = {
        "ln1": {"scale": lead, "bias": lead},
        "qkv": P(None, None, None, cfg.tp_axis, None),
        "attn_out": P(None, cfg.tp_axis, None),
        "ln2": {"scale": lead, "bias": lead},
        "router": P(None, None, None),
        "w_in": P(None, ep, None, None),
        "w_in_b": P(None, ep, None),
        "w_out": P(None, ep, None, None),
        "w_out_b": P(None, ep, None),
    }
    return {
        "embed": {"tok": rep, "pos": rep},
        "blocks": block,
        "final_ln": {"scale": rep, "bias": rep},
    }


# ------------------------------------------------------------------ layer

def _route(x, router_w, cfg: MoEConfig):
    """Top-k routing. x: [T, h] → (combine [T, E, C], dispatch [T, E, C],
    aux scalar). Static capacity C; overflow tokens are dropped (their
    residual path carries them through)."""
    tcount, e = x.shape[0], cfg.num_experts
    cap = max(1, int(cfg.capacity_factor * cfg.top_k * tcount / e))
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                   # [T, E]

    # top-k expert choices per token; renormalize gate weights over the k
    topv, topi = jax.lax.top_k(probs, cfg.top_k)              # [T, k]
    gates_norm = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # one-hot per choice → position in each expert's capacity buffer.
    # Choices are flattened in (k, token) order so first choices win
    # buffer slots before any second choice competes.
    sel = jax.nn.one_hot(topi, e, dtype=jnp.float32)          # [T, k, E]
    sel_flat = sel.transpose(1, 0, 2).reshape(-1, e)          # [k*T, E]
    pos_flat = jnp.cumsum(sel_flat, axis=0) - sel_flat        # slot index
    keep_flat = sel_flat * (pos_flat < cap)
    dispatch_flat = keep_flat[..., None] * jax.nn.one_hot(
        pos_flat.astype(jnp.int32), cap, dtype=jnp.float32)   # [k*T, E, C]
    dispatch_k = dispatch_flat.reshape(cfg.top_k, tcount, e, cap)
    combine = jnp.einsum("ktec,tk->tec", dispatch_k, gates_norm)
    dispatch = dispatch_k.sum(0)                              # [T, E, C]

    # Switch aux loss: E · Σ_e (fraction routed to e)·(mean prob of e)
    frac = sel.sum(1).mean(0)                                 # [E]
    aux = e * jnp.sum(frac * probs.mean(0)) / cfg.top_k
    return combine, dispatch, aux


def _moe_ffn(x, blk, cfg: MoEConfig):
    """MoE FFN over flattened tokens x: [T, h] → ([T, h], aux)."""
    combine, dispatch, aux = _route(x, blk["router"], cfg)
    dt = x.dtype
    buf = jnp.einsum("tec,th->ech", dispatch.astype(dt), x)   # [E, C, h]

    if cfg.ep_axis is not None:
        n = jax.lax.axis_size(cfg.ep_axis)
        if cfg.num_experts % n:
            raise ValueError(
                f"{cfg.num_experts} experts not divisible by ep size {n}")
        # exchange: every rank keeps E/n experts, receives all ranks' slots
        buf = jax.lax.all_to_all(buf, cfg.ep_axis, split_axis=0,
                                 concat_axis=1, tiled=True)   # [E/n, n·C, h]

    h1 = jnp.einsum("ech,ehm->ecm", buf, blk["w_in"].astype(dt))
    h1 = jax.nn.gelu(h1 + blk["w_in_b"][:, None, :].astype(dt))
    out = jnp.einsum("ecm,emh->ech", h1, blk["w_out"].astype(dt))
    out = out + blk["w_out_b"][:, None, :].astype(dt)

    if cfg.ep_axis is not None:
        out = jax.lax.all_to_all(out, cfg.ep_axis, split_axis=1,
                                 concat_axis=0, tiled=True)   # [E, C, h]

    y = jnp.einsum("tec,ech->th", combine.astype(dt), out)
    return y, aux


def _moe_block(carry, blk, cfg: MoEConfig, tp_size: int):
    x, aux_acc = carry
    x = x + _attention(_layernorm(x, blk["ln1"]["scale"], blk["ln1"]["bias"]),
                       blk, cfg, tp_size)
    b, s, h = x.shape
    flat = _layernorm(x, blk["ln2"]["scale"], blk["ln2"]["bias"]).reshape(-1, h)
    y, aux = _moe_ffn(flat, blk, cfg)
    return (x + y.reshape(b, s, h), aux_acc + aux), None


# ---------------------------------------------------------------- forward

def moe_apply(params, cfg: MoEConfig, tokens: jnp.ndarray,
              positions: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward to (hidden [b, s, h], mean aux loss). Call inside shard_map
    when ep/tp/sp axes are set."""
    if cfg.pp_axis is not None:
        raise ValueError("MoE does not support pipeline parallelism yet; "
                         "unset pp_axis")
    dt = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    if positions is None:
        if cfg.sp_axis is not None:
            offset = jax.lax.axis_index(cfg.sp_axis) * s
        else:
            offset = 0
        positions = offset + jnp.arange(s)
    tp_size = jax.lax.axis_size(cfg.tp_axis) if cfg.tp_axis else 1
    x = embed_lookup(params["embed"]["tok"], tokens, dt)
    x = x + params["embed"]["pos"][positions].astype(dt)

    blk_fn = partial(_moe_block, cfg=cfg, tp_size=tp_size)
    if cfg.remat:
        blk_fn = jax.checkpoint(blk_fn)

    (x, aux), _ = jax.lax.scan(blk_fn, (x, jnp.float32(0.0)),
                               params["blocks"])
    x = _layernorm(x, params["final_ln"]["scale"], params["final_ln"]["bias"])
    return x, aux / cfg.layers


def moe_lm_loss(params, cfg: MoEConfig, batch) -> jnp.ndarray:
    """Cross-entropy + load-balance aux. batch = (tokens, targets),
    targets < 0 ignored (same convention as transformer.lm_loss)."""
    tokens, targets = batch
    h, aux = moe_apply(params, cfg, tokens)
    lg = jnp.einsum("bsh,vh->bsv", h.astype(jnp.float32),
                    params["embed"]["tok"].astype(jnp.float32))
    logp = jax.nn.log_softmax(lg, axis=-1)
    mask = (targets >= 0)
    tgt = jnp.where(mask, targets, 0)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    nll_sum = (nll * mask).sum()
    cnt = mask.sum().astype(jnp.float32)
    if cfg.sp_axis is not None:
        nll_sum = jax.lax.psum(nll_sum, cfg.sp_axis)
        cnt = jax.lax.psum(cnt, cfg.sp_axis)
    return nll_sum / jnp.maximum(cnt, 1.0) + cfg.aux_weight * aux


def moe_tiny(**kw) -> MoEConfig:
    """Test-sized config."""
    return MoEConfig(vocab_size=128, hidden=64, layers=2, heads=4,
                     mlp_dim=128, max_seq=64, causal=False, dtype="float32",
                     remat=False, num_experts=4, top_k=2, **kw)


# ------------------------------------------- routing without drops (afmoe)

@dataclasses.dataclass(frozen=True)
class RoutedConfig:
    """A routed feed-forward layer as one chip sees it."""
    num_experts: int              # the router's outputs: the whole layer's
    held: Tuple[int, ...]         # the experts held here, by router output
    top_k: int
    route_scale: float = 1.0      # on the weights, normalised over the k
    row_tile: int = 512           # rows a tile of the grouped products
    impl: str = "auto"            # ops.grouped_matmul.grouped_matmul's
    balanced: bool = False        # choose on standardised outputs (route)
    act: str = "gated_silu"       # the experts' function: one of ACTS
    score: str = "sigmoid"        # the router's scores: one of SCORES
    shared_dim: int = 0           # the shared expert's width, where the
    # layer states one of its own (the weights' shapes say it besides)

    def __post_init__(self):
        held = tuple(self.held)
        if len(set(held)) != len(held) or not held or not all(
                0 <= e < self.num_experts for e in held):
            raise ValueError(f"held experts {held} are not distinct outputs "
                             f"of a router with {self.num_experts}")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} of {self.num_experts}")
        if self.act not in ACTS:
            raise ValueError(f"experts' function {self.act!r} is none of "
                             f"{sorted(ACTS)}")
        if self.score not in SCORES:
            raise ValueError(f"router's scores {self.score!r} are none of "
                             f"{SCORES}")

    @property
    def rows(self):
        """Most rows a token can send to the experts held here."""
        return min(self.top_k, len(self.held))


# an expert is ``down(act(first x))``: the function between its two
# products, and the name of the first one's weights ([h, 2 m] fused gate
# and up under ``gated_silu``, [h, m] under ``relu2``)
ACTS = {"gated_silu": (gated_silu, "gate_up"), "relu2": (relu2, "up")}
# the router's scores: each output's own sigmoid, or a softmax over ALL
# the router's outputs (held here or not)
SCORES = ("sigmoid", "softmax")


# the name a ``jax.checkpoint`` policy keeps the routed layer's plan
# under: every int32 array of ``plan_rows`` and the rows' weights beside
# them, the choice of experts it is made from and the chosen scores
# (``route``). They are a few MB a layer, and making them (top-k, a sort of
# the 131,072 chosen pairs with its payloads, compares against the held
# experts and every output, a running count, a slice of the sorted pairs a
# row tile: under a millisecond a layer) is not worth a second time:
# ``decoder.apply``'s checkpoint saves them, so the backward's recompute
# holds none of it. The int32 arrays carry no gradient; the scores' flows
# through the name as through an identity.
PLAN_NAME = "moe_plan"

# ... and the experts' weights in the compute dtype under: the float32
# parameters cast to bf16 for the grouped products, [held, h, 2 m] and
# [held, m, h] (201 MB a layer at 16 experts of 1024 over a hidden of 2048).
# The Pallas products cannot take the convert as a fused operand the way
# XLA's own dots do, so the cast is an op of its own over every held
# parameter, and a relayout before it where a width is off the lane tile:
# ``decoder.apply``'s checkpoint keeps the forward's copy from a layer's
# forward to its backward (the compute copy every mixed-precision trainer
# holds), and the recompute and ``_dx`` read it. The gradient flows through
# the name as through an identity: ``_gmm_dw``'s bf16 cotangent becomes
# float32 in the cast's transpose, as without the name.
WEIGHTS_NAME = "moe_weights"


def route(f, router_w, cfg: RoutedConfig, sequences: int = 1):
    """(weights [T, k] fp32, experts [T, k] int32): sigmoid scores (or,
    ``cfg.score``, a softmax) over ALL the router's outputs in fp32, the k
    largest, their scores normalised over the k and scaled.

    With ``cfg.balanced`` the k are the largest of the router's outputs
    STANDARDISED an expert over the tokens of a sequence (``f`` is
    ``sequences`` of them, one after another), ``(l - mean_t l) /
    deviation_t l`` with ``l`` the output before its sigmoid, so that
    every expert is chosen about equally often whatever the router's
    weights; the weights are still the chosen experts' own scores. It is
    what the published model's selection bias is there for (the k largest
    of ``s + b``, ``b`` moved a little a step toward equal counts by the
    training framework), done on the batch at hand and without state."""
    logits = jnp.dot(f.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, -1) if cfg.score == "softmax"
              else jax.nn.sigmoid(logits))
    ranked = jax.lax.stop_gradient(scores)
    if cfg.balanced:
        by_seq = jax.lax.stop_gradient(logits).reshape(
            sequences, -1, logits.shape[-1])
        centred = by_seq - by_seq.mean(1, keepdims=True)
        centred *= jax.lax.rsqrt(
            jnp.mean(centred * centred, 1, keepdims=True) + 1e-12)
        ranked = centred.reshape(logits.shape)
    # the scores are a choice by the NAMED experts: neither is made again.
    # A compare against every output and a sum of which one term is not
    # zero: ``take_along_axis``'s values to the bit, elementwise where that
    # is a gather of single floats and its transpose a scatter-add. Tokens
    # along the lanes, so that the sum over the outputs adds registers
    experts = checkpoint_name(jax.lax.top_k(ranked, cfg.top_k)[1], PLAN_NAME)
    chosen = experts.T[:, None, :] == jnp.arange(
        cfg.num_experts, dtype=experts.dtype)[None, :, None]   # [k, E, T]
    top = checkpoint_name(
        jnp.where(chosen, scores.T[None], 0.0).sum(1).T, PLAN_NAME)
    # XLA would fold that sum and the next into one over [k, outputs] and
    # add the k scores in another order: an ulp of every weight
    top = jax.lax.optimization_barrier(top)
    return cfg.route_scale * top / top.sum(-1, keepdims=True), experts


def plan_rows(experts, cfg: RoutedConfig, weights=None):
    """Where each chosen (token, expert) pair's row goes. The pairs whose
    expert is held are sorted by expert; expert g's run starts on a tile
    border and is padded to whole tiles (at least one, so that every
    expert's weight gradient is written). Static sizes, for the worst
    routing: ``buffer`` rows. Returns a dict of int32 arrays:

    ``dest`` [T, k]: the pair's row, or ``buffer`` (none: not held);
    ``row_pair`` [buffer]: the row's pair t * k + j, or T * k (a pad row),
    and ``row_token`` [buffer]: that pair's token t, or T;
    ``tile_group`` [tiles], ``num_tiles`` [1], ``group_rows`` [held]: what
    ``grouped_matmul`` reads; ``counts`` [held]: rows routed to each;
    ``lo``, ``hi``, ``lanes``, ``live``: where a tile of tokens has its
    rows in each expert's run (``routed_rows.tile_bounds``: what
    ``bps_moe_combine`` reads). Every one is named ``PLAN_NAME``.

    Given the pairs' ``weights`` [T, k] as well, ``row_weight`` [buffer]
    fp32 beside them: the weight of the row's pair, zero in a pad row (what
    the combine's backward scales a row's cotangent by); a constant, no
    gradient flows to ``weights`` through it.

    What it costs is a sort of the pairs and passes over them, whatever
    the routing: a pair's row is its expert's run and a running count of
    that expert's pairs before it; an expert's run in the buffer is a
    CONTIGUOUS stretch of the pairs sorted by expert, so a row tile is one
    slice of them; every table read by expert has ``held`` entries and is
    read by compares. Nothing takes one element an index over the buffer
    or over the pairs."""
    t, k = experts.shape
    held, tile = len(cfg.held), cfg.row_tile
    pairs = t * k
    tiles = -(-t * cfg.rows // tile) + held
    mine = experts.reshape(-1) == jnp.asarray(
        cfg.held, experts.dtype)[:, None]               # [held, pairs]
    to_held = mine.any(0)
    local = jnp.where(to_held, jnp.argmax(mine, 0).astype(jnp.int32), held)
    counts = mine.sum(1, dtype=jnp.int32)
    start = jnp.cumsum(counts) - counts                 # in the sorted pairs
    padded = jnp.maximum(-(-counts // tile), 1) * tile
    run = jnp.cumsum(padded) - padded                   # in the buffer
    before = jnp.cumsum(mine, 1, dtype=jnp.int32) - mine
    dest = jnp.where(to_held, jnp.where(mine, run[:, None] + before, 0).sum(0),
                     tiles * tile)
    # the pairs by expert, each expert's by pair (a stable sort); where
    # given, the weights' bits ride along, so that a row tile is ONE slice
    payload = [jnp.arange(pairs, dtype=jnp.int32)]
    if weights is not None:
        payload.append(jax.lax.bitcast_convert_type(jax.lax.stop_gradient(
            weights).astype(jnp.float32).reshape(-1), jnp.int32))
    by_expert = jnp.stack(jax.lax.sort(
        [local, *payload], num_keys=1, is_stable=True)[1:])
    first = jnp.arange(tiles, dtype=jnp.int32) * tile   # a tile's first row
    tile_group = jnp.clip(jnp.searchsorted(
        run, first, side="right", method="compare_all") - 1,
        0, held - 1).astype(jnp.int32)
    # tile i of expert g holds the sorted pairs from start[g] + i * tile -
    # run[g] on, as far as the expert has rows ([tiles]-sized tables). The
    # sorted pairs are padded by a tile: a slice that began inside their
    # last tile would otherwise be moved back to fit
    inside = first - run[tile_group]
    there = (inside[:, None] + jnp.arange(tile, dtype=jnp.int32)
             < counts[tile_group][:, None])             # [tiles, tile]
    by_expert = jnp.pad(by_expert, ((0, 0), (0, tile)))
    sliced = jax.vmap(lambda at: jax.lax.dynamic_slice(
        by_expert, (0, at), (len(payload), tile)))(start[tile_group] + inside)
    row_pair = jnp.where(there, sliced[:, 0], pairs).reshape(-1)
    plan = {"dest": dest.reshape(t, k), "row_pair": row_pair,
            "row_token": jnp.where(row_pair < pairs, row_pair // k, t),
            "tile_group": tile_group,
            "num_tiles": (padded.sum() // tile).astype(jnp.int32)[None],
            "group_rows": padded, "counts": counts,
            **tile_bounds(local.reshape(t, k), padded)}
    if weights is not None:
        plan["row_weight"] = jnp.where(there, jax.lax.bitcast_convert_type(
            sliced[:, 1], jnp.float32), 0.0).reshape(-1)
    return checkpoint_name(plan, PLAN_NAME)


# Dispatch and combine are each other's transposes, and both are written
# as GATHERS (rows by token, tokens by row): autodiff's transpose of a
# gather is a scatter-add, which serialises on the TPU. ``move``: how the
# rows travel (``ops/routed_rows.py``): its kernels, or "ragged" for XLA's
# gathers over the whole buffer and every chosen pair.

@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dispatch(x, plan, tile, move):
    """[buffer, h]: token ``row_token[r]``'s row of ``x``, zeros in pad
    rows."""
    return take_rows(x, plan["row_token"], plan["num_tiles"], tile,
                     impl=move)


def _dispatch_fwd(x, plan, tile, move):
    return _dispatch(x, plan, tile, move), plan


def _dispatch_bwd(tile, move, plan, d_rows):
    return combine_rows(d_rows, plan["dest"], None, plan,
                        impl=move), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(y, weights, plan, tile, move):
    """[T, h]: sum over a token's pairs of weight * the pair's row of
    ``y``; a pair with no row here (its expert is not held) adds zero.
    ``plan``: ``plan_rows`` of the choice WITH these weights (the backward
    reads the rows' weights there)."""
    return combine_rows(y, plan["dest"], weights, plan, impl=move)


def _combine_fwd(y, weights, plan, tile, move):
    return _combine(y, weights, plan, tile, move), (y, weights, plan)


def _combine_bwd(tile, move, res, d_out):
    y, weights, plan = res
    d_y = take_rows(d_out, plan["row_token"], plan["num_tiles"], tile,
                    scale=plan["row_weight"], impl=move)
    d_w = combine_rows(y, plan["dest"], None, plan, d_out=d_out,
                       impl=move)
    return d_y, d_w.astype(weights.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_ffn(f, blk, cfg: RoutedConfig, sequences: int = 1):
    """The routed feed-forward of one layer over flattened tokens
    ``f`` [T, h] -> [T, h] (``sequences`` of them end to end: what a
    balanced choice is balanced over, see ``route``):
    ``shared(f) + sum over the token's chosen
    experts that are held here of weight * expert(f)``, every expert
    ``down(act(first f))`` with ``cfg.act`` the layer's function (``ACTS``:
    a gated SiLU over a fused ``gate_up`` [h, 2 m], or ``relu(.)^2`` over
    ``up`` [h, m]). ``blk``: ``router`` [h, num_experts]; ``experts``
    ``gate_up`` [held, h, 2 m] or ``up`` [held, h, m], ``down``
    [held, m, h]; optional ``shared``, the same MLP once at a width of its
    own, ``gate_up`` [h, 2 ms] or ``up`` [h, ms], ``down`` [ms, h];
    optional ``shared_gate`` [h, 1]: the shared expert's output times
    ``sigmoid(f shared_gate)``, a gate a token.

    No row is dropped, whatever the imbalance: the buffer of rows is sized
    for the worst routing. The grouped products and the movement of rows
    (to the buffer by live row tile, back by the rows a tile of tokens has
    here: ``ops/routed_rows.py``) take time by the rows routed this step,
    and so does the function between the products, forward and backward
    (``ops/routed_act.py``); the plan costs a sort of the chosen pairs,
    a few passes over them and a slice of the sorted pairs a row tile,
    whatever the routing (``plan_rows``; PERF.md section 5)."""
    dt = f.dtype
    tile = cfg.row_tile
    act, first = ACTS[cfg.act]
    move = resolve_rows(cfg.impl, *f.shape, blk["experts"]["down"].shape[1],
                        len(cfg.held), tile)
    with jax.named_scope("bps.moe"):
        with jax.named_scope("bps.moe.route"):
            with jax.named_scope("bps.moe.route.plan"):
                weights, experts = route(f, blk["router"], cfg, sequences)
                plan = plan_rows(experts, cfg, weights)
            rows = _dispatch(f, plan, tile, move)
        with jax.named_scope("bps.moe.experts"):
            def product(lhs, w):
                return grouped_matmul(
                    lhs, checkpoint_name(w.astype(dt), WEIGHTS_NAME),
                    plan["tile_group"], plan["num_tiles"],
                    plan["group_rows"], tile, cfg.impl)
            # never the kernels around lax.ragged_dot, as the rows' movement
            y = product(routed_act(
                product(rows, blk["experts"][first]), plan["num_tiles"],
                tile, cfg.act, cfg.impl if move != "ragged" else move),
                blk["experts"]["down"])
        with jax.named_scope("bps.moe.route"):
            out = _combine(y, weights, plan, tile, move)
        if "shared" in blk:
            with jax.named_scope("bps.moe.shared"):
                shared = act(
                    f @ blk["shared"][first].astype(dt)
                ) @ blk["shared"]["down"].astype(dt)
                if "shared_gate" in blk:
                    shared = shared * jax.nn.sigmoid(jnp.dot(
                        f, blk["shared_gate"].astype(dt),
                        preferred_element_type=jnp.float32)).astype(dt)
                out = out + shared
    return out

"""Plain reference of one chip's share of an ``afmoe`` decoder (arcee-ai
Trinity), the configuration ``trinity_mini_lm``.

Straightforward ``jax.numpy`` in float32, no kernel and nothing of
``byteps_tpu``. What it computes (``sizes`` holds every number):

* embedding: ``x = E[token] * sqrt(hidden)``;
* attention half of a layer: ``a = RMSNorm(x)``; ``q = a Wq`` as
  [s, heads, d], ``k = a Wk``, ``v = a Wv`` as [s, kv_heads, d],
  ``g = a Wg`` as [s, heads, d], no biases; RMSNorm over the d of each
  head of q and k; a ``*_sliding`` layer rotates q and k (RoPE, halves
  paired) and lets query i see key j where ``0 <= i - j < window``, a
  ``*_full`` layer has no positions and is causal;
  ``o = softmax(q k^T / sqrt(d) + mask) v``, each kv head serving
  ``heads / kv_heads`` query heads; ``x = x + RMSNorm((o * sigmoid(g)) Wo)``;
* feed-forward half: ``f = RMSNorm(x)``. Dense layer:
  ``m = (silu(f Wgate) * (f Wup)) Wdown``. Routed layer: ``s = sigmoid(f
  Wr)`` in float32 over all ``router_outputs``; S = the ``top_k`` largest
  (with ``sizes["balanced"]``: of the outputs standardised, below);
  ``w_e = route_scale * s_e / sum_{j in S} s_j``; ``m = shared(f) + sum
  over e in S that are HELD of w_e * expert_e(f)``: what the experts held
  on other chips would add is left out. ``x = x + RMSNorm(m)``;
* head: final RMSNorm, ``logits = x Whead`` (untied) over the rows held,
  the mean negative log-likelihood of the next token.

``balanced``: S is chosen on ``(l_e - mean_t l_e) / deviation_t l_e``,
``l = f Wr`` the router's outputs before the sigmoid, each expert's
standardised over the tokens of a sequence, so that a sequence chooses
every expert about ``s top_k / router_outputs`` times whatever the
router's weights. The published model reaches that with a selection
bias (S = the largest of ``s + b``) that its training framework moves by
``load_balance_coeff * sign(mean count - count)`` a step: state that the
harness cannot carry through a step, and too slow for this cell's load
(PERF.md section 6). The weights ``w_e`` are the chosen experts' own
scores either way.

The share is in ``sizes``: ``heads`` / ``kv_heads`` held, ``vocab_size``
rows held, ``held`` = the router outputs whose experts are held. The
scores are a masked [queries, s] product a head and a block of queries
at a time, the experts a loop of dense masked products over all rows, a
feed-forward a piece of the rows at a time, the head a chunk of positions
at a time, the batch a block of rows at a time within each layer, each
layer rematerialised: none changes a number that is computed.

``precision`` is ``pre_ln_transformer``'s: ``float32`` (THE reference),
``bfloat16``, or ``float8`` (the control). The router's scores stay
float32 in every precision, as the configuration states them.
"""

from __future__ import annotations

import importlib.util
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .pre_ln_transformer import (INIT_STD, _dot, _f32_dot, adamw,
                                 targets_of)

# The harness lets the reference follow its three steps (a minute or more,
# the first time on a machine) BEFORE it builds the program. A checkout
# whose program has no such model (the parent of the PR that brought this
# configuration, with the benchmark's new files laid over it) would fail
# only then; it ends here instead, at once. Nothing of the program is used.
if importlib.util.find_spec("byteps_tpu.models.decoder") is None:
    raise ImportError("this checkout's program has no byteps_tpu.models."
                      "decoder: it cannot run the configuration that "
                      "benchmark.reference.afmoe_share is the reference of")

QUERY_BLOCK = 2048      # queries a block of the [queries, s] scores
HEAD_CHUNK = 2048       # positions a chunk of the head
MLP_ROWS = 2048         # rows a piece of a feed-forward


def _static(sizes: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in sizes.items()))


def make_params(seed: int, sizes: dict):
    """The weights of one run, made on the device in one jitted call:
    N(0, 0.02) matrices, unit norm scales, float32, in the layout the
    program trains (a list of per-layer dicts)."""
    return _make_params(jax.random.PRNGKey(seed), _static(sizes))


@partial(jax.jit, static_argnums=(1,))
def _make_params(key, static_sizes):
    z = dict(static_sizes)
    h, d, held = z["hidden"], z["head_dim"], len(z["held"])
    keys = iter(jax.random.split(key, 16 * len(z["layer_kinds"]) + 2))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * INIT_STD

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def mlp(width, *lead):
        return {"gate_up": normal(*lead, h, 2 * width),
                "down": normal(*lead, width, h)}

    def layer(kind):
        attn = {"norm_in": ones(h), "q": normal(h, z["heads"], d),
                "k": normal(h, z["kv_heads"], d),
                "v": normal(h, z["kv_heads"], d),
                "gate": normal(h, z["heads"], d), "q_norm": ones(d),
                "k_norm": ones(d), "o": normal(z["heads"], d, h),
                "norm_post": ones(h)}
        ffn = {"norm_pre": ones(h), "norm_post": ones(h)}
        if kind.startswith("dense"):
            ffn.update(mlp(z["mlp_dim"]))
        else:
            ffn["router"] = normal(h, z["router_outputs"])
            ffn["experts"] = mlp(z["moe_dim"], held)
            if z["shared_experts"]:
                ffn["shared"] = mlp(z["shared_experts"] * z["moe_dim"])
        return {"attn": attn, "ffn": ffn}

    return {"embed": normal(z["vocab_size"], h),
            "layers": [layer(kind) for kind in z["layer_kinds"]],
            "final_norm": ones(h),
            "head": normal(z["vocab_size"], h)}


# ---------------------------------------------------------------- model

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _gated(h):
    m = h.shape[-1] // 2
    return jax.nn.silu(h[..., :m]) * h[..., m:]


def _scores_block(q, k, v, first, window, dot):
    """One head's block of queries [b, n, d], whose first row is position
    ``first``, over all keys [b, s, d]."""
    n, s, d = q.shape[1], k.shape[1], q.shape[-1]
    scores = dot("bqd,bkd->bqk", q, k) / math.sqrt(d)
    rows = first + jnp.arange(n)[:, None]
    cols = jnp.arange(s)[None, :]
    keep = rows >= cols
    if window is not None:
        keep = jnp.logical_and(keep, rows - cols < window)
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return dot("bqk,bkd->bqd", probs, v)


def _attention(x, blk, z, sliding, dot):
    b, s, _ = x.shape
    heads, group = z["heads"], z["heads"] // z["kv_heads"]
    a = _rmsnorm(x, blk["norm_in"], z["norm_eps"])
    q = _rmsnorm(dot("bsh,hnd->bsnd", a, blk["q"]), blk["q_norm"],
                 z["norm_eps"])
    k = _rmsnorm(dot("bsh,hnd->bsnd", a, blk["k"]), blk["k_norm"],
                 z["norm_eps"])
    v = dot("bsh,hnd->bsnd", a, blk["v"])
    gate = dot("bsh,hnd->bsnd", a, blk["gate"])
    if sliding:
        q, k = _rope(q, z["rope_theta"]), _rope(k, z["rope_theta"])
    n = min(s, QUERY_BLOCK)
    blocks = s // n
    # [heads * blocks, b, n, d] blocks of queries, head-major
    qb = jnp.moveaxis(q.reshape(b, blocks, n, heads, -1), (3, 1), (0, 1))
    qb = qb.reshape((heads * blocks,) + qb.shape[2:])
    kt, vt = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)   # [kv, b, s, d]

    @jax.checkpoint
    def one(args):
        i, qi = args
        kv = i // blocks // group
        return _scores_block(qi, kt[kv], vt[kv], (i % blocks) * n,
                             z["window"] if sliding else None, dot)

    out = jax.lax.map(one, (jnp.arange(heads * blocks), qb))
    out = jnp.moveaxis(out.reshape((heads, blocks) + out.shape[1:]),
                       (0, 1), (3, 1)).reshape(b, s, heads, -1)
    out = dot("bsnd,ndh->bsh", out * jax.nn.sigmoid(gate), blk["o"])
    return _rmsnorm(out, blk["norm_post"], z["norm_eps"])


def _mlp(f, w, dot):
    """The gated-SiLU feed-forward of rows [T, h], ``MLP_ROWS`` rows at a
    time (what it keeps for its backward pass is then one piece's)."""
    @jax.checkpoint
    def piece(rows):
        return dot("tm,mh->th", _gated(dot("th,hm->tm", rows, w["gate_up"])),
                   w["down"])

    t, h = f.shape
    if t <= MLP_ROWS or t % MLP_ROWS:
        return piece(f)
    return jax.lax.map(piece, f.reshape(t // MLP_ROWS, MLP_ROWS, h)).reshape(
        t, h)


def _routed(f, blk, z, dot, sequences=1):
    """[T, h] -> [T, h]: the shared expert and the held experts' part;
    ``f`` is ``sequences`` sequences end to end."""
    logits = _f32_dot("th,he->te", f, blk["router"])
    scores = jax.nn.sigmoid(logits)
    if z.get("balanced"):       # chosen on the outputs standardised
        by_seq = jax.lax.stop_gradient(logits).reshape(
            sequences, -1, logits.shape[-1])
        centred = by_seq - by_seq.mean(1, keepdims=True)
        centred /= jnp.sqrt(
            jnp.mean(centred * centred, 1, keepdims=True) + 1e-12)
        _, chosen = jax.lax.top_k(centred.reshape(logits.shape), z["top_k"])
        top = jnp.take_along_axis(scores, chosen, axis=-1)
    else:
        top, chosen = jax.lax.top_k(scores, z["top_k"])
    weights = z["route_scale"] * top / top.sum(-1, keepdims=True)
    out = (_mlp(f, blk["shared"], dot) if "shared" in blk
           else jnp.zeros_like(f))

    @jax.checkpoint
    def part(w, e):
        mine = jnp.where(chosen == e, weights, 0.0).sum(-1)     # [T]
        return mine[:, None] * _mlp(f, w, dot)

    def one(out, expert):       # the sum is carried, not rematerialised
        return out + part(*expert), None

    out, _ = jax.lax.scan(one, out, (blk["experts"],
                                     jnp.asarray(z["held"], jnp.int32)))
    return out


def _ffn(x, blk, z, routed, dot):
    b, s, h = x.shape
    f = _rmsnorm(x, blk["norm_pre"], z["norm_eps"]).reshape(b * s, h)
    m = _routed(f, blk, z, dot, b) if routed else _mlp(f, blk, dot)
    return _rmsnorm(m.reshape(b, s, h), blk["norm_post"], z["norm_eps"])


def layer(x, blk, z, kind, dot):
    """One layer of ``kind`` (exported: the tests hold the program's
    layers and the shares of the experts against it)."""
    x = x + _attention(x, blk["attn"], z, kind.endswith("sliding"), dot)
    return x + _ffn(x, blk["ffn"], z, kind.startswith("moe"), dot)


def nll_sum_and_count(params, tokens, targets, z, precision):
    """Sum of the negative log-likelihoods of the targets >= 0 of
    ``tokens`` [blocks, rows, s], and how many there are. A layer takes
    the blocks one after another (``lax.map``), and so does the head: what
    a layer keeps for its backward pass is one block's, and a layer's
    gradients add up over the blocks inside that layer's own pass."""
    dot = partial(_dot, precision)
    x = params["embed"][tokens] * math.sqrt(z["hidden"])
    for kind, blk in zip(z["layer_kinds"], params["layers"]):
        one = jax.checkpoint(partial(layer, z=z, kind=kind, dot=dot))
        x = jax.lax.map(lambda xb, one=one, blk=blk: one(xb, blk), x)
    x = _rmsnorm(x, params["final_norm"], z["norm_eps"])
    blocks, b, s, _ = x.shape
    n = min(s, HEAD_CHUNK)
    valid = targets >= 0

    @jax.checkpoint
    def chunk(args):
        xc, tc, vc = args
        logp = jax.nn.log_softmax(dot("bch,vh->bcv", xc, params["head"]), -1)
        nll = -jnp.take_along_axis(
            logp, jnp.where(vc, tc, 0)[..., None], axis=-1)[..., 0]
        return (nll * vc).sum()

    def split(a):       # [blocks * chunks, b, n, ...]
        a = jnp.moveaxis(a.reshape((blocks, b, s // n, n) + a.shape[3:]),
                         2, 1)
        return a.reshape((blocks * (s // n),) + a.shape[2:])

    nll = jax.lax.map(chunk, (split(x), split(targets), split(valid)))
    return nll.sum(), valid.sum().astype(jnp.float32)


def loss_and_grads(params, tokens, targets, z, precision):
    """Mean loss over every target of the batch and its gradient, the
    batch given in blocks: ``tokens``/``targets`` are [blocks, rows, s].
    ONE differentiation over all the blocks: a gradient tree a block and
    their sum would not fit beside 603 M parameters and their moments."""
    def mean_loss(p):
        nll, cnt = nll_sum_and_count(p, tokens, targets, z, precision)
        return nll / cnt

    return jax.value_and_grad(mean_loss)(params)


# ------------------------------------------------------------- readings

def leaf_norms(tree):
    """The L2 norm of every leaf of a parameter-shaped tree as one vector
    in ``leaf_names`` order. A layer's held experts are ONE leaf each
    (``experts.gate_up``, ``experts.down``): a single expert's rows hang
    on choices that rounding flips, the layer's do not. The harness takes
    the program's norms with this same function."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def leaf_names(tree) -> list:
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


def train_steps(params0, batches, sizes: dict, optimizer: dict, kind: str,
                rows_per_block: int, precision: str = "float32",
                row_sharding=None) -> dict:
    """Follow the first ``len(batches)`` training steps from ``params0``
    (``pre_ln_transformer.train_steps``' contract): each step's loss, the
    norm of every leaf of the first gradient, and the norm of every leaf
    of the parameters' change after the last step. ``params0`` is not
    consumed."""
    losses, grad_norms = [], None
    p = jax.tree_util.tree_map(jnp.copy, params0)
    m = jax.tree_util.tree_map(jnp.zeros_like, params0)
    v = jax.tree_util.tree_map(jnp.zeros_like, params0)
    for t, batch in enumerate(batches, start=1):
        tokens, targets = targets_of(batch, kind)
        rows, s = tokens.shape
        if rows % rows_per_block:
            raise ValueError(f"{rows} rows do not divide into blocks of "
                             f"{rows_per_block}")
        shape = (rows // rows_per_block, rows_per_block, s)
        tokens, targets = tokens.reshape(shape), targets.reshape(shape)
        if row_sharding is not None:
            tokens = jax.device_put(tokens, row_sharding)
            targets = jax.device_put(targets, row_sharding)
        p, m, v, loss, gn = _step(p, m, v, tokens, targets, jnp.float32(t),
                                  _static(sizes), _static(optimizer),
                                  precision)
        losses.append(loss)
        if t == 1:
            grad_norms = gn
    change = _change_norms(p, params0)
    del p, m, v
    return {"loss": [float(x) for x in losses],
            "grad_norm": np.asarray(grad_norms, np.float64),
            "change_norm": np.asarray(change, np.float64),
            "leaf_names": leaf_names(params0)}


@partial(jax.jit, static_argnums=(6, 7, 8), donate_argnums=(0, 1, 2))
def _step(p, m, v, tokens, targets, t, static_sizes, static_opt, precision):
    loss, g = loss_and_grads(p, tokens, targets, dict(static_sizes),
                             precision)
    p, m, v = adamw(p, m, v, g, t, dict(static_opt))
    return p, m, v, loss, leaf_norms(g)


@jax.jit
def _change_norms(p, p0):
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0))

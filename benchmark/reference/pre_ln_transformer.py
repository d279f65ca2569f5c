"""Plain reference of the pre-LN transformer language model that the
configurations ``bert_large_mlm`` and ``gpt2_medium_lm`` both are.

Straightforward ``jax.numpy``: token + learned position embeddings,
``layers`` blocks of [LayerNorm -> attention over the whole [s, s] scores
with a plain softmax -> residual, LayerNorm -> tanh-GELU MLP -> residual],
a final LayerNorm and the head tied to the token embedding; the loss is
the mean negative log-likelihood over the positions whose target is >= 0
(masked-LM targets, or the next token). AdamW is written out. No kernel
and nothing of ``byteps_tpu``; the only concessions to the chip's memory
are that a step walks the batch in blocks of rows and keeps one layer's
activations at a time (``jax.checkpoint`` around the block), neither of
which changes a number that is computed.

``precision`` selects how every matrix product is taken:

* ``float32``  - operands and products in float32 at ``highest``; THE
  reference.
* ``bfloat16`` - operands rounded to bfloat16, float32 accumulation: what
  the configurations state for the program. Used by tests.
* ``float8``   - operands rounded to float8 (e4m3 forward, e5m2 for the
  cotangents) with a per-tensor scale from their own largest value,
  float32 accumulation: the nearest precision below the stated one, the
  benchmark's CONTROL, which ``correct`` has to refuse.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
INIT_STD = 0.02


# ------------------------------------------------------------- products

def _scaled_round(x, dtype):
    """``x`` rounded to ``dtype`` and back, with the scale that puts its
    largest magnitude on the dtype's largest finite value."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _f32_dot(spec, a, b):
    return jnp.einsum(spec, a, b, precision="highest",
                      preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _f8_dot(spec, a, b):
    return _f32_dot(spec, _scaled_round(a, jnp.float8_e4m3fn),
                    _scaled_round(b, jnp.float8_e4m3fn))


def _f8_dot_fwd(spec, a, b):
    qa = _scaled_round(a, jnp.float8_e4m3fn)
    qb = _scaled_round(b, jnp.float8_e4m3fn)
    return _f32_dot(spec, qa, qb), (qa, qb)


def _f8_dot_bwd(spec, res, ct):
    qa, qb = res
    ins, out = spec.split("->")
    ia, ib = ins.split(",")
    qc = _scaled_round(ct, jnp.float8_e5m2)
    return (_f32_dot(f"{out},{ib}->{ia}", qc, qb),
            _f32_dot(f"{ia},{out}->{ib}", qa, qc))


_f8_dot.defvjp(_f8_dot_fwd, _f8_dot_bwd)


def _dot(precision: str, spec: str, a, b):
    if precision == "float32":
        return _f32_dot(spec, a, b)
    if precision == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "float8":
        return _f8_dot(spec, a, b)
    raise ValueError(f"precision must be one of {PRECISIONS}, "
                     f"got {precision!r}")


# ---------------------------------------------------------------- model

def make_params(seed: int, sizes: dict):
    """The weights of one run, made on the device in one jitted call:
    N(0, 0.02) matrices and embeddings, unit LayerNorm scales, zero
    biases, float32, in the layout the program trains (blocks stacked on
    a leading layer axis)."""
    return _make_params(jax.random.PRNGKey(seed), _static(sizes))


def _static(sizes: dict):
    return tuple(sorted((k, v) for k, v in sizes.items()))


@partial(jax.jit, static_argnums=(1,))
def _make_params(key, static_sizes):
    z = dict(static_sizes)
    n, h, m, heads = z["layers"], z["hidden"], z["mlp_dim"], z["heads"]
    k = jax.random.split(key, 6)

    def normal(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * INIT_STD

    def ln(*lead):
        return {"scale": jnp.ones(lead + (h,), jnp.float32),
                "bias": jnp.zeros(lead + (h,), jnp.float32)}

    return {
        "embed": {"tok": normal(k[0], (z["vocab_size"], h)),
                  "pos": normal(k[1], (z["max_seq"], h))},
        "blocks": {
            "ln1": ln(n),
            "qkv": normal(k[2], (n, h, 3, heads, h // heads)),
            "attn_out": normal(k[3], (n, h, h)),
            "ln2": ln(n),
            "mlp_in": normal(k[4], (n, h, m)),
            "mlp_in_b": jnp.zeros((n, m), jnp.float32),
            "mlp_out": normal(k[5], (n, m, h)),
            "mlp_out_b": jnp.zeros((n, h), jnp.float32),
        },
        "final_ln": ln(),
    }


def _layernorm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, blk, z, dot):
    b, s, h = x.shape
    d = h // z["heads"]
    y = _layernorm(x, blk["ln1"], z["ln_eps"])
    qkv = dot("bsh,hcnd->bscnd", y, blk["qkv"])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = dot("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
    if z["causal"]:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = dot("bnqk,bknd->bqnd", probs, v).reshape(b, s, h)
    x = x + dot("bsh,hk->bsk", ctx, blk["attn_out"])
    y = _layernorm(x, blk["ln2"], z["ln_eps"])
    y = _gelu_tanh(dot("bsh,hm->bsm", y, blk["mlp_in"]) + blk["mlp_in_b"])
    return x + dot("bsm,mh->bsh", y, blk["mlp_out"]) + blk["mlp_out_b"]


def nll_sum_and_count(params, tokens, targets, z, precision, n_select):
    """Sum of the negative log-likelihoods of the targets >= 0 of
    ``tokens`` [rows, s], and how many there are. ``n_select`` is the
    largest number of targets a row has: the head runs on that many
    positions of each row, the targeted ones first."""
    dot = partial(_dot, precision)
    s = tokens.shape[1]
    x = params["embed"]["tok"][tokens] + params["embed"]["pos"][:s]

    @jax.checkpoint
    def body(x, blk):
        return _block(x, blk, z, dot), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _layernorm(x, params["final_ln"], z["ln_eps"])
    valid = targets >= 0
    if n_select < s:
        order = jnp.argsort(~valid, axis=1, stable=True)[:, :n_select]
        x = jnp.take_along_axis(x, order[..., None], axis=1)
        targets = jnp.take_along_axis(targets, order, axis=1)
        valid = jnp.take_along_axis(valid, order, axis=1)
    logits = dot("bph,vh->bpv", x, params["embed"]["tok"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (nll * valid).sum(), valid.sum().astype(jnp.float32)


def loss_and_grads(params, tokens, targets, z, precision, n_select):
    """Mean loss over every target of the batch and its gradient, the
    batch given in blocks: ``tokens``/``targets`` are [blocks, rows, s]."""
    def one(carry, block):
        (nll, cnt), g = jax.value_and_grad(
            lambda p: nll_sum_and_count(p, *block, z, precision, n_select),
            has_aux=True)(params)
        gsum, nll_sum, cnt_sum = carry
        return (jax.tree_util.tree_map(jnp.add, gsum, g),
                nll_sum + nll, cnt_sum + cnt), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (gsum, nll, cnt), _ = jax.lax.scan(
        one, (zeros, jnp.float32(0), jnp.float32(0)), (tokens, targets))
    return nll / cnt, jax.tree_util.tree_map(lambda g: g / cnt, gsum)


def adamw(params, m, v, grads, t, opt):
    """One AdamW update as optax.adamw defines it: decoupled weight decay
    on every leaf, bias-corrected moments, ``t`` counted from 1."""
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def leaf(p, m, v, g):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        return (p - opt["learning_rate"] * (step + opt["weight_decay"] * p),
                m, v)

    out = jax.tree_util.tree_map(leaf, params, m, v, grads)
    pick = lambda i: jax.tree_util.tree_map(
        lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


# ------------------------------------------------------------- readings

def leaf_norms(tree):
    """The L2 norm of every leaf of a parameter-shaped tree, and of every
    layer of a stacked leaf, as one vector in ``leaf_names`` order. The
    harness takes the program's norms with this same function."""
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        x = leaf.astype(jnp.float32)
        if _stacked(path):
            out.append(jnp.sqrt((x * x).reshape(x.shape[0], -1).sum(1)))
        else:
            out.append(jnp.sqrt((x * x).sum())[None])
    return jnp.concatenate(out)


def leaf_names(tree) -> list:
    names = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = ".".join(str(getattr(k, "key", k)) for k in path)
        if _stacked(path):
            names += [f"{name}[{i}]" for i in range(leaf.shape[0])]
        else:
            names.append(name)
    return names


def _stacked(path) -> bool:
    return getattr(path[0], "key", None) == "blocks"


def targets_of(batch, kind: str):
    """(tokens, targets) of one host batch of the traffic generator."""
    if kind == "mlm":
        return batch
    tokens = batch
    last = np.full((tokens.shape[0], 1), -1, tokens.dtype)
    return tokens, np.concatenate([tokens[:, 1:], last], axis=1)


def train_steps(params0, batches, sizes: dict, optimizer: dict, kind: str,
                rows_per_block: int, precision: str = "float32",
                row_sharding=None) -> dict:
    """Follow the first ``len(batches)`` training steps from ``params0``
    and return what the benchmark compares: each step's loss, the norm of
    every leaf of the first gradient, and the norm of every leaf of the
    parameters' change after the last step. ``params0`` is not consumed.
    ``row_sharding``: where a block's rows are split over several chips,
    the sharding of the [blocks, rows, s] arrays."""
    z = dict(sizes)
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
        n_select = int((targets >= 0).sum(1).max())
        shape = (rows // rows_per_block, rows_per_block, s)
        tokens, targets = tokens.reshape(shape), targets.reshape(shape)
        if row_sharding is not None:
            tokens = jax.device_put(tokens, row_sharding)
            targets = jax.device_put(targets, row_sharding)
        p, m, v, loss, gn = _step(p, m, v, tokens, targets,
                                  jnp.float32(t), _static(z),
                                  _static(optimizer), precision,
                                  n_select)
        losses.append(loss)
        if t == 1:
            grad_norms = gn
    change = _change_norms(p, params0)
    del p, m, v
    return {"loss": [float(x) for x in losses],
            "grad_norm": np.asarray(grad_norms, np.float64),
            "change_norm": np.asarray(change, np.float64),
            "leaf_names": leaf_names(params0)}


@partial(jax.jit, static_argnums=(6, 7, 8, 9), donate_argnums=(0, 1, 2))
def _step(p, m, v, tokens, targets, t, static_sizes, static_opt, precision,
          n_select):
    loss, g = loss_and_grads(p, tokens, targets, dict(static_sizes),
                             precision, n_select)
    p, m, v = adamw(p, m, v, g, t, dict(static_opt))
    return p, m, v, loss, leaf_norms(g)


@jax.jit
def _change_norms(p, p0):
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0))

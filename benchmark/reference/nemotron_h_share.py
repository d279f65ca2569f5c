"""Plain reference of one chip's share of a ``nemotron_h`` decoder (NVIDIA
Nemotron 3 Nano), the configuration ``nemotron3_nano_lm``.

Straightforward ``jax.numpy`` in float32, no kernel and nothing of
``byteps_tpu``. Every layer is ONE mixer, ``x = x + mixer(RMSNorm(x))``
(``sizes`` holds every number; ``layer_kinds`` names each layer's):

* embedding: ``x = E[token]``, not scaled;
* ``ssm``, Mamba-2: ``z, xBC, dt = a W_in`` ([hidden, inner + (inner +
  2 groups n) + heads]); ``xBC`` passes a depthwise causal convolution of
  ``conv_kernel`` taps with bias, then SiLU, and splits into ``x``
  [s, heads, p], ``B`` and ``C`` [s, groups, n] (head h reads group
  ``h // (heads / groups)``); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the state of a head, in R^{p x n}, goes ONE
  POSITION AT A TIME (not in chunks, as the program has it)::

      H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T      y_t = H_t C_t + D x_t

  then ``y = RMSNorm_groups(y * silu(z))`` over ``groups`` runs of
  channels and ``y W_out``. The recurrence is a ``lax.scan`` over
  stretches of ``STRETCH`` positions, each stretch rematerialised, so that
  what its backward pass keeps is a stretch's states and not a sequence's,
  ``GROUPS_AT_ONCE`` groups of heads at a time;
* ``attn``: ``q = a Wq`` [s, heads, d], ``k = a Wk``, ``v = a Wv``
  [s, kv_heads, d]; no positions, no norm on q or k, no gate;
  ``o = softmax(q k^T / sqrt(d) + causal mask) v`` a head and a block of
  query rows at a time, each kv head serving ``heads / kv_heads`` query
  heads; ``o Wo``;
* ``moe``: ``s = sigmoid(a Wr)`` in float32 over all ``router_outputs``;
  S = the ``top_k`` largest (with ``sizes["balanced"]``: of the outputs
  standardised an expert over a sequence's tokens, as ``afmoe_share`` has
  it and for the same reason); ``w_e = route_scale * s_e / sum_{j in S}
  s_j``; ``shared(a) + sum over e in S that are HELD of w_e expert_e(a)``,
  a loop over the held experts, every expert NOT gated:
  ``down(relu(up a)^2)``, the shared one at ``shared_dim``. What the
  experts held on other chips would add is left out;
* head: final RMSNorm, ``logits = x Whead`` (untied) over the rows held,
  the mean negative log-likelihood of the next token.

``precision`` is ``pre_ln_transformer``'s: ``float32`` (THE reference),
``bfloat16``, or ``float8`` (the control): the matrix products' operands,
and the operands of the recurrence (``dt x``, ``B``, ``C``: what the
program's chunked products take in its compute dtype). The router's
scores, ``dt``, the decays and the carried state stay float32 in every
precision, as the configuration states them.
"""

from __future__ import annotations

import importlib.util
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .afmoe_share import (HEAD_CHUNK, QUERY_BLOCK, _change_norms, _rmsnorm,
                          _scores_block, _static, leaf_names, leaf_norms)
from .pre_ln_transformer import (INIT_STD, _dot, _f32_dot, _scaled_round,
                                 adamw, targets_of)

__all__ = ["make_params", "train_steps", "leaf_norms", "leaf_names"]

# As afmoe_share: a checkout whose program has no such model (the parent of
# the PR that brought this configuration, with the benchmark's new files
# laid over it) ends here, at once, and not after the reference's minutes.
if importlib.util.find_spec("byteps_tpu.models.mamba2") is None:
    raise ImportError("this checkout's program has no byteps_tpu.models."
                      "mamba2: it cannot run the configuration that "
                      "benchmark.reference.nemotron_h_share is the "
                      "reference of")

STRETCH = 128           # positions a rematerialised stretch of the scan
GROUPS_AT_ONCE = 4      # groups of state-space heads the scan carries together
MLP_ROWS = 2048         # rows a piece of a feed-forward
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4     # the seeded dt_bias


def make_params(seed: int, sizes: dict):
    """The weights of one run, made on the device in one jitted call, in
    the layout the program trains (a list of per-layer dicts): matrices
    N(0, 0.02), unit norm scales; a state-space mixer's convolution
    uniform within 1 / sqrt(taps), ``dt_bias`` the inverse softplus of a
    step drawn log-uniform in [0.001, 0.1] and floored at 1e-4, ``A_log``
    the log of uniform [1, 16], ``D`` one."""
    return _make_params(jax.random.PRNGKey(seed), _static(sizes))


@partial(jax.jit, static_argnums=(1,))
def _make_params(key, static_sizes):
    z = dict(static_sizes)
    h, d, held = z["hidden"], z["head_dim"], len(z["held"])
    keys = iter(jax.random.split(key, 16 * len(z["layer_kinds"]) + 2))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * INIT_STD

    def uniform(lo, hi, *shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def ssm():
        heads, inner = z["ssm_heads"], z["ssm_heads"] * z["ssm_head_dim"]
        conv = inner + 2 * z["ssm_groups"] * z["ssm_state"]
        bound = 1.0 / math.sqrt(z["conv_kernel"])
        step = jnp.maximum(jnp.exp(
            uniform(0.0, 1.0, heads) * (math.log(DT_MAX) - math.log(DT_MIN))
            + math.log(DT_MIN)), DT_FLOOR)
        return {"in_proj": normal(h, inner + conv + heads),
                "conv_w": uniform(-bound, bound, z["conv_kernel"], conv),
                "conv_b": uniform(-bound, bound, conv),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(uniform(1.0, 16.0, heads)),
                "D": ones(heads), "gated_norm": ones(inner),
                "out_proj": normal(inner, h)}

    def layer(kind):
        blk = {"norm": ones(h)}
        if kind == "ssm":
            blk.update(ssm())
        elif kind == "attn":
            blk.update(q=normal(h, z["heads"], d),
                       k=normal(h, z["kv_heads"], d),
                       v=normal(h, z["kv_heads"], d),
                       o=normal(z["heads"], d, h))
        else:
            blk["router"] = normal(h, z["router_outputs"])
            blk["experts"] = {"up": normal(held, h, z["moe_dim"]),
                              "down": normal(held, z["moe_dim"], h)}
            blk["shared"] = {"up": normal(h, z["shared_dim"]),
                             "down": normal(z["shared_dim"], h)}
        return blk

    return {"embed": normal(z["vocab_size"], h),
            "layers": [layer(kind) for kind in z["layer_kinds"]],
            "final_norm": ones(h),
            "head": normal(z["vocab_size"], h)}


# ---------------------------------------------------------------- model

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _operand(precision, a):
    """``a`` as an operand of a product in ``precision``: itself in
    float32, rounded to bfloat16, or to float8 as ``_dot`` rounds (e4m3
    going forward, e5m2 the cotangent coming back)."""
    if precision == "float32":
        return a
    if precision == "bfloat16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    return _scaled_round(a, jnp.float8_e4m3fn)


def _operand_fwd(precision, a):
    return _operand(precision, a), None


def _operand_bwd(precision, _, ct):
    return (_scaled_round(ct, jnp.float8_e5m2) if precision == "float8"
            else ct,)


_operand.defvjp(_operand_fwd, _operand_bwd)


def _recurrence(xd, decay, b, c):
    """``y_t = H_t C_t`` with ``H_t = decay_t H_{t-1} + xd_t B_t^T``, one
    position at a time from a zero state, ``GROUPS_AT_ONCE`` groups of
    heads together and one such piece after another. ``xd`` [groups, s,
    batch, per, p] (``dt x``), ``decay`` [groups, s, batch, per], ``b``,
    ``c`` [groups, s, batch, n] (a group's, shared by its ``per``
    heads)."""
    groups, s, bsz, per, p = xd.shape
    n = b.shape[-1]
    at_once = GROUPS_AT_ONCE if groups % GROUPS_AT_ONCE == 0 else groups

    def one(state, at):         # state [at_once, batch, per, p, n]
        xt, dec, bt, ct = at
        state = (dec[..., None, None] * state
                 + xt[..., None] * bt[:, :, None, None, :])
        return state, (state * ct[:, :, None, None, :]).sum(-1)

    @jax.checkpoint
    def stretch(state, at):
        return jax.lax.scan(one, state, at)

    n_stretch = s // STRETCH if s % STRETCH == 0 and s > STRETCH else 1

    @jax.checkpoint
    def piece(at):              # [at_once, s, ...] -> [stretches, len, at_once, ...]
        split = lambda t: jnp.moveaxis(t, 0, 1).reshape(  # noqa: E731
            (n_stretch, s // n_stretch, at_once) + t.shape[2:])
        first = jnp.zeros((at_once, bsz, per, p, n), jnp.float32)
        _, y = jax.lax.scan(stretch, first, tuple(split(t) for t in at))
        return jnp.moveaxis(y.reshape(s, at_once, bsz, per, p), 1, 0)

    pieces = lambda t: t.reshape((groups // at_once, at_once) + t.shape[1:])  # noqa: E731
    y = jax.lax.map(piece, tuple(pieces(t) for t in (xd, decay, b, c)))
    return y.reshape(xd.shape)


def _ssm(a, blk, z, dot, precision):
    bsz, s, _ = a.shape
    heads, p = z["ssm_heads"], z["ssm_head_dim"]
    groups, n = z["ssm_groups"], z["ssm_state"]
    inner, gn, per = heads * p, groups * n, heads // groups

    @jax.checkpoint
    def front(a, w_in, conv_w, conv_b, dt_bias):
        zxbcdt = dot("bsh,hm->bsm", a, w_in)
        xbc = zxbcdt[..., inner:2 * inner + 2 * gn]
        taps = conv_w.shape[0]
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        xbc = jax.nn.silu(conv_b + sum(
            conv_w[k] * padded[:, k:k + s] for k in range(taps)))
        return (zxbcdt[..., :inner], xbc[..., :inner],
                xbc[..., inner:inner + gn], xbc[..., inner + gn:],
                jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * gn:] + dt_bias))

    gate, x, b, c, dt = front(a, blk["in_proj"], blk["conv_w"],
                              blk["conv_b"], blk["dt_bias"])
    x = x.reshape(bsz, s, groups, per, p)
    dt = dt.reshape(bsz, s, groups, per)
    decay = jnp.exp(dt * -jnp.exp(blk["A_log"]).reshape(groups, per))

    def by_group(t):            # [b, s, groups, ...] -> [groups, s, b, ...]
        return jnp.moveaxis(t, (2, 1), (0, 1))

    y = _recurrence(
        by_group(_operand(precision, dt[..., None] * x)), by_group(decay),
        *(by_group(_operand(precision, t).reshape(bsz, s, groups, n))
          for t in (b, c)))
    y = jnp.moveaxis(y, (0, 1), (2, 1)) + blk["D"].reshape(
        groups, per)[:, :, None] * x

    @jax.checkpoint
    def back(y, gate, scale, w_out):
        y = (y.reshape(bsz, s, inner) * jax.nn.silu(gate)).reshape(
            bsz, s, groups, inner // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + z["norm_eps"])
        return dot("bsm,mh->bsh", y.reshape(bsz, s, inner) * scale, w_out)

    return back(y, gate, blk["gated_norm"], blk["out_proj"])


def _attention(a, blk, z, dot):
    b, s, _ = a.shape
    heads, group = z["heads"], z["heads"] // z["kv_heads"]
    q = dot("bsh,hnd->bsnd", a, blk["q"])
    k = dot("bsh,hnd->bsnd", a, blk["k"])
    v = dot("bsh,hnd->bsnd", a, blk["v"])
    n = min(s, QUERY_BLOCK)
    blocks = s // n
    qb = jnp.moveaxis(q.reshape(b, blocks, n, heads, -1), (3, 1), (0, 1))
    qb = qb.reshape((heads * blocks,) + qb.shape[2:])
    kt, vt = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)   # [kv, b, s, d]

    @jax.checkpoint
    def one(args):
        i, qi = args
        kv = i // blocks // group
        return _scores_block(qi, kt[kv], vt[kv], (i % blocks) * n, None, dot)

    out = jax.lax.map(one, (jnp.arange(heads * blocks), qb))
    out = jnp.moveaxis(out.reshape((heads, blocks) + out.shape[1:]),
                       (0, 1), (3, 1)).reshape(b, s, heads, -1)
    return dot("bsnd,ndh->bsh", out, blk["o"])


def _mlp(f, w, dot):
    """``down(relu(up f)^2)`` of rows [T, h], ``MLP_ROWS`` rows at a
    time."""
    @jax.checkpoint
    def piece(rows):
        return dot("tm,mh->th", jnp.square(jax.nn.relu(
            dot("th,hm->tm", rows, w["up"]))), w["down"])

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


def layer(x, blk, z, kind, precision="float32"):
    """One layer of ``kind`` (exported: the tests hold the program's
    layers and the shares of the experts against it)."""
    dot = partial(_dot, precision)
    a = _rmsnorm(x, blk["norm"], z["norm_eps"])
    if kind == "ssm":
        return x + _ssm(a, blk, z, dot, precision)
    if kind == "attn":
        return x + _attention(a, blk, z, dot)
    b, s, h = a.shape
    return x + _routed(a.reshape(b * s, h), blk, z, dot, b).reshape(b, s, h)


def nll_sum_and_count(params, tokens, targets, z, precision):
    """Sum of the negative log-likelihoods of the targets >= 0 of
    ``tokens`` [blocks, rows, s], and how many there are; a layer takes
    the blocks one after another, and so does the head
    (``afmoe_share``'s)."""
    dot = partial(_dot, precision)
    x = params["embed"][tokens]
    for kind, blk in zip(z["layer_kinds"], params["layers"]):
        one = jax.checkpoint(partial(layer, z=z, kind=kind,
                                     precision=precision))
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
    """Mean loss over every target of the batch and its gradient, ONE
    differentiation over all the blocks."""
    def mean_loss(p):
        nll, cnt = nll_sum_and_count(p, tokens, targets, z, precision)
        return nll / cnt

    return jax.value_and_grad(mean_loss)(params)


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

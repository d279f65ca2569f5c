"""Plain reference of one chip's share of a ``qwen3_next`` decoder
(Qwen3-Next-80B-A3B), the configuration ``qwen3_next_80b_a3b_lm``.

Straightforward ``jax.numpy`` in float32, no kernel and nothing of
``byteps_tpu``. A layer is two halves with a norm BEFORE each and none
after, ``x = x + mixer(norm(x)); x = x + routed(norm(x))``; every norm over
the hidden width and over a q or k head is ZERO-CENTRED, ``x * rsqrt(mean
x^2 + eps) * (1 + w)`` with the leaf ``w`` seeded at zero (``sizes`` holds
every number; ``layer_kinds`` names each layer's first half):

* embedding: ``x = E[token]``, not scaled;
* ``gdn_moe``, Gated DeltaNet: ``q, k, v, z = a W_qkvz`` (widths key_dim,
  key_dim, value_dim, value_dim), ``b, a_ = a W_ba`` (a value head each);
  ``q, k, v`` side by side pass a depthwise causal convolution of
  ``conv_kernel`` taps WITHOUT a bias, then SiLU; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a_ + dt_bias)``; q and k (``gdn_key_heads``
  heads, value head h reads key head ``h // (value / key heads)``) are
  l2-normalised over a head's lanes, ``x * rsqrt(sum x^2 + 1e-6)``, q then
  scaled by ``head_dim ** -0.5``. The state of a value head, in
  R^{dk x dv} and zero before the first position, goes ONE POSITION AT A
  TIME (not in chunks, as the program has it; no triangular inverse, no
  running sum of the decays)::

      S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
      o_t = S^T q_t

  then ``y = rmsnorm_head(o) * w_norm * silu(z)`` (the norm over each
  head's lanes, its weight shared by the heads, seeded at one and NOT
  zero-centred, the gate AFTER the norm) and ``y W_out``. The recurrence
  is a ``lax.scan`` over stretches of ``STRETCH`` positions, each stretch
  rematerialised, so that what its backward pass keeps is a stretch's
  states and not a sequence's, ``HEADS_AT_ONCE`` value heads at a time;
* ``gattn_moe``, gated attention: ``q = a Wq``, ``gate = a Wg`` as
  [s, heads, d], ``k = a Wk``, ``v = a Wv`` as [s, kv_heads, d]; the
  zero-centred norm on each head of q and k; rotary positions on the first
  ``rotary_dim`` lanes of a head (halves paired, the frequencies of a head
  that wide), the other lanes as they are, on every such layer;
  ``o = softmax(q k^T / sqrt(d) + causal mask) v`` a head and a block of
  query rows at a time, each kv head serving ``heads / kv_heads`` query
  heads; ``(o * sigmoid(gate)) Wo``;
* the routed half: ``p = softmax(f Wr)`` in float32 over ALL
  ``router_outputs``; S = the ``top_k`` largest (with
  ``sizes["balanced"]``: of the outputs standardised an expert over a
  sequence's tokens, as ``afmoe_share`` has it and for the same reason);
  ``w_e = route_scale * p_e / sum_{j in S} p_j``; ``sigmoid(f w_gate) *
  shared(f) + sum over e in S that are HELD of w_e expert_e(f)``, a loop
  over the held experts, every expert a gated-SiLU MLP. What the experts
  held on other chips would add is left out;
* head: final zero-centred RMSNorm, ``logits = x Whead`` (untied) over the
  rows held, the mean negative log-likelihood of the next token.

``precision`` is ``pre_ln_transformer``'s: ``float32`` (THE reference),
``bfloat16``, or ``float8`` (the control): the matrix products' operands,
and the operands of the recurrence (``q``, ``k``, ``v``: what the
program's chunked products take in its compute dtype). The router's
scores, ``g``, ``beta``, the decays and the carried state stay float32 in
every precision, as the configuration states them.
"""

from __future__ import annotations

import importlib.util
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .afmoe_share import (HEAD_CHUNK, QUERY_BLOCK, _change_norms, _mlp,
                          _rmsnorm, _rope, _scores_block, _static,
                          leaf_names, leaf_norms)
from .nemotron_h_share import _operand
from .pre_ln_transformer import INIT_STD, _dot, _f32_dot, adamw, targets_of

__all__ = ["make_params", "train_steps", "leaf_norms", "leaf_names"]

# As afmoe_share: a checkout whose program has no such model (the parent of
# the PR that brought this configuration, with the benchmark's new files
# laid over it) ends here, at once, and not after the reference's minutes.
if importlib.util.find_spec("byteps_tpu.models.gated_delta_net") is None:
    raise ImportError("this checkout's program has no byteps_tpu.models."
                      "gated_delta_net: it cannot run the configuration "
                      "that benchmark.reference.qwen3_next_share is the "
                      "reference of")

STRETCH = 128           # positions a rematerialised stretch of the scan
HEADS_AT_ONCE = 32      # value heads the scan carries together
A_FLOOR = 1e-4          # the seeded decay rate, uniform (0, 16], floored
L2_EPS = 1e-6


def make_params(seed: int, sizes: dict):
    """The weights of one run, made on the device in one jitted call, in
    the layout the program trains (a list of per-layer dicts): matrices
    N(0, 0.02); zero leaves of the zero-centred norms; a Gated DeltaNet's
    convolution uniform within 1 / sqrt(taps), ``A_log`` the log of
    uniform (0, 16] floored at 1e-4, ``dt_bias`` one, its head norm one."""
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

    def zeros(n):
        return jnp.zeros((n,), jnp.float32)

    def mlp(width, *lead):
        return {"gate_up": normal(*lead, h, 2 * width),
                "down": normal(*lead, width, h)}

    def gdn():
        hv, gd = z["gdn_value_heads"], z["gdn_head_dim"]
        key_dim, value_dim = z["gdn_key_heads"] * gd, hv * gd
        bound = 1.0 / math.sqrt(z["conv_kernel"])
        return {"in_proj_qkvz": normal(h, 2 * key_dim + 2 * value_dim),
                "in_proj_ba": normal(h, 2 * hv),
                "conv_w": uniform(-bound, bound, z["conv_kernel"],
                                  2 * key_dim + value_dim),
                "dt_bias": jnp.ones((hv,), jnp.float32),
                "A_log": jnp.log(jnp.maximum(uniform(0.0, 16.0, hv),
                                             A_FLOOR)),
                "gdn_norm": jnp.ones((gd,), jnp.float32),
                "out_proj": normal(value_dim, h)}

    def layer(kind):
        if kind == "gdn_moe":
            attn = gdn()
        else:
            attn = {"q": normal(h, z["heads"], d),
                    "k": normal(h, z["kv_heads"], d),
                    "v": normal(h, z["kv_heads"], d),
                    "gate": normal(h, z["heads"], d), "q_norm": zeros(d),
                    "k_norm": zeros(d), "o": normal(z["heads"], d, h)}
        ffn = {"norm": zeros(h), "router": normal(h, z["router_outputs"]),
               "experts": mlp(z["moe_dim"], held),
               "shared": mlp(z["shared_dim"]), "shared_gate": normal(h, 1)}
        return {"attn": {"norm": zeros(h), **attn}, "ffn": ffn}

    return {"embed": normal(z["vocab_size"], h),
            "layers": [layer(kind) for kind in z["layer_kinds"]],
            "final_norm": zeros(h),
            "head": normal(z["vocab_size"], h)}


# ---------------------------------------------------------------- model

def _norm(x, w, eps):
    """The zero-centred RMSNorm: the scale is ``1 + w``."""
    return _rmsnorm(x, 1.0 + w, eps)


def _partial_rope(x, theta, width):
    """Rotary positions on the first ``width`` lanes of [b, s, heads, d],
    their halves paired (i, i + width / 2) at the frequencies of a head
    that wide; the rest unrotated."""
    return jnp.concatenate([_rope(x[..., :width], theta), x[..., width:]],
                           -1)


def _delta_recurrence(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` with ``S`` the gated delta rule's state, one
    position at a time from a zero state, ``HEADS_AT_ONCE`` value heads
    together and one such piece after another. ``q``, ``k``, ``v``
    [heads, s, batch, d] (a value head's each), ``g``, ``beta``
    [heads, s, batch]. Elementwise products and sums alone."""
    heads, s, bsz, d = q.shape
    dv = v.shape[-1]
    at_once = HEADS_AT_ONCE if heads % HEADS_AT_ONCE == 0 else heads

    def one(state, at):         # state [at_once, batch, dk, dv]
        qt, kt, vt, gt, bt = at
        state = jnp.exp(gt)[..., None, None] * state
        old = (state * kt[..., None]).sum(-2)
        state = state + kt[..., None] * (
            bt[..., None] * (vt - old))[..., None, :]
        return state, (state * qt[..., None]).sum(-2)

    @jax.checkpoint
    def stretch(state, at):
        return jax.lax.scan(one, state, at)

    n_stretch = s // STRETCH if s % STRETCH == 0 and s > STRETCH else 1

    @jax.checkpoint
    def piece(at):      # [at_once, s, ...] -> [stretches, len, at_once, ...]
        split = lambda t: jnp.moveaxis(t, 0, 1).reshape(  # noqa: E731
            (n_stretch, s // n_stretch, at_once) + t.shape[2:])
        first = jnp.zeros((at_once, bsz, d, dv), jnp.float32)
        _, o = jax.lax.scan(stretch, first, tuple(split(t) for t in at))
        return jnp.moveaxis(o.reshape(s, at_once, bsz, dv), 1, 0)

    pieces = lambda t: t.reshape((heads // at_once, at_once) + t.shape[1:])  # noqa: E731
    o = jax.lax.map(piece, tuple(pieces(t) for t in (q, k, v, g, beta)))
    return o.reshape(heads, s, bsz, dv)


def _gdn(a, blk, z, dot, precision):
    bsz, s, _ = a.shape
    hk, hv, d = z["gdn_key_heads"], z["gdn_value_heads"], z["gdn_head_dim"]
    key_dim, value_dim, rep = hk * d, hv * d, hv // hk

    @jax.checkpoint
    def front(a, w_qkvz, w_ba, conv_w, dt_bias, a_log):
        qkvz = dot("bsh,hm->bsm", a, w_qkvz)
        ba = dot("bsh,hm->bsm", a, w_ba)
        taps = conv_w.shape[0]
        padded = jnp.pad(qkvz[..., :2 * key_dim + value_dim],
                         ((0, 0), (taps - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(conv_w[t] * padded[:, t:t + s]
                              for t in range(taps)))

        def unit(x):
            x = x.reshape(bsz, s, hk, d)
            return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)

        return (unit(qkv[..., :key_dim]) * d ** -0.5,
                unit(qkv[..., key_dim:2 * key_dim]),
                qkv[..., 2 * key_dim:].reshape(bsz, s, hv, d),
                qkvz[..., 2 * key_dim + value_dim:],
                jax.nn.sigmoid(ba[..., :hv]),
                -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias))

    q, k, v, gate, beta, g = front(a, blk["in_proj_qkvz"], blk["in_proj_ba"],
                                   blk["conv_w"], blk["dt_bias"],
                                   blk["A_log"])

    def by_head(t):             # [b, s, heads, ...] -> [heads, s, b, ...]
        return jnp.moveaxis(t, (2, 1), (0, 1))

    o = _delta_recurrence(
        *(by_head(jnp.repeat(_operand(precision, t), rep, axis=2))
          for t in (q, k)), by_head(_operand(precision, v)), by_head(g),
        by_head(beta))
    o = jnp.moveaxis(o, (0, 1), (2, 1))                 # [b, s, hv, d]

    @jax.checkpoint
    def back(o, gate, scale, w_out):
        y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + z["norm_eps"]) * scale
        y = y.reshape(bsz, s, value_dim) * jax.nn.silu(gate)
        return dot("bsm,mh->bsh", y, w_out)

    return back(o, gate, blk["gdn_norm"], blk["out_proj"])


def _attention(a, blk, z, dot):
    b, s, _ = a.shape
    heads, group = z["heads"], z["heads"] // z["kv_heads"]
    eps = z["norm_eps"]
    q = _norm(dot("bsh,hnd->bsnd", a, blk["q"]), blk["q_norm"], eps)
    k = _norm(dot("bsh,hnd->bsnd", a, blk["k"]), blk["k_norm"], eps)
    v = dot("bsh,hnd->bsnd", a, blk["v"])
    gate = dot("bsh,hnd->bsnd", a, blk["gate"])
    q = _partial_rope(q, z["rope_theta"], z["rotary_dim"])
    k = _partial_rope(k, z["rope_theta"], z["rotary_dim"])
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
    return dot("bsnd,ndh->bsh", out * jax.nn.sigmoid(gate), blk["o"])


def _routed(f, blk, z, dot, sequences=1):
    """[T, h] -> [T, h]: the gated shared expert and the held experts'
    part; ``f`` is ``sequences`` sequences end to end."""
    logits = _f32_dot("th,he->te", f, blk["router"])
    probs = jax.nn.softmax(logits, -1)
    if z.get("balanced"):       # chosen on the outputs standardised
        by_seq = jax.lax.stop_gradient(logits).reshape(
            sequences, -1, logits.shape[-1])
        centred = by_seq - by_seq.mean(1, keepdims=True)
        centred /= jnp.sqrt(
            jnp.mean(centred * centred, 1, keepdims=True) + 1e-12)
        _, chosen = jax.lax.top_k(centred.reshape(logits.shape), z["top_k"])
        top = jnp.take_along_axis(probs, chosen, axis=-1)
    else:
        top, chosen = jax.lax.top_k(probs, z["top_k"])
    weights = z.get("route_scale", 1.0) * top / top.sum(-1, keepdims=True)
    out = jax.nn.sigmoid(dot("th,ho->to", f, blk["shared_gate"])) * _mlp(
        f, blk["shared"], dot)

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
    attn, ffn = blk["attn"], blk["ffn"]
    a = _norm(x, attn["norm"], z["norm_eps"])
    if kind == "gdn_moe":
        x = x + _gdn(a, attn, z, dot, precision)
    else:
        x = x + _attention(a, attn, z, dot)
    b, s, h = x.shape
    f = _norm(x, ffn["norm"], z["norm_eps"]).reshape(b * s, h)
    return x + _routed(f, ffn, z, dot, b).reshape(b, s, h)


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
    x = _norm(x, params["final_norm"], z["norm_eps"])
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

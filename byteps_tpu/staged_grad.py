"""Staged backward for the sync-PS step head.

The monolithic PS head computes the whole tree's gradients in one jitted
program, so the first byte reaches the wire only after the LAST layer
finished differentiating — push bandwidth sits idle for the entire
backward. BytePS's headline win is the opposite schedule: gradients are
intercepted per tensor and pushed while earlier layers are still
differentiating (reference: the priority queues of scheduled_queue.cc
feeding free-running push loops, core_loops.cc:538-618).

The TPU-native equivalent built here: trace ``value_and_grad(loss_fn)``
once to its jaxpr — a linear, topologically ordered equation list where
each parameter's gradient has a definite producer position — and CUT
that list into K jitted segments at the exchange's bucket-group
boundaries. Executing the segments in order yields gradients in
backward-completion order (output-side groups first, matching the
exchange's priority order): the caller hands each group to
``PSGradientExchange.exchange_ingest`` the moment its segment finishes,
so D2H + pack + push of group k run while group k+1 is still
differentiating.

For the cross-step pipeline (``BPS_CROSS_STEP``, cross_step.py) the
FORWARD is cut at the same group boundaries too (``forward_cuts``):
forward segment s then reads only group s's params, each segment
carries the param leaves it is the first to read
(``param_first_use``), and ``run`` can bind params lazily from a live
leaf list behind a readiness gate — the per-parameter unblocking of
the reference's cross-barrier, at bucket-group granularity.

The same jaxpr-cutting machinery generalized ACROSS WORKERS — P
(forward, backward) segment pairs on P processes with explicit
chain-relayed boundary tensors — is the MPMD pipeline-parallel stage
partitioner (byteps_tpu.pipeline.partitioner), which reuses this
module's bitwise-probe contract and cut-signal analysis.

Exactness contract: a cut point survives only if the segmented program
reproduces the fused head BIT-FOR-BIT on a real (params, batch) probe.
Splitting a program at an arbitrary boundary can perturb XLA's fusion
(e.g. an FMA contracted across the boundary in the fused program rounds
once instead of twice), so candidate cuts are validated — first all
together, then individually with the failures dropped — and when no cut
survives, ``build_staged_grad`` returns None and the caller keeps the
monolithic head. Losses that cannot trace outside their shard_map
(mesh-collective models: MoE expert all_to_all, ring-attention SP) fail
at ``make_jaxpr`` and fall back the same way.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.core import DropVar
from jax.extend import core as jcore

from .common.logging import get_logger
from .obs.metrics import get_registry as _registry

log = get_logger()

# refusing to probe more than this many single-cut repairs bounds the
# one-time build cost on pathological bucket plans
_MAX_CUT_TRIALS = 16


@dataclass
class _Segment:
    """One jitted slice of the gradient program."""
    fn: Callable                  # jit(eqns[s:e]) as a flat-arg callable
    invars: Tuple                 # env keys to read (jaxpr Vars)
    outvars: Tuple                # env keys to write
    emit_leaves: Tuple[int, ...]  # flat param-leaf indices ready after it
    emits_loss: bool
    free_after: Tuple             # env keys dead once this segment ran
    param_first_use: Tuple[int, ...] = ()  # param leaves FIRST read here
    #                                        (the cross-step gate set)


@dataclass
class SegmentResult:
    """Yielded per segment by ``StagedGrad.run`` — gradients arrive
    group-by-group, in backward-completion order."""
    index: int
    leaf_ids: Tuple[int, ...]     # flat indices into the param leaf list
    grads: List                   # device arrays, aligned with leaf_ids
    loss: Optional[jax.Array]     # the loss, on the segment computing it
    t0: float                     # wall-clock start of the segment
    dur: float                    # wall-clock duration (blocked on outputs)


class StagedGrad:
    """K jitted backward segments over a fixed (params, batch) signature.

    ``run`` blocks on each segment's outputs before yielding, so the
    yielded timing is the segment's real compute span (the PS_BWD_SEG
    timeline stage) and the consumer's D2H/push work for group k runs
    concurrently with segment k+1's compute, not merely its dispatch.
    """

    def __init__(self, segments: List[_Segment], invars, const_env,
                 loss_var, grad_outvars, in_treedef, n_eqns: int,
                 n_params: int = 0) -> None:
        self.segments = segments
        self._invars = invars
        self._const_env = const_env
        self._loss_var = loss_var
        self._grad_outvars = grad_outvars   # per param leaf: Var | Literal
        self._in_treedef = in_treedef
        self.n_eqns = n_eqns
        self.n_params = n_params            # leading invars = param leaves

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    def _grad_value(self, env, li: int):
        v = self._grad_outvars[li]
        if isinstance(v, jcore.Literal):
            # constant gradient (e.g. a leaf the loss never touches,
            # materialized as a literal): broadcast to the leaf's aval
            aval = v.aval
            import jax.numpy as jnp
            return jnp.broadcast_to(jnp.asarray(v.val, dtype=aval.dtype),
                                    aval.shape)
        return env[v]

    def run(self, params, batch, gate=None, params_flat=None,
            block_nonemitting=True):
        """Generator of ``SegmentResult`` in execution order.

        ``params_flat``: a LIVE flat param-leaf list read lazily — each
        segment binds only the param leaves it is the first to read,
        immediately before it runs. The cross-step driver hands the
        list its tail thread updates in place, so a segment gated on
        step k's apply reads the step-k value without the whole tree
        having to exist up front. ``params`` then only supplies the
        structure for the signature check.

        ``gate(seg_index, param_leaf_ids)``: called before each
        segment binds/runs — the cross-step readiness gate. With
        neither argument this is exactly the eager PR-2 behavior.

        ``block_nonemitting=False``: don't ``block_until_ready`` on
        segments that emit no gradients (the forward slices) — their
        compute then overlaps the NEXT gates' waits on the XLA pool
        instead of serializing with them, which takes the forward off
        the cross-step critical chain. Emitting segments always block,
        so gradient handover timing (and the PS_BWD_SEG spans the head
        overlap telemetry anchors on) keeps its meaning; non-emitting
        spans are dispatch-only in this mode."""
        flat, treedef = jax.tree_util.tree_flatten((params, batch))
        if treedef != self._in_treedef:
            raise ValueError(
                "staged backward was built for a different (params, batch) "
                "structure — rebuild it for the new signature")
        if params_flat is None:
            env = dict(zip(self._invars, flat))
        else:
            if len(params_flat) != self.n_params:
                raise ValueError(
                    f"params_flat has {len(params_flat)} leaves, staged "
                    f"program was built for {self.n_params}")
            env = dict(zip(self._invars[self.n_params:],
                           flat[self.n_params:]))
        env.update(self._const_env)
        pvars = self._invars[:self.n_params]
        for si, seg in enumerate(self.segments):
            if gate is not None:
                gate(si, seg.param_first_use)
            if params_flat is not None:
                for li in seg.param_first_use:
                    env[pvars[li]] = params_flat[li]
            t0 = time.time()
            outs = seg.fn(*[env[v] for v in seg.invars])
            if block_nonemitting or seg.emit_leaves or seg.emits_loss:
                jax.block_until_ready(outs)
            dur = time.time() - t0
            env.update(zip(seg.outvars, outs))
            grads = [self._grad_value(env, li) for li in seg.emit_leaves]
            loss = env[self._loss_var] if seg.emits_loss else None
            for v in seg.free_after:    # residuals dead past this point:
                env.pop(v, None)        # don't pin activation memory
            _registry().counter("staged/segments_run").inc()
            yield SegmentResult(si, seg.emit_leaves, grads, loss, t0, dur)


def _assemble(cj, cuts: Sequence[int], leaf_ready, loss_var,
              grad_outvars, in_treedef, n_params: int = 0) -> StagedGrad:
    """Build the segment list for boundary-after-eqn indices ``cuts``."""
    jaxpr = cj.jaxpr
    n_eqns = len(jaxpr.eqns)
    bounds, start = [], 0
    for c in sorted(set(cuts)):
        bounds.append((start, c + 1))
        start = c + 1
    if start < n_eqns:
        bounds.append((start, n_eqns))

    const_env = dict(zip(jaxpr.constvars, cj.consts))
    outset = {v for v in jaxpr.outvars if isinstance(v, jcore.Var)}

    # last segment consuming each var (for residual freeing); grads and
    # loss count as consumed where they are emitted
    produced_in = {}
    for si, (s, e) in enumerate(bounds):
        for eq in jaxpr.eqns[s:e]:
            for v in eq.outvars:
                if not isinstance(v, DropVar):
                    produced_in[v] = si
    last_use = {}
    for si, (s, e) in enumerate(bounds):
        for eq in jaxpr.eqns[s:e]:
            for v in eq.invars:
                if isinstance(v, jcore.Var):
                    last_use[v] = si
    loss_seg = produced_in.get(loss_var, 0)
    last_use[loss_var] = max(last_use.get(loss_var, 0), loss_seg)

    # cross-step gating metadata: which segment FIRST reads each param
    # invar (the leading n_params jaxpr invars). A segment's gate set is
    # the params it binds; later segments reuse the env binding, so
    # first-read is exactly when the value must be step-k fresh.
    pvar_index = {v: li for li, v in enumerate(jaxpr.invars[:n_params])}
    first_seg: dict = {}
    for si, (s, e) in enumerate(bounds):
        for eq in jaxpr.eqns[s:e]:
            for v in eq.invars:
                li = pvar_index.get(v) if isinstance(v, jcore.Var) else None
                if li is not None and li not in first_seg:
                    first_seg[li] = si

    emit_at: dict = {}
    for li, r in enumerate(leaf_ready):
        si = 0
        for j, (s, e) in enumerate(bounds):
            if r < e:
                si = j
                break
        emit_at.setdefault(si, []).append(li)
        gv = grad_outvars[li]
        if isinstance(gv, jcore.Var):
            last_use[gv] = max(last_use.get(gv, 0), si)
            if gv in pvar_index:
                # passthrough gradient (grad var IS a param invar): the
                # emit reads it, so it must be bound by then
                pi = pvar_index[gv]
                first_seg[pi] = min(first_seg.get(pi, si), si)

    first_use_at: dict = {}
    for li, si in first_seg.items():
        first_use_at.setdefault(si, []).append(li)

    segments: List[_Segment] = []
    for si, (s, e) in enumerate(bounds):
        eqns = jaxpr.eqns[s:e]
        prod_here = set()
        for eq in eqns:
            prod_here.update(v for v in eq.outvars
                             if not isinstance(v, DropVar))
        used_here = set()
        for eq in eqns:
            used_here.update(v for v in eq.invars
                             if isinstance(v, jcore.Var))
        invars = sorted(used_here - prod_here, key=lambda v: v.count)
        used_later = set()
        for eq in jaxpr.eqns[e:]:
            used_later.update(v for v in eq.invars
                              if isinstance(v, jcore.Var))
        outs = sorted(prod_here & (used_later | outset),
                      key=lambda v: v.count)
        sub = jcore.Jaxpr((), tuple(invars), tuple(outs), tuple(eqns))
        fn = jax.jit(jcore.jaxpr_as_fun(jcore.ClosedJaxpr(sub, ())))
        free = tuple(v for v, lu in last_use.items() if lu == si)
        segments.append(_Segment(
            fn=fn, invars=tuple(invars), outvars=tuple(outs),
            emit_leaves=tuple(emit_at.get(si, ())),
            emits_loss=si == loss_seg, free_after=free,
            param_first_use=tuple(sorted(first_use_at.get(si, ())))))
    return StagedGrad(segments, tuple(jaxpr.invars), const_env,
                      loss_var, grad_outvars, in_treedef, n_eqns,
                      n_params=n_params)


def _bitwise_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and np.array_equal(a, b, equal_nan=True)


def _probe(staged: StagedGrad, fused_flat, params, batch) -> bool:
    """Does the segmented program reproduce the fused head bit-for-bit?"""
    got = [None] * (len(fused_flat) - 1)
    loss = None
    for seg in staged.run(params, batch):
        if seg.loss is not None:
            loss = seg.loss
        for li, g in zip(seg.leaf_ids, seg.grads):
            got[li] = g
    if loss is None or any(g is None for g in got):
        return False
    return all(_bitwise_equal(a, b)
               for a, b in zip([loss] + got, fused_flat))


def _coalesce(cuts: List[int], max_segments: int) -> List[int]:
    """Reduce to at most ``max_segments - 1`` cuts, keeping the spread."""
    want = max(0, max_segments - 1)
    if len(cuts) <= want:
        return cuts
    idx = np.linspace(0, len(cuts) - 1, want).round().astype(int)
    return sorted({cuts[i] for i in idx})


def build_staged_grad(loss_fn: Callable, params, batch,
                      groups: Optional[Sequence[Sequence[int]]] = None,
                      fused_fn: Optional[Callable] = None,
                      max_segments: int = 4,
                      name: str = "loss",
                      forward_cuts: bool = False) -> Optional[StagedGrad]:
    """Build a bit-exact staged backward for ``loss_fn``, or None.
    Outcomes are counted (``staged/builds`` vs ``staged/build_fallback``)
    so a fleet silently running monolithic heads is visible without
    log scraping."""
    st = _build_staged_grad_impl(loss_fn, params, batch, groups=groups,
                                 fused_fn=fused_fn,
                                 max_segments=max_segments, name=name,
                                 forward_cuts=forward_cuts)
    _registry().counter(
        "staged/builds" if st is not None else "staged/build_fallback"
    ).inc()
    return st


def _build_staged_grad_impl(loss_fn: Callable, params, batch,
                            groups=None, fused_fn=None,
                            max_segments: int = 4, name: str = "loss",
                            forward_cuts: bool = False
                            ) -> Optional[StagedGrad]:
    """(See ``build_staged_grad``.)

    ``groups``: partition of the flat param-leaf indices (the exchange's
    ``leaf_groups``) — candidate cuts are placed where each group's last
    gradient is produced, so segment boundaries line up with bucket
    completion. None = one candidate cut per leaf (coalesced below).

    ``fused_fn``: the monolithic arm to validate against,
    ``(params, batch) -> (loss, grads)``; defaults to a plain jitted
    ``value_and_grad(loss_fn)``. The probe runs BOTH arms on the given
    (params, batch) and requires bitwise equality, so pass the exact
    callable the staged head will replace.

    ``forward_cuts``: also place candidate cuts in the FORWARD region,
    right before each bucket group's params are first read — for a
    sequential model, forward segment s then reads only group s's
    params, which is what lets the cross-step driver launch next-step
    forward segments as soon as individual groups' applies land
    instead of gating the whole program on the full tree. Same bitwise
    probe-or-drop contract as the backward cuts.

    Returns None (with a logged reason) whenever staging is impossible
    (mesh-collective loss, effects, no cut point) or not provably exact.
    """
    try:
        cj = jax.make_jaxpr(jax.value_and_grad(loss_fn))(params, batch)
    except Exception as e:  # noqa: BLE001 — e.g. unbound mesh axis names
        log.info("staged backward unavailable for %s: trace failed (%s: %s)",
                 name, type(e).__name__, e)
        return None
    jaxpr = cj.jaxpr
    if jaxpr.effects:
        log.info("staged backward unavailable for %s: effectful jaxpr", name)
        return None
    flat_in, in_treedef = jax.tree_util.tree_flatten((params, batch))
    n_leaves = len(jax.tree_util.tree_leaves(params))
    if len(jaxpr.invars) != len(flat_in) \
            or len(jaxpr.outvars) != 1 + n_leaves:
        log.info("staged backward unavailable for %s: unexpected jaxpr "
                 "arity", name)
        return None
    loss_var = jaxpr.outvars[0]
    grad_outvars = list(jaxpr.outvars[1:])
    if not isinstance(loss_var, jcore.Var):
        log.info("staged backward unavailable for %s: constant loss", name)
        return None

    producer = {}
    for i, eq in enumerate(jaxpr.eqns):
        for v in eq.outvars:
            producer[v] = i
    # constant/passthrough grads are ready before any eqn runs
    leaf_ready = [producer.get(v, -1) if isinstance(v, jcore.Var) else -1
                  for v in grad_outvars]

    if groups is not None:
        cand = sorted({max(leaf_ready[li] for li in g)
                       for g in groups if len(g)})
    else:
        cand = sorted(set(leaf_ready))
    if forward_cuts:
        # one candidate boundary right before each group's params are
        # first read: the forward then advances group-by-group in the
        # same partition the exchange/apply use, so next-step segments
        # gate on exactly one group's apply each
        pvar_index = {v: li for li, v in
                      enumerate(jaxpr.invars[:n_leaves])}
        first_use: dict = {}
        for i, eq in enumerate(jaxpr.eqns):
            for v in eq.invars:
                li = (pvar_index.get(v) if isinstance(v, jcore.Var)
                      else None)
                if li is not None and li not in first_use:
                    first_use[li] = i
        group_first = sorted(
            {min(first_use[li] for li in g if li in first_use)
             for g in (groups or [[li] for li in range(n_leaves)])
             if any(li in first_use for li in g)})
        cand = sorted(set(cand) | {c - 1 for c in group_first[1:]})
    # a boundary after the last eqn (or before the first) splits nothing
    cand = [c for c in cand if 0 <= c < len(jaxpr.eqns) - 1]
    cand = _coalesce(cand, max_segments)
    if not cand:
        log.info("staged backward unavailable for %s: no usable cut "
                 "points (%d eqns)", name, len(jaxpr.eqns))
        return None

    if fused_fn is None:
        fused_fn = jax.jit(jax.value_and_grad(loss_fn))
    floss, fgrads = fused_fn(params, batch)
    fused_flat = [floss] + jax.tree_util.tree_leaves(fgrads)

    def try_cuts(cuts):
        st = _assemble(cj, cuts, leaf_ready, loss_var, grad_outvars,
                       in_treedef, n_params=n_leaves)
        return st if _probe(st, fused_flat, params, batch) else None

    staged = try_cuts(cand)
    if staged is None and len(cand) > 1:
        # some boundary perturbs fusion numerics: keep only the cuts
        # that are individually bit-exact, then re-validate the set
        kept = [c for c in cand[:_MAX_CUT_TRIALS]
                if try_cuts([c]) is not None]
        if kept and kept != cand:
            staged = try_cuts(kept)
            cand = kept
    if staged is None:
        log.info("staged backward falls back for %s: no cut set "
                 "reproduces the fused backward bit-for-bit", name)
        return None
    log.info("staged backward for %s: %d segments over %d eqns "
             "(cuts at %s)", name, staged.n_segments, staged.n_eqns, cand)
    return staged

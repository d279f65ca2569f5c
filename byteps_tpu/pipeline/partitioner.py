"""Stage partitioner: cut one loss program into P pipeline stages.

``staged_grad`` cuts ``value_and_grad(loss_fn)``'s jaxpr into K jitted
segments that run back to back on ONE worker, so D2H/push of group k
overlaps the differentiation of group k+1. This module generalizes the
same machinery across WORKERS: the jaxpr — forward equations first,
then backward, topologically ordered — is cut into 2P segments
(P forward, P backward) and segment k is assigned to stage

    stage(k) = k            for k <  P   (forward sweep, stages 0..P-1)
    stage(k) = 2P - 1 - k   for k >= P   (backward sweep, P-1..0)

so the execution order of the segments IS the pipeline's microbatch
path: fwd 0 → 1 → … → P-1 (loss) → bwd P-1 → … → 0. The cut points
come from the same signals ``staged_grad`` uses — each stage owns a
contiguous (by first-use order) byte-balanced group of param leaves,
the forward cut sits right before stage s+1's params are first read
(``forward_cuts``), the backward cut right after stage s+1's grads
finish (bucket-group boundaries).

**Boundary tensors are explicit.** For each of the 2P-1 segment
boundaries the partitioner computes the exact variable set that must
cross it: a var rides boundary b iff some later segment consumes it on
a stage that does not yet hold it (chain relay — a residual produced
and consumed on one stage never moves; a skip connection relays
through intermediate stages hop by hop). Params are held by their
owning stage, batch leaves and consts by every stage (each worker
feeds the same microbatch), so for a sequential model the boundaries
carry exactly the activations (forward) and activation-grads
(backward) — the two traffic classes of the wire scheduler.

**Exactness contract** (same as ``staged_grad``): the partitioned
program must reproduce the fused ``value_and_grad`` BIT-FOR-BIT on a
real (params, microbatch) probe, and every param leaf's gradient must
be emitted on the stage that owns the leaf. Any violation —
fusion-perturbing cut, grads produced out of stage order, interleaved
first-use/grad-ready intervals — makes ``build`` return None and the
caller refuses to pipeline, loudly. Pipelining never changes numerics.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.core import DropVar
from jax.extend import core as jcore

from ..common.logging import get_logger
from ..obs.metrics import get_registry
from ..staged_grad import _bitwise_equal

log = get_logger()


@dataclass
class _PPSegment:
    """One jitted slice of the program, owned by one stage."""
    fn: Callable
    invars: Tuple                  # env keys read (jaxpr Vars)
    outvars: Tuple                 # env keys written
    stage: int                     # owning stage
    kind: str                      # "fwd" | "bwd"
    emit_leaves: Tuple[int, ...]   # param-leaf grads finalized here
    emits_loss: bool = False


@dataclass
class Boundary:
    """Segment boundary b: what segment b's worker hands segment b+1's
    worker. ``local`` boundaries (the fwd(P-1)→bwd(P-1) turn) stay in
    the worker's env — nothing crosses the wire."""
    index: int
    src_stage: int
    dst_stage: int
    vars: Tuple                    # ordered jaxpr Vars
    local: bool
    kind: str                      # "act" (forward) | "act_grad" (backward)

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(v.aval.shape))
                   * np.dtype(v.aval.dtype).itemsize for v in self.vars)

    def specs(self) -> List[Tuple[tuple, str]]:
        """[(shape, dtype)] per var — the (de)serialization contract
        both sides of the wire derive from the shared program."""
        return [(tuple(v.aval.shape), str(np.dtype(v.aval.dtype)))
                for v in self.vars]


@dataclass
class PipelineProgram:
    """The partitioned program: 2P segments, 2P-1 boundaries, and the
    binding metadata each stage driver needs."""
    num_stages: int
    segments: List[_PPSegment]            # execution order
    boundaries: List[Boundary]
    stage_param_leaves: List[Tuple[int, ...]]   # leaf ids per stage
    invars: Tuple                         # full jaxpr invars
    const_env: Dict
    n_params: int
    in_treedef: object
    loss_var: object
    grad_outvars: List                    # per leaf: Var | Literal
    n_eqns: int = 0
    # derived maps, filled in __post_init__
    param_var_of: Dict = field(default_factory=dict)

    def __post_init__(self) -> None:
        self.param_var_of = {li: v for li, v in
                             enumerate(self.invars[:self.n_params])}

    def stage_segment(self, stage: int, kind: str) -> int:
        """Index of ``stage``'s fwd/bwd segment in execution order."""
        return stage if kind == "fwd" \
            else 2 * self.num_stages - 1 - stage

    def owner_of(self, leaf: int) -> int:
        for s, leaves in enumerate(self.stage_param_leaves):
            if leaf in leaves:
                return s
        raise KeyError(leaf)

    # ------------------------------------------------- local execution

    def run_local(self, params, batch):
        """Run every segment in order in ONE process/env — the probe
        arm, and the degenerate P=1 execution. Returns (loss, flat
        grads list)."""
        flat, treedef = jax.tree_util.tree_flatten((params, batch))
        if treedef != self.in_treedef:
            raise ValueError("pipeline program built for a different "
                             "(params, batch) structure")
        env = dict(zip(self.invars, flat))
        env.update(self.const_env)
        loss = None
        for seg in self.segments:
            outs = seg.fn(*[env[v] for v in seg.invars])
            env.update(zip(seg.outvars, outs))
            if seg.emits_loss:
                loss = env[self.loss_var]
        grads = [self.grad_value(env, li)
                 for li in range(len(self.grad_outvars))]
        return loss, grads

    def grad_value(self, env, li: int):
        v = self.grad_outvars[li]
        if isinstance(v, jcore.Literal):
            import jax.numpy as jnp
            return jnp.broadcast_to(
                jnp.asarray(v.val, dtype=v.aval.dtype), v.aval.shape)
        return env[v]


def _balanced_groups(order: List[int], leaf_bytes: List[int],
                     nstages: int) -> List[List[int]]:
    """Split ``order`` (leaf ids, first-use order) into ``nstages``
    contiguous byte-balanced groups, each non-empty."""
    total = sum(leaf_bytes[li] for li in order)
    target = total / nstages
    groups: List[List[int]] = []
    cur: List[int] = []
    acc = 0
    for pos, li in enumerate(order):
        cur.append(li)
        acc += leaf_bytes[li]
        stages_left = nstages - len(groups) - 1
        leaves_left = len(order) - pos - 1
        # close the group once it carries its fair share, but never so
        # greedily that a later stage would end up empty
        if (stages_left > 0 and acc >= target
                and leaves_left >= stages_left):
            groups.append(cur)
            cur, acc = [], 0
    groups.append(cur)
    return groups if len(groups) == nstages and all(groups) else []


class StagePartitioner:
    """Builds a ``PipelineProgram`` with ``num_stages`` stages, or
    returns None when the model cannot be staged exactly (the
    probe-or-drop contract). ``build`` must be called with the
    MICRObatch-shaped batch — the schedule replays the program once per
    microbatch. ``num_stages=None`` resolves ``BPS_PP_STAGES`` (via
    the live Config when ``bps.init`` ran, the env otherwise) — every
    stage worker builds the same program from the same inputs."""

    def __init__(self, num_stages: Optional[int] = None) -> None:
        if num_stages is None:
            from ..common.config import Config
            from ..common.global_state import GlobalState
            cfg = (GlobalState.get().config if GlobalState.initialized()
                   else Config.from_env())
            num_stages = cfg.pp_stages
        if num_stages < 1:
            raise ValueError("num_stages must be >= 1")
        self.num_stages = int(num_stages)

    # ------------------------------------------------------------ build

    def build(self, loss_fn: Callable, params, batch,
              fused_fn: Optional[Callable] = None,
              name: str = "pp",
              exact: bool = True) -> Optional[PipelineProgram]:
        """``exact=True`` (default) demands BITWISE equality with the
        fused head on the probe — what the MLP-class models satisfy.
        ``exact=False`` accepts the ``test_grad_exactness`` tolerance
        contract instead (rtol=2e-3, atol=2e-5): stage cuts through a
        transformer block perturb XLA's fusion rounding by last-ulp
        amounts the bitwise probe rejects, the same reason
        ``staged_grad`` drops individual cuts — but a pipeline NEEDS
        its cuts, so the caller chooses tolerance explicitly and the
        build logs which contract it validated."""
        prog = self._build_impl(loss_fn, params, batch,
                                fused_fn=fused_fn, name=name,
                                exact=exact)
        get_registry().counter(
            "pp/builds" if prog is not None else "pp/build_fallback").inc()
        return prog

    # the test_grad_exactness tolerance contract (its bert/gpt2 sweep)
    _PROBE_RTOL, _PROBE_ATOL = 2e-3, 2e-5

    def _build_impl(self, loss_fn, params, batch, fused_fn, name,
                    exact=True):
        P = self.num_stages
        try:
            cj = jax.make_jaxpr(jax.value_and_grad(loss_fn))(params, batch)
        except Exception as e:  # noqa: BLE001 — mesh-collective losses etc.
            log.info("pipeline partition unavailable for %s: trace failed "
                     "(%s: %s)", name, type(e).__name__, e)
            return None
        jaxpr = cj.jaxpr
        if jaxpr.effects:
            log.info("pipeline partition unavailable for %s: effectful "
                     "jaxpr", name)
            return None
        flat_in, in_treedef = jax.tree_util.tree_flatten((params, batch))
        leaves = jax.tree_util.tree_leaves(params)
        n_params = len(leaves)
        if len(jaxpr.invars) != len(flat_in) \
                or len(jaxpr.outvars) != 1 + n_params:
            log.info("pipeline partition unavailable for %s: unexpected "
                     "jaxpr arity", name)
            return None
        loss_var = jaxpr.outvars[0]
        if not isinstance(loss_var, jcore.Var):
            log.info("pipeline partition unavailable for %s: constant "
                     "loss", name)
            return None
        grad_outvars = list(jaxpr.outvars[1:])

        producer = {}
        for i, eq in enumerate(jaxpr.eqns):
            for v in eq.outvars:
                producer[v] = i
        leaf_ready = [producer.get(v, -1) if isinstance(v, jcore.Var)
                      else -1 for v in grad_outvars]
        pvar_index = {v: li for li, v in
                      enumerate(jaxpr.invars[:n_params])}
        first_use: Dict[int, int] = {}
        for i, eq in enumerate(jaxpr.eqns):
            for v in eq.invars:
                li = pvar_index.get(v) if isinstance(v, jcore.Var) else None
                if li is not None and li not in first_use:
                    first_use[li] = i

        # ---- stage ownership: contiguous byte-balanced first-use groups
        leaf_bytes = [int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                      for l in leaves]
        order = sorted(range(n_params),
                       key=lambda li: (first_use.get(li, 1 << 60), li))
        used = [li for li in order if li in first_use]
        if len(used) < P:
            log.info("pipeline partition unavailable for %s: %d used "
                     "param leaves < %d stages", name, len(used), P)
            return None
        groups = _balanced_groups(order, leaf_bytes, P)
        if not groups:
            log.info("pipeline partition unavailable for %s: could not "
                     "form %d non-empty stage groups", name, P)
            return None

        if P == 1:
            cuts = [producer[loss_var]]
        else:
            # forward cuts: right before each later stage's params are
            # first read; backward cuts: right after each later stage's
            # grads are complete; the loss producer splits fwd | bwd
            fwd_cuts, bwd_cuts = [], []
            for s in range(1, P):
                fu = [first_use[li] for li in groups[s] if li in first_use]
                if not fu:
                    log.info("pipeline partition unavailable for %s: "
                             "stage %d has no used params", name, s)
                    return None
                fwd_cuts.append(min(fu) - 1)
            loss_cut = producer[loss_var]
            for s in range(P - 1, 0, -1):
                lr = [leaf_ready[li] for li in groups[s]
                      if leaf_ready[li] >= 0]
                if not lr:
                    log.info("pipeline partition unavailable for %s: "
                             "stage %d emits no grads", name, s)
                    return None
                bwd_cuts.append(max(lr))
            cuts = fwd_cuts + [loss_cut] + bwd_cuts
            if any(c < 0 or c >= len(jaxpr.eqns) - 1 for c in cuts) \
                    or sorted(set(cuts)) != cuts:
                log.info("pipeline partition unavailable for %s: cut "
                         "points not strictly ordered (%s) — stage "
                         "first-use/grad-ready intervals interleave",
                         name, cuts)
                return None

        prog = self._assemble(cj, cuts, groups, leaf_ready, loss_var,
                              grad_outvars, in_treedef, n_params, name)
        if prog is None:
            return None

        # ---- bitwise probe-or-drop against the fused head
        if fused_fn is None:
            fused_fn = jax.jit(jax.value_and_grad(loss_fn))
        floss, fgrads = fused_fn(params, batch)
        fused_flat = [floss] + jax.tree_util.tree_leaves(fgrads)
        loss, grads = prog.run_local(params, batch)
        if exact:
            ok = loss is not None and all(
                _bitwise_equal(a, b)
                for a, b in zip([loss] + grads, fused_flat))
        else:
            ok = loss is not None and all(
                np.allclose(np.asarray(a), np.asarray(b),
                            rtol=self._PROBE_RTOL, atol=self._PROBE_ATOL)
                for a, b in zip([loss] + grads, fused_flat))
        if not ok:
            log.info("pipeline partition falls back for %s: the %d-stage "
                     "program does not reproduce the fused "
                     "value_and_grad %s", name, P,
                     "bit-for-bit" if exact else "within tolerance")
            return None
        log.info("pipeline partition for %s: %d stages over %d eqns, "
                 "%s contract (cuts at %s; boundary bytes %s)", name, P,
                 len(jaxpr.eqns),
                 "bitwise" if exact else "tolerance",
                 cuts, [b.nbytes for b in prog.boundaries if not b.local])
        return prog

    # --------------------------------------------------------- assembly

    def _assemble(self, cj, cuts: Sequence[int], groups,
                  leaf_ready, loss_var, grad_outvars, in_treedef,
                  n_params: int, name: str) -> Optional[PipelineProgram]:
        P = self.num_stages
        jaxpr = cj.jaxpr
        n_eqns = len(jaxpr.eqns)
        bounds, start = [], 0
        for c in sorted(set(cuts)):
            bounds.append((start, c + 1))
            start = c + 1
        if start < n_eqns:
            bounds.append((start, n_eqns))
        if len(bounds) != 2 * P:
            log.info("pipeline partition unavailable for %s: %d cuts "
                     "yielded %d segments, wanted %d", name, len(cuts),
                     len(bounds), 2 * P)
            return None
        stage_of = list(range(P)) + list(range(P - 1, -1, -1))

        const_env = dict(zip(jaxpr.constvars, cj.consts))
        outset = {v for v in jaxpr.outvars if isinstance(v, jcore.Var)}
        owner = {}
        for s, g in enumerate(groups):
            for li in g:
                owner[li] = s
        pvar_index = {v: li for li, v in
                      enumerate(jaxpr.invars[:n_params])}

        produced_in: Dict = {}
        for si, (s, e) in enumerate(bounds):
            for eq in jaxpr.eqns[s:e]:
                for v in eq.outvars:
                    if not isinstance(v, DropVar):
                        produced_in[v] = si
        consumers: Dict = {}
        for si, (s, e) in enumerate(bounds):
            for eq in jaxpr.eqns[s:e]:
                for v in eq.invars:
                    if isinstance(v, jcore.Var):
                        consumers.setdefault(v, []).append(si)

        # grad emission: every leaf's grad is OWED to its owner's bwd
        # segment — the stage that holds the leaf applies its update.
        # A grad finalized on a foreign stage (tied weights: the token
        # embedding's grad carries an LM-head contribution produced in
        # the LAST stage's backward) is declared a consumer of the
        # owner's bwd segment, so the generic boundary relay carries it
        # down the chain like any activation-grad. Only a grad produced
        # AFTER the owner's bwd segment is unreachable (the chain only
        # moves forward) — refuse.
        loss_seg = produced_in.get(loss_var, 0)
        emit_at: Dict[int, List[int]] = {}
        for li, r in enumerate(leaf_ready):
            gv = grad_outvars[li]
            own_bwd = 2 * P - 1 - owner[li]
            if isinstance(gv, jcore.Var) and gv not in pvar_index \
                    and r >= 0:
                psi = produced_in.get(gv)
                if psi is None:
                    return None
                if psi > own_bwd:
                    log.info("pipeline partition unavailable for %s: "
                             "leaf %d's grad is produced in segment %d, "
                             "after its owner stage %d's backward "
                             "(segment %d)", name, li, psi, owner[li],
                             own_bwd)
                    return None
                consumers.setdefault(gv, []).append(own_bwd)
            emit_at.setdefault(own_bwd, []).append(li)
        consumers.setdefault(loss_var, []).append(loss_seg)

        segments: List[_PPSegment] = []
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
            segments.append(_PPSegment(
                fn=fn, invars=tuple(invars), outvars=tuple(outs),
                stage=stage_of[si], kind="fwd" if si < P else "bwd",
                emit_leaves=tuple(sorted(emit_at.get(si, ()))),
                emits_loss=si == loss_seg))

        # ---- boundary send sets: the chain-relay holders walk.
        # holder[v] = stages that have v; a var rides boundary b iff a
        # later segment consumes it on a stage that does not hold it.
        holder: Dict = {}
        for v in jaxpr.constvars:
            holder[v] = set(range(P))
        for i, v in enumerate(jaxpr.invars):
            li = pvar_index.get(v)
            if li is not None:
                holder[v] = {owner[li]}
            else:                      # batch leaf: every worker binds it
                holder[v] = set(range(P))
        avail_seg: Dict = {}           # var -> first segment it exists at
        for v in jaxpr.invars:
            li = pvar_index.get(v)
            avail_seg[v] = owner[li] if li is not None else 0
        for v, si in produced_in.items():
            holder.setdefault(v, {stage_of[si]})
            avail_seg[v] = si

        boundaries: List[Boundary] = []
        for b in range(2 * P - 1):
            dst = stage_of[b + 1]
            send: List = []
            for v, cs in consumers.items():
                if avail_seg.get(v, 1 << 30) > b:
                    continue          # not yet in existence at boundary b
                future = [c for c in cs if c > b]
                if not future:
                    continue
                if any(stage_of[c] not in holder[v] for c in future):
                    send.append(v)
                    holder[v].add(dst)
            send.sort(key=lambda v: v.count)
            boundaries.append(Boundary(
                index=b, src_stage=stage_of[b], dst_stage=dst,
                vars=tuple(send), local=stage_of[b] == dst,
                kind="act" if b < P else "act_grad"))

        return PipelineProgram(
            num_stages=P, segments=segments, boundaries=boundaries,
            stage_param_leaves=[tuple(sorted(g)) for g in groups],
            invars=tuple(jaxpr.invars), const_env=const_env,
            n_params=n_params, in_treedef=in_treedef, loss_var=loss_var,
            grad_outvars=grad_outvars, n_eqns=n_eqns)

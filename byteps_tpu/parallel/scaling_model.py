"""Compile-time scaling evidence for the multi-chip north star.

The reference's headline is a *measured* 8 → 256 GPU curve (reference:
README.md:37-44 — BERT-large, ~90% scaling efficiency on 100 Gbps RDMA).
This box has one TPU chip, so that curve cannot be re-measured here; what
CAN be verified today, with no hardware, is everything the curve depends
on besides link speed:

1. **The compiled program has the intended communication structure.**
   ``lower_flagship_step`` AOT-lowers the real data-parallel training
   step (same ``distributed_optimizer`` + ``shard_map`` path
   ``DistributedTrainer._build_step`` jits) over an
   ``AbstractMesh`` of any logical size — 8, 64, 256 devices — and
   ``collective_schedule`` walks the lowered StableHLO for its
   collectives. ``verify_dp_schedule`` then asserts the invariants the
   analytic model (and the performance story) relies on:

   - on an ICI-only mesh with the default reducer (the leaf form,
     ``collectives.leaf_allreduce``): nothing but all-reduces over the
     data axes, every gradient leaf reduced exactly once in its own
     size — a regression that re-packs the gradients, drops a leaf or
     adds a hop of another kind fails; wherever buckets run (a custom
     reducer, a dcn mesh) exactly ONE reduction collective per bucket;
   - on hybrid ``dcn × ici`` meshes, the hierarchical schedule of
     ``psum_reducer``: per bucket one in-slice reduce_scatter, one
     cross-slice all_reduce over the 1/ici shard, one in-slice
     all_gather — and NO bulk collective whose replica group crosses
     the dcn tier at full bucket size;
   - byte volumes: collective-visible gradient bytes equal the
     parameter-gradient bytes (2(n-1)/n per-wire scaling follows from
     the op kinds and is applied by the cost model).

2. **An analytic step-time / scaling-efficiency curve** from the
   measured single-chip compute time plus a documented per-tier
   bandwidth model (``CommModel``), evaluated over the HLO-extracted
   schedule — not over hand-waved totals. Run
   ``python -m byteps_tpu.parallel.scaling_model`` for the table that
   docs/performance.md cites.

Nothing here executes on devices: ``jit(...).lower(...)`` with
``AbstractMesh`` traces and lowers only, so 256-device programs are
checkable on this 1-chip box.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

__all__ = [
    "Collective", "CommModel", "V5E_COMM", "lower_flagship_step",
    "lower_hybrid_step", "lower_moe_step", "collective_schedule",
    "verify_dp_schedule", "verify_hybrid_schedule",
    "verify_moe_schedule", "model_step_time", "scaling_table",
    "format_table",
]


# --------------------------------------------------------------------------
# HLO collective extraction
# --------------------------------------------------------------------------

_COLLECTIVE_OPS = (
    "stablehlo.all_reduce", "stablehlo.reduce_scatter",
    "stablehlo.all_gather", "stablehlo.all_to_all",
    "stablehlo.collective_permute",
)


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective op from a lowered program, in cost-model terms."""
    kind: str                 # "all_reduce" | "reduce_scatter" | ...
    operand_elems: int        # per-participant input elements
    result_elems: int         # per-participant output elements
    dtype: str
    dtype_bytes: int
    group_size: int           # participants per replica group
    n_groups: int
    crosses_dcn: bool         # any group spans >1 dcn slice
    spans: frozenset = frozenset()   # mesh axes the replica groups vary
    # over (populated when collective_schedule gets axis_sizes) —
    # classification by membership, NOT by group size: sizes collide
    # (tp×sp == dcn is common) and would mask layout regressions

    @property
    def operand_bytes(self) -> int:
        return self.operand_elems * self.dtype_bytes

    def wire_bytes(self) -> int:
        """Bytes each participant sends (= receives) on the wire, ring
        algorithms: all_reduce 2(g-1)/g·B, reduce_scatter (g-1)/g·B on
        the input, all_gather (g-1)/g·B on the output."""
        g = self.group_size
        if g <= 1:
            return 0
        if self.kind == "all_reduce":
            return int(2 * (g - 1) / g * self.operand_bytes)
        if self.kind == "reduce_scatter":
            return int((g - 1) / g * self.operand_bytes)
        if self.kind == "all_gather":
            return int((g - 1) / g * self.result_elems * self.dtype_bytes)
        if self.kind == "all_to_all":
            return int((g - 1) / g * self.operand_bytes)
        if self.kind == "collective_permute":
            return self.operand_bytes
        raise ValueError(self.kind)


_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "i64": 8,
                "i32": 4, "u32": 4, "i16": 2, "u16": 2, "i8": 1, "u8": 1,
                "i1": 1}


def _parse_tensor_type(t) -> Tuple[int, str, int]:
    """(elems, dtype, dtype_bytes) from an MLIR RankedTensorType."""
    s = str(t)                       # e.g. tensor<4x128xf32>
    inner = s[s.index("<") + 1:s.rindex(">")]
    parts = inner.split("x")
    dtype = parts[-1]
    elems = 1
    for p in parts[:-1]:
        elems *= int(p)
    return elems, dtype, _DTYPE_BYTES.get(dtype, 4)


def collective_schedule(lowered, n_devices: int, dcn: int = 1,
                        axis_sizes: Optional[Sequence[Tuple[str, int]]]
                        = None) -> List[Collective]:
    """Walk a ``jax.stages.Lowered`` MLIR module and return every
    collective with its replica-group structure classified against the
    row-major dcn-slice layout of ``AbstractMesh((dcn, ...))``.
    ``axis_sizes`` (the mesh's ``(name, size)`` pairs in declaration
    order) additionally derives each collective's ``spans`` — the set
    of mesh axes its replica groups vary over."""
    per_slice = n_devices // max(dcn, 1)
    out: List[Collective] = []

    strides: List[Tuple[str, int, int]] = []
    if axis_sizes is not None:
        stride = 1
        for name, size in reversed(list(axis_sizes)):
            strides.append((name, size, stride))
            stride *= size

    def classify(groups: np.ndarray) -> Tuple[int, int, bool, frozenset]:
        g = groups.shape[-1]
        crosses = False
        if dcn > 1:
            for row in groups.reshape(-1, g):
                slices = {int(d) // per_slice for d in row}
                if len(slices) > 1:
                    crosses = True
                    break
        spans: set = set()
        if strides:
            for row in groups.reshape(-1, g):
                for name, size, stride in strides:
                    if len({(int(d) // stride) % size for d in row}) > 1:
                        spans.add(name)
        return g, int(np.prod(groups.shape[:-1])), crosses, \
            frozenset(spans)

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    name = o.operation.name
                    if name in _COLLECTIVE_OPS:
                        try:
                            groups = np.array(
                                o.attributes["replica_groups"])
                        except KeyError:   # collective_permute
                            groups = np.array(
                                o.attributes["source_target_pairs"])
                        gsz, ngroups, crosses, spans = classify(groups)
                        oelems, dt, db = _parse_tensor_type(
                            o.operands[0].type)
                        relems, _, _ = _parse_tensor_type(
                            o.results[0].type)
                        out.append(Collective(
                            kind=name.split(".", 1)[1],
                            operand_elems=oelems, result_elems=relems,
                            dtype=dt, dtype_bytes=db, group_size=gsz,
                            n_groups=ngroups, crosses_dcn=crosses,
                            spans=spans))
                    walk(o)

    walk(lowered.compiler_ir().operation)
    return out


# --------------------------------------------------------------------------
# Flagship-step lowering at arbitrary logical device counts
# --------------------------------------------------------------------------

def lower_flagship_step(n_devices: int, dcn: int = 1, cfg=None,
                        seq: int = 128, batch_per_replica: int = 2,
                        partition_bytes: int = 4 << 20,
                        tx=None, reducer=None):
    """AOT-lower the flagship data-parallel training step over an
    ``AbstractMesh((dcn, n_devices // dcn), ("dcn", "data"))``.

    Builds the SAME program ``DistributedTrainer._build_step`` jits —
    ``distributed_optimizer``-wrapped optax inside a ``shard_map`` —
    but from ``ShapeDtypeStruct``s, so no arrays, devices, or compiles
    are involved. Returns ``(lowered, info)`` where ``info`` has the
    exchange's form (``collectives.exchange_form``), the buckets it ran
    (none on the leaf form), the leaves' sizes and the gradient byte
    totals the invariant checks need.
    """
    import optax
    from ..common.partition import plan_buckets
    from ..models import bert, transformer
    from ..optim import distributed_optimizer
    from .collectives import exchange_form, leaf_specs_of_tree

    if cfg is None:
        cfg = bert.bert_large(max_seq=seq)
    if dcn > 1:
        if n_devices % dcn:
            raise ValueError(f"n_devices={n_devices} not divisible by "
                             f"dcn={dcn}")
        mesh = AbstractMesh((dcn, n_devices // dcn), ("dcn", "data"))
        axes: Tuple[str, ...] = ("dcn", "data")
    else:
        mesh = AbstractMesh((n_devices,), ("data",))
        axes = ("data",)

    if tx is None:
        tx = optax.adamw(1e-4)
    kw = {} if reducer is None else {"reducer": reducer}
    dist_tx = distributed_optimizer(tx, axes=axes,
                                    partition_bytes=partition_bytes, **kw)

    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(dist_tx.init, params)
    max_pred = max(1, int(0.2 * seq))

    def loss_fn(p, batch):
        return bert.mlm_loss(p, cfg, batch, max_predictions=max_pred)

    def step(p, s, batch):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        updates, s = dist_tx.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return p, s, jax.lax.pmean(loss, axes)

    shard_fn = jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P(axes)),
        out_specs=(P(), P(), P()), check_vma=False)

    global_batch = batch_per_replica * n_devices
    batch = (jax.ShapeDtypeStruct((global_batch, seq), jnp.int32),
             jax.ShapeDtypeStruct((global_batch, seq), jnp.int32))
    lowered = jax.jit(shard_fn).lower(params, opt_state, batch)

    specs = leaf_specs_of_tree(params)
    buckets = plan_buckets(specs, partition_bytes, reverse_order=True)
    grad_bytes = sum(sp.size * np.dtype(sp.dtype).itemsize
                     for sp in specs)
    form, _ = exchange_form(axes, **kw)
    # the plan that RAN: the leaf form packs no bucket
    info = {"n_buckets": len(buckets) if form == "buckets" else 0,
            "grad_bytes": grad_bytes,
            "axes": axes, "ici": n_devices // max(dcn, 1), "dcn": dcn,
            "form": form, "leaf_elems": [sp.size for sp in specs]}
    return lowered, info


def lower_hybrid_step(n_devices: int, dcn: int = 1, tp: int = 2,
                      sp: int = 2, cfg=None, seq: int = 64,
                      batch_per_replica: int = 2,
                      partition_bytes: int = 4 << 20):
    """AOT-lower the HYBRID step — data × tensor × sequence parallel
    over ``AbstractMesh((dcn, data, seq, model))`` — mirroring
    ``ShardedTrainer``'s program (training.py): per-leaf grad psum over
    the non-dp axes the leaf is not sharded on, then the bucketed DP
    exchange. Used to pin that model/seq collectives NEVER cross the
    dcn tier at any logical scale (the mesh layout guarantee the
    8→256 north star rides on)."""
    import optax
    from ..models import bert, transformer
    from ..optim import distributed_optimizer
    from .sharding import opt_state_specs, spec_axes

    ici_dp = n_devices // (dcn * tp * sp)
    if ici_dp < 1 or n_devices % (dcn * tp * sp):
        raise ValueError(f"{n_devices} devices can't mesh as "
                         f"dcn={dcn}×dp×seq={sp}×model={tp}")
    mesh = AbstractMesh((dcn, ici_dp, sp, tp),
                        ("dcn", "data", "seq", "model"))
    dp_axes = ("dcn", "data") if dcn > 1 else ("data",)
    other_axes = ("seq", "model")

    if cfg is None:
        cfg = bert.bert_tiny(tp_axis="model", sp_axis="seq")
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    pspec = transformer.param_specs(cfg)
    tx = distributed_optimizer(optax.adamw(1e-4), axes=dp_axes,
                               partition_bytes=partition_bytes)
    opt_state = jax.eval_shape(tx.init, params)
    ospec = opt_state_specs(tx, params, pspec)
    max_pred = max(1, int(0.2 * seq))
    flat_specs = jax.tree_util.tree_leaves(
        pspec, is_leaf=lambda x: isinstance(x, P))
    other_prod = sp * tp

    def loss_fn(p, batch):
        return bert.mlm_loss(p, cfg, batch, max_predictions=max_pred)

    def step(p, s, batch):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        g_leaves, g_def = jax.tree_util.tree_flatten(grads)
        synced = []
        for g, sp_ in zip(g_leaves, flat_specs):
            axes = tuple(a for a in other_axes if a not in spec_axes(sp_))
            g = jax.lax.psum(g, axes) if axes else g
            synced.append(g / other_prod)
        grads = jax.tree_util.tree_unflatten(g_def, synced)
        updates, s = tx.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return p, s, jax.lax.pmean(loss, dp_axes + ("seq",))

    batch_spec = P(dp_axes, "seq")
    shard_fn = jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, ospec, batch_spec),
        out_specs=(pspec, ospec, P()), check_vma=False)
    global_batch = batch_per_replica * dcn * ici_dp
    batch = (jax.ShapeDtypeStruct((global_batch, seq), jnp.int32),
             jax.ShapeDtypeStruct((global_batch, seq), jnp.int32))
    lowered = jax.jit(shard_fn).lower(params, opt_state, batch)
    info = {"ici": ici_dp * sp * tp, "dcn": dcn, "tp": tp, "sp": sp,
            "dp": dcn * ici_dp,
            "axis_sizes": (("dcn", dcn), ("data", ici_dp),
                           ("seq", sp), ("model", tp))}
    return lowered, info


def verify_hybrid_schedule(schedule: Sequence[Collective], info: Dict,
                           small_bytes: int = 4096) -> Dict[str, int]:
    """The hybrid-mesh invariant the north star rides on: model/seq
    (TP/SP) collectives — activation syncs and per-leaf grad psums —
    stay INSIDE the slice at every logical scale; only the bucketed DP
    gradient exchange touches dcn. Classified by the mesh AXES each
    replica group actually spans (``Collective.spans``), never by
    group size — sizes collide (tp×sp == dcn at common configs) and a
    size-based check was shown to pass on a broken layout."""
    dcn = info["dcn"]
    bulk = [c for c in schedule if c.operand_bytes > small_bytes]
    assert all(c.spans for c in bulk), \
        "schedule lacks axis spans — pass axis_sizes to " \
        "collective_schedule"
    tp_like = [c for c in bulk if {"model", "seq"} & c.spans]
    for c in tp_like:
        assert "dcn" not in c.spans and not c.crosses_dcn, (
            "a TP/SP collective crosses the dcn tier — the mesh "
            "layout broke", c)
    crossers = [c for c in bulk if "dcn" in c.spans]
    if dcn > 1:
        assert crossers, "no dcn collectives at dcn>1 — grads not synced?"
        for c in crossers:
            assert c.spans == {"dcn"}, (
                "only the pure cross-slice DP stage may span slices", c)
    return {"bulk": len(bulk), "tp_like": len(tp_like),
            "dcn_crossers": len(crossers)}


def lower_moe_step(n_devices: int, dcn: int = 1, ep: int = 2,
                   seq: int = 32, batch_per_replica: int = 2,
                   partition_bytes: int = 64 << 10):
    """AOT-lower the expert-parallel MoE training step over
    ``AbstractMesh((dcn, data, expert))``. Pins that the token-routing
    ``all_to_all`` pair (dispatch + return) rides the expert axis
    INSIDE the slice — all_to_all over DCN would be the worst possible
    placement for the chattiest collective in the program."""
    import optax
    from ..models import moe
    from ..optim import distributed_optimizer

    ici_dp = n_devices // (dcn * ep)
    if ici_dp < 1 or n_devices % (dcn * ep):
        raise ValueError(f"{n_devices} devices can't mesh as "
                         f"dcn={dcn}×dp×expert={ep}")
    mesh = AbstractMesh((dcn, ici_dp, ep), ("dcn", "data", "expert"))
    dp_axes = ("dcn", "data") if dcn > 1 else ("data",)
    cfg = moe.moe_tiny(ep_axis="expert")
    params = jax.eval_shape(
        lambda: moe.init_moe_params(jax.random.PRNGKey(0), cfg))
    pspec = moe.moe_param_specs(cfg)
    tx = distributed_optimizer(optax.adamw(1e-4), axes=dp_axes,
                               partition_bytes=partition_bytes)
    opt_state = jax.eval_shape(tx.init, params)
    from .sharding import opt_state_specs, spec_axes
    ospec = opt_state_specs(tx, params, pspec)
    flat_specs = jax.tree_util.tree_leaves(
        pspec, is_leaf=lambda x: isinstance(x, P))

    def step(p, s, batch):
        loss, grads = jax.value_and_grad(
            lambda p, b: moe.moe_lm_loss(p, cfg, b))(p, batch)
        g_leaves, g_def = jax.tree_util.tree_flatten(grads)
        synced = []
        for g, sp_ in zip(g_leaves, flat_specs):
            if "expert" not in spec_axes(sp_):
                g = jax.lax.psum(g, ("expert",)) / ep
            else:
                g = g / ep
            synced.append(g)
        grads = jax.tree_util.tree_unflatten(g_def, synced)
        updates, s = tx.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return p, s, jax.lax.pmean(loss, dp_axes)

    batch_spec = P(dp_axes)
    shard_fn = jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, ospec, batch_spec),
        out_specs=(pspec, ospec, P()), check_vma=False)
    global_batch = batch_per_replica * dcn * ici_dp
    batch = (jax.ShapeDtypeStruct((global_batch, seq), jnp.int32),
             jax.ShapeDtypeStruct((global_batch, seq), jnp.int32))
    lowered = jax.jit(shard_fn).lower(params, opt_state, batch)
    info = {"dcn": dcn, "ep": ep, "dp": dcn * ici_dp,
            "ici": ici_dp * ep,
            "axis_sizes": (("dcn", dcn), ("data", ici_dp),
                           ("expert", ep))}
    return lowered, info


def verify_moe_schedule(schedule: Sequence[Collective], info: Dict,
                        small_bytes: int = 1024) -> Dict[str, int]:
    """EP invariant: every all_to_all spans EXACTLY the expert axis (so
    it never leaves the slice); dcn crossers span only dcn — and at
    dcn>1 they must EXIST (a schedule with no cross-slice stage means
    gradients are never synchronized across slices)."""
    bulk = [c for c in schedule if c.operand_bytes > small_bytes]
    assert all(c.spans for c in bulk), \
        "schedule lacks axis spans — pass axis_sizes to " \
        "collective_schedule"
    a2a = [c for c in schedule if c.kind == "all_to_all"]
    assert a2a, "MoE step lowered no all_to_all — routing vanished?"
    for c in a2a:
        assert c.spans == {"expert"}, (
            "token routing must ride the expert axis only", c)
    crossers = [c for c in bulk if "dcn" in c.spans]
    for c in crossers:
        assert c.spans == {"dcn"}, (
            "only the cross-slice DP stage may span slices", c)
    if info["dcn"] > 1:
        assert crossers, "no dcn collectives at dcn>1 — grads not synced?"
    return {"bulk": len(bulk), "all_to_all": len(a2a),
            "dcn_crossers": len(crossers)}


# --------------------------------------------------------------------------
# Invariant verification
# --------------------------------------------------------------------------

def verify_dp_schedule(schedule: Sequence[Collective], info: Dict,
                       small_bytes: int = 4096) -> Dict[str, int]:
    """Assert the collective schedule of a lowered DP step.

    Pins, per the module docstring: on the leaf form every gradient leaf
    in exactly one all-reduce and no collective of another kind, where
    buckets run one reduction collective per bucket, hierarchical
    rs/ar/ag shape on hybrid meshes, no full-size bulk collective across
    the dcn tier, and gradient byte totals. Raises
    ``AssertionError`` with a diagnostic on any violation; returns
    summary counts on success."""
    n_buckets = info["n_buckets"]
    ici, dcn = info["ici"], info["dcn"]
    bulk = [c for c in schedule if c.operand_bytes > small_bytes]
    small = [c for c in schedule if c.operand_bytes <= small_bytes]

    if dcn <= 1:
        # ICI-only, either form: nothing bulk but all-reduces, every
        # all-reduce over the whole data axis
        assert not [c for c in bulk if c.kind != "all_reduce"], bulk
        ars = [c for c in schedule if c.kind == "all_reduce"]
        for c in ars:
            assert c.group_size == ici * dcn, c
    if dcn <= 1 and info.get("form") == "leaves":
        # the leaf form (collectives.leaf_allreduce): every gradient leaf
        # is reduced as it is. What matters of the old one-per-bucket pin
        # stays: each leaf exactly once, together exactly the gradient
        # bytes. Matched as multisets of element counts; what is left
        # over must be small (the loss's mean).
        left = sorted(info["leaf_elems"])
        reduced = 0
        for c in sorted(ars, key=lambda c: -c.operand_elems):
            if c.operand_elems in left:
                left.remove(c.operand_elems)
                reduced += c.operand_bytes
            else:
                assert c.operand_bytes <= small_bytes, (
                    "a bulk all_reduce that is no gradient leaf: the "
                    "exchange re-packed or reduced something twice", c)
        assert not left, (
            f"gradient leaves of {left} elements reach no all_reduce: "
            f"the exchange dropped or re-packed them")
    elif dcn <= 1:
        assert len(bulk) == n_buckets, (
            f"expected exactly one all_reduce per bucket "
            f"({n_buckets}), lowered program has {len(bulk)}: a "
            f"regression de-bucketed or serialized the exchange\n"
            f"{bulk}")
        reduced = sum(c.operand_bytes for c in bulk)
    else:
        rs = [c for c in bulk if c.kind == "reduce_scatter"]
        ar = [c for c in bulk if c.kind == "all_reduce"]
        ag = [c for c in bulk if c.kind == "all_gather"]
        assert len(rs) == len(ar) == len(ag) == n_buckets, (
            f"hybrid mesh must lower one rs/ar/ag triplet per bucket "
            f"({n_buckets}); got rs={len(rs)} ar={len(ar)} "
            f"ag={len(ag)}")
        other = [c for c in bulk
                 if c.kind not in ("reduce_scatter", "all_reduce",
                                   "all_gather")]
        assert not other, (
            "bulk collectives outside the rs/ar/ag schedule", other)
        for c in rs + ag:
            assert not c.crosses_dcn and c.group_size == ici, (
                "in-slice stage leaked across dcn", c)
        for c in ar:
            assert c.crosses_dcn and c.group_size == dcn, c
        # the cross-slice stage must carry the 1/ici shards, not full
        # buckets — this IS the hierarchical bandwidth win. Matched as
        # multisets: HLO walk order is a trace implementation detail
        want = sorted(math.ceil(c.operand_elems / ici) for c in rs)
        got = sorted(c.operand_elems for c in ar)
        assert got == want, (
            f"dcn all_reduce sizes {got} != in-slice shard sizes {want}")
        reduced = sum(c.operand_bytes for c in rs)
    # total collective-visible gradient bytes == parameter-grad bytes
    # (± per-bucket padding to a multiple of ici)
    pad_slack = n_buckets * ici * 8
    assert abs(reduced - info["grad_bytes"]) <= pad_slack, (
        f"collectives reduce {reduced} bytes; gradients are "
        f"{info['grad_bytes']}")
    # nothing big may cross dcn at full size; small (loss pmean etc.)
    # collectives are unconstrained
    return {"bulk": len(bulk), "small": len(small),
        "reduced_bytes": reduced}


# --------------------------------------------------------------------------
# Analytic step-time / scaling model
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommModel:
    """Per-tier bandwidth/latency model. Defaults are DOCUMENTED
    ASSUMPTIONS, tunable per deployment:

    - ``ici_bw``: effective per-chip ring bandwidth inside a slice.
      TPU v5e has 4 ICI links/chip at ~45 GB/s per direction
      ("How to Scale Your Model", jax-ml.github.io/scaling-book); a 1-D
      ring decomposition drives one link pair both directions →
      ~9e10 B/s algorithm bandwidth per chip.
    - ``dcn_bw``: per-slice (8-chip host group) data-center network
      bandwidth. 25 GB/s ≈ 200 Gbps NICs — the same class as the
      reference's 100 Gbps RDMA fabric (reference README.md:37-44),
      conservatively doubled for current-gen pods.
    - ``latency``: per-collective launch+hop cost.
    """
    ici_bw: float = 9.0e10
    dcn_bw: float = 2.5e10
    latency: float = 15e-6

    def time(self, c: Collective) -> float:
        bw = self.dcn_bw if c.crosses_dcn else self.ici_bw
        return self.latency + c.wire_bytes() / bw


V5E_COMM = CommModel()


def model_step_time(schedule: Sequence[Collective], compute_s: float,
                    comm: CommModel = V5E_COMM,
                    small_bytes: int = 4096) -> Dict[str, float]:
    """Step-time bounds from measured compute + modeled comm.

    ``no_overlap``: compute then serial comm (pessimal). ``overlap``:
    XLA's latency-hiding scheduler hides comm under backward compute —
    comm only shows once it exceeds the compute window. Reality lands
    between; the reference's measured 90% @ 256 sits at the overlap
    end. On the v5e at dp=4 today's step sits at the no-overlap end:
    under ``lax.scan`` every gradient is ready at once and the
    all-reduces run exposed after the backward (PERF.md section 5,
    ROADMAP A3(b))."""
    t_comm = sum(comm.time(c) for c in schedule
                 if c.operand_bytes > small_bytes)
    return {
        "compute_s": compute_s,
        "comm_s": t_comm,
        "no_overlap_s": compute_s + t_comm,
        "overlap_s": max(compute_s, t_comm),
    }


def scaling_table(compute_s: float,
                  configs: Sequence[Tuple[int, int]] = ((8, 1), (64, 8),
                                                       (256, 32)),
                  comm: CommModel = V5E_COMM, cfg=None, seq: int = 512,
                  partition_bytes: int = 4 << 20,
                  verify: bool = True,
                  small_bytes: int = 4096) -> List[Dict[str, float]]:
    """Lower the flagship step at each ``(n_devices, dcn)``, verify its
    schedule, and evaluate the analytic model. ``compute_s`` is the
    measured single-chip per-step compute time (bench.py)."""
    rows = []
    for n, dcn in configs:
        lowered, info = lower_flagship_step(
            n, dcn=dcn, cfg=cfg, seq=seq,
            partition_bytes=partition_bytes)
        sched = collective_schedule(lowered, n, dcn=dcn)
        if verify:
            verify_dp_schedule(sched, info, small_bytes=small_bytes)
        t = model_step_time(sched, compute_s, comm,
                            small_bytes=small_bytes)
        rows.append({
            "devices": n, "dcn": dcn, "ici": info["ici"],
            "buckets": info["n_buckets"],
            "grad_mb": info["grad_bytes"] / 1e6,
            "comm_ms": t["comm_s"] * 1e3,
            "dcn_ms": sum(comm.time(c) for c in sched
                          if c.crosses_dcn
                          and c.operand_bytes > small_bytes) * 1e3,
            "eff_no_overlap": compute_s / t["no_overlap_s"],
            "eff_overlap": compute_s / t["overlap_s"],
        })
    return rows


def format_table(rows: Sequence[Dict[str, float]]) -> str:
    hdr = ("| devices | mesh (dcn×ici) | buckets | grad MB | comm ms "
           "| dcn ms | eff (no overlap) | eff (overlapped) |")
    sep = "|" + "---|" * 8
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['devices']} | {r['dcn']}×{r['ici']} | {r['buckets']} "
            f"| {r['grad_mb']:.0f} | {r['comm_ms']:.1f} "
            f"| {r['dcn_ms']:.1f} | {r['eff_no_overlap']:.3f} "
            f"| {r['eff_overlap']:.3f} |")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--compute-ms", type=float, default=848.0,
                    help="measured single-chip step time (bench.py: "
                         "64 samples @ 75.48 samples/s = 848 ms)")
    ap.add_argument("--configs", default="8:1,64:8,256:32",
                    help="comma list of n_devices:dcn")
    ap.add_argument("--seq", type=int, default=512)
    args = ap.parse_args(argv)
    configs = [tuple(map(int, c.split(":")))
               for c in args.configs.split(",")]
    rows = scaling_table(args.compute_ms / 1e3, configs=configs,
                         seq=args.seq)
    print(format_table(rows))
    # one-stop evidence: also verify the hybrid (TP/SP) and MoE (EP)
    # schedules at a multi-slice size
    lowered, info = lower_hybrid_step(64, dcn=4,
                                      partition_bytes=64 << 10)
    sched = collective_schedule(lowered, 64, dcn=4,
                                axis_sizes=info["axis_sizes"])
    verify_hybrid_schedule(sched, info)
    lowered, info = lower_moe_step(64, dcn=4)
    sched = collective_schedule(lowered, 64, dcn=4,
                                axis_sizes=info["axis_sizes"])
    verify_moe_schedule(sched, info)
    print("hybrid (dcn×data×seq×model) and MoE (dcn×data×expert) "
          "schedules verified at 64 devices: TP/SP/EP collectives "
          "never cross the dcn tier")


if __name__ == "__main__":
    main()

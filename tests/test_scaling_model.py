"""HLO collective-schedule invariants at 8/64/256 logical devices.

The multi-chip north star (BASELINE.md: ≥90% scaling efficiency
8 → 256, per reference README.md:37-44) cannot be measured on this box;
these tests pin what the curve depends on that IS checkable without
hardware: the compiled data-parallel step's communication structure,
AOT-lowered over an AbstractMesh (see parallel/scaling_model.py
docstring). A regression that drops or re-packs a gradient leaf on the
ICI path, de-buckets where buckets run, serializes an extra hop, or
ships full-size buckets across the dcn tier fails here.
"""

import jax
import numpy as np
import pytest

from byteps_tpu.models import bert
from byteps_tpu.parallel.scaling_model import (
    CommModel, collective_schedule, format_table, lower_flagship_step,
    model_step_time, scaling_table, verify_dp_schedule)

# small model + small buckets: same program shape as the flagship
# (multi-bucket, multi-layer), seconds to trace instead of minutes
CFG = bert.bert_tiny()
PB = 64 << 10


def _lower(n, dcn=1, **kw):
    return lower_flagship_step(n, dcn=dcn, cfg=CFG, seq=32,
                               partition_bytes=PB, **kw)


def _flat_psum(x, axes):
    return jax.lax.psum(x, axes)


@pytest.mark.parametrize("form", ["leaves", "buckets"])
def test_ici_only_one_allreduce_per_bucket(form):
    """ICI-only: nothing but all-reduces over the data axes, together
    exactly the gradient bytes. On the default path each gradient leaf
    once, in its own size (no bucket is packed); under a custom reducer,
    whose contract is a flat buffer, one a bucket as before."""
    kw = {} if form == "leaves" else {"reducer": _flat_psum}
    lowered, info = _lower(8, **kw)
    assert info["form"] == form
    sched = collective_schedule(lowered, 8)
    counts = verify_dp_schedule(sched, info)
    assert {c.kind for c in sched} == {"all_reduce"}
    assert all(c.group_size == 8 for c in sched)
    if form == "buckets":
        assert info["n_buckets"] > 1, "config must exercise multi-bucket"
        assert counts["bulk"] == info["n_buckets"]
    else:
        assert info["n_buckets"] == 0
        bulk = sorted(c.operand_elems for c in sched
                      if c.operand_bytes > 4096)
        assert bulk == sorted(n for n in info["leaf_elems"] if n > 1024)
        assert counts["bulk"] == len(bulk) > 1
    # byte volume: collectives carry exactly the gradient bytes
    assert counts["reduced_bytes"] == info["grad_bytes"]


@pytest.mark.parametrize("fault", ["dropped_leaf", "repacked"])
def test_leaf_form_regressions_fail_the_ici_invariants(fault):
    """What the one-per-bucket pin was for, on the form that has no
    bucket: a leaf that reaches no all-reduce, or gradients re-packed
    into buffers that are no leaf, must FAIL verification."""
    import dataclasses
    lowered, info = _lower(8)
    sched = collective_schedule(lowered, 8)
    big = max(sched, key=lambda c: c.operand_elems)
    if fault == "dropped_leaf":
        bad = [c for c in sched if c is not big]
    else:
        half = dataclasses.replace(big, operand_elems=big.operand_elems // 2,
                                   result_elems=big.result_elems // 2)
        bad = [c for c in sched if c is not big] + [half, half]
    with pytest.raises(AssertionError):
        verify_dp_schedule(bad, info)


def test_hybrid_mesh_hierarchical_schedule():
    """dcn×ici lowers one reduce_scatter/all_reduce/all_gather triplet
    per bucket; only the 1/ici shard crosses the dcn tier."""
    lowered, info = _lower(64, dcn=8)
    sched = collective_schedule(lowered, 64, dcn=8)
    verify_dp_schedule(sched, info)
    bulk = [c for c in sched if c.operand_bytes > 4096]
    dcn_bytes = sum(c.wire_bytes() for c in bulk if c.crosses_dcn)
    ici_stage = sum(c.wire_bytes() for c in bulk if not c.crosses_dcn)
    # hierarchical win: dcn wire traffic ≈ 2(dcn-1)/dcn × grads/ici —
    # 8× less than a flat all_reduce of the full gradients would ship
    flat_dcn = 2 * 63 / 64 * info["grad_bytes"]
    assert dcn_bytes < flat_dcn / 4, (dcn_bytes, flat_dcn)
    assert ici_stage > 0


def test_256_devices_lowers_and_verifies():
    """The 256-logical-device program is checkable on a 1-chip box —
    the whole point of AOT lowering over AbstractMesh."""
    lowered, info = _lower(256, dcn=32)
    sched = collective_schedule(lowered, 256, dcn=32)
    verify_dp_schedule(sched, info)
    ar = [c for c in sched if c.kind == "all_reduce"
          and c.operand_bytes > 4096]
    assert all(c.group_size == 32 and c.crosses_dcn for c in ar)


def test_flat_psum_regression_fails_hybrid_invariants():
    """A reducer that ships full buckets across dcn (flat psum over both
    axes — the pre-round-3 lowering) must FAIL verification: this is the
    regression the pins exist to catch."""
    flat = lambda x, axes: jax.lax.psum(x, axes)  # noqa: E731
    lowered, info = _lower(64, dcn=8, reducer=flat)
    sched = collective_schedule(lowered, 64, dcn=8)
    with pytest.raises(AssertionError):
        verify_dp_schedule(sched, info)


def test_wire_bytes_formulas():
    from byteps_tpu.parallel.scaling_model import Collective
    ar = Collective("all_reduce", 1000, 1000, "f32", 4, 8, 1, False)
    assert ar.wire_bytes() == int(2 * 7 / 8 * 4000)
    rs = Collective("reduce_scatter", 1000, 125, "f32", 4, 8, 1, False)
    assert rs.wire_bytes() == int(7 / 8 * 4000)
    ag = Collective("all_gather", 125, 1000, "f32", 4, 8, 1, False)
    assert ag.wire_bytes() == int(7 / 8 * 4000)


def test_model_step_time_and_table():
    """Analytic model sanity: comm grows with dcn, overlap bound never
    exceeds the no-overlap bound, efficiencies in (0, 1]."""
    rows = scaling_table(0.848, configs=((8, 1), (64, 8)), cfg=CFG,
                         seq=32, partition_bytes=PB)
    assert rows[1]["dcn_ms"] > rows[0]["dcn_ms"] == 0
    for r in rows:
        assert 0 < r["eff_no_overlap"] <= r["eff_overlap"] <= 1
    txt = format_table(rows)
    assert "devices" in txt and "64" in txt


def test_slow_fabric_breaks_overlap_bound():
    """On a 100× slower fabric the model must show comm-bound steps —
    guards against the model silently reporting 1.0 for any input."""
    lowered, info = _lower(64, dcn=8)
    sched = collective_schedule(lowered, 64, dcn=8)
    slow = CommModel(ici_bw=9e8, dcn_bw=2.5e7)
    t = model_step_time(sched, compute_s=1e-4, comm=slow)
    assert t["overlap_s"] > 1e-4, t


def test_hybrid_mesh_tp_sp_never_cross_dcn():
    """The hybrid (dcn × data × seq × model) step's TP/SP collectives —
    activation syncs and per-leaf grad psums — must stay inside the
    slice at every logical scale; only the DP gradient stages may span
    slices. This is the mesh-layout guarantee the 8→256 curve rides
    on (ICI carries the chatty parallelism, DCN only the 1/ici
    gradient shard)."""
    from byteps_tpu.parallel.scaling_model import (lower_hybrid_step,
                                                   verify_hybrid_schedule)
    for n, dcn in ((16, 2), (64, 4), (256, 8)):
        lowered, info = lower_hybrid_step(n, dcn=dcn,
                                          partition_bytes=64 << 10)
        sched = collective_schedule(lowered, n, dcn=dcn,
                                    axis_sizes=info["axis_sizes"])
        out = verify_hybrid_schedule(sched, info)
        # the dcn-crossing count must not grow with device count: it is
        # one per DP bucket stage, not per chip
        assert out["dcn_crossers"] == 4, out
        assert out["bulk"] > out["dcn_crossers"], out
        # axis-membership classification (NOT group size — sizes
        # collide at e.g. tp*sp == dcn): every bulk collective's spans
        # are known, TP/SP ones present and slice-local
        assert out["tp_like"] > 0, out


def test_moe_all_to_all_rides_expert_axis_only():
    """EP invariant: the token-routing all_to_all pair (dispatch +
    return, fwd + bwd) spans exactly the expert axis — never the dcn
    tier — at any logical scale; dcn sees only the pure DP stage."""
    from byteps_tpu.parallel.scaling_model import (lower_moe_step,
                                                   verify_moe_schedule)
    for n, dcn in ((16, 2), (64, 4)):
        lowered, info = lower_moe_step(n, dcn=dcn)
        sched = collective_schedule(lowered, n, dcn=dcn,
                                    axis_sizes=info["axis_sizes"])
        out = verify_moe_schedule(sched, info)
        assert out["all_to_all"] == 4, out   # fwd+bwd x dispatch+return


# ---------------------------------------------------------------------------
# round 4: weld the analytic model to the throttle rig (VERDICT r3 #4)
# ---------------------------------------------------------------------------

def test_comm_model_dcn_term_matches_throttled_emulation():
    """The scaling table's cross-slice (DCN) comm term — the piece the
    94.1%@256 efficiency claim leans on — validated by EXECUTION, not
    arithmetic: the flagship schedule's ar-dcn collectives (the 1/ici
    shards) are run as a real ring all-reduce over throttled sockets at
    a scaled-down bandwidth, and CommModel's prediction at that same
    bandwidth must land within a ±30% band of the measured wall time
    (the rig carries real framing/threading overheads; the ring itself
    tracks its analytic form within ~4% when idle)."""
    from byteps_tpu.server.allreduce_emu import ring_allreduce

    n, dcn = 16, 4
    lowered, info = _lower(n, dcn=dcn)
    sched = collective_schedule(lowered, n, dcn=dcn)
    ars = [c for c in sched
           if c.kind == "all_reduce" and c.crosses_dcn
           and c.operand_bytes > 4096]
    assert ars, "no cross-slice all_reduce in the hybrid schedule"
    for c in ars:
        assert c.group_size == dcn
    shard_bytes = sum(c.operand_bytes for c in ars)

    # pick the emulation bandwidth so the predicted hop lands at
    # ~150 ms — slow enough that socket/CPU overheads are noise, fast
    # enough for CI (self-calibrating: the tiny model's shard total
    # sets W, the RATIO is what's under test)
    wire_factor = 2 * (dcn - 1) / dcn
    W = wire_factor * shard_bytes / 0.15
    model = CommModel(ici_bw=1e30, dcn_bw=W, latency=0.0)
    t_model = sum(model.time(c) for c in ars)
    # one ring all-reduce of the concatenated shards between dcn
    # endpoints — the same algorithm (reduce-scatter + all-gather),
    # same 2(g-1)/g wire factor, real sockets
    t_emu = ring_allreduce(dcn, shard_bytes, rate=W, iters=2)
    assert t_model > 0.05, (t_model, "regime too fast to measure")
    ratio = t_emu / t_model
    assert 0.7 < ratio < 1.3, (
        f"CommModel dcn term {t_model*1e3:.0f} ms vs emulated "
        f"{t_emu*1e3:.0f} ms (ratio {ratio:.2f}) — the analytic model "
        f"and the throttle rig disagree")


def test_slow_dcn_degrades_and_compression_recovers():
    """The slower-DCN sweep point: at dcn_bw/10 the overlapped
    efficiency bound degrades; shrinking the cross-slice bytes by the
    onebit codec ratio (32x) recovers it. Model-level here — the
    EXECUTED version of the compression recovery is
    test_ps_vs_allreduce.py::test_compressed_ps_crushes_bandwidth_bound_regime
    and the training-level A/B (test_train_emu.py)."""
    import dataclasses as _dc

    n, dcn = 64, 8
    lowered, info = _lower(n, dcn=dcn)
    sched = collective_schedule(lowered, n, dcn=dcn)
    verify_dp_schedule(sched, info)

    # latency=0: the tiny CI model's collectives are so small that the
    # 15 us/op launch cost would swamp the BANDWIDTH term this test is
    # about (the flagship's buckets are 4 MB; per-op latency is noise
    # there)
    fast = _dc.replace(CommModel(), latency=0.0)
    slow = _dc.replace(fast, dcn_bw=fast.dcn_bw / 10)

    def comm_time(comm, byte_scale=1.0):
        t = 0.0
        for c in sched:
            if c.operand_bytes <= 4096:
                continue
            dt = comm.time(c)
            if c.crosses_dcn and byte_scale != 1.0:
                # compression shrinks only the WIRE bytes of the
                # cross-slice hop (the in-slice stages stay dense)
                dt = comm.latency + c.wire_bytes() * byte_scale / comm.dcn_bw
            t += dt
        return t

    # compute window calibrated to the tiny model: 2x the fast-fabric
    # comm, so overlap fully hides comm at the documented bandwidths
    # (the flagship table's regime) and the RATIOS carry the test
    compute_s = 2 * comm_time(fast)

    def eff(comm, byte_scale=1.0):
        return compute_s / max(compute_s, comm_time(comm, byte_scale))

    e_fast = eff(fast)
    e_slow = eff(slow)
    e_recovered = eff(slow, byte_scale=1 / 32)   # onebit on the dcn hop
    assert e_fast == 1.0
    assert e_slow < 0.95, f"10x slower DCN should break overlap: {e_slow}"
    assert e_recovered > 0.99, (
        f"32x fewer cross-slice bytes should restore full overlap at "
        f"this scale: {e_recovered}")

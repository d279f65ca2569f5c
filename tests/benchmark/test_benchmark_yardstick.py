"""The benchmark's yardstick: traffic generator, required FLOPs, the trace
reducer's arithmetic and the comparison that decides ``correct``."""

import json
import math
import os

import numpy as np
import pytest

from tinybench import ROOT

from benchmark import correct, flops, generator
from benchmark.trace import reduce

BENCH = os.path.join(ROOT, "benchmark")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    return generator.load(os.path.join(BENCH, "traffic", name + ".json"))


# -------------------------------------------------------------- traffic

@pytest.mark.parametrize("mix,per_row", [("mlm_b64_s512", 77),
                                         ("mlm_b64_s512_dp4", 77),
                                         ("mlm_b256_s128", 19)])
def test_mlm_rows_have_exactly_the_published_count(mix, per_row):
    m = _mix(mix)
    assert generator.targets_per_row(m) == per_row <= \
        m["max_predictions_per_seq"]
    stream = generator.batches(m, 30522, 1, seed=2**31 + 9)
    for _ in range(3):
        tokens, targets = next(stream)
        assert tokens.shape == targets.shape == (m["batch_per_chip"],
                                                 m["seq"])
        assert ((targets >= 0).sum(1) == per_row).all()
        at = targets >= 0
        assert (tokens[at] == m["mask_token_id"]).all()
        assert (targets[at] >= 1).all() and (targets[at] < 30522).all()
        assert (tokens[~at] >= 1).all() and (tokens[~at] < 30522).all()


@pytest.mark.parametrize("mix", ["mlm_b64_s512", "lm_b8_s1024"])
def test_batches_are_a_function_of_the_seed_alone(mix):
    m = _mix(mix)

    def three(seed, chips=1):
        s = generator.batches(m, 1000, chips, seed)
        return [np.concatenate([np.ravel(x) for x in
                                (b if isinstance(b, tuple) else (b,))])
                for b in (next(s), next(s), next(s))]

    a, b, c = three(5), three(5), three(6)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()
    assert not (a[0] == a[1]).all()          # a fresh batch every step
    assert three(5, chips=4)[0].size == 4 * a[0].size


def test_lm_batch_is_tokens_and_every_position_but_the_last_a_target():
    m = _mix("lm_b8_s1024")
    tokens = next(generator.batches(m, 50257, 1, 3))
    assert tokens.shape == (8, 1024) and tokens.dtype == np.int32
    assert generator.targets_per_row(m) == 1023


def test_a_traffic_file_without_its_sizes_is_refused(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"kind": "mlm", "seq": 128}))
    with pytest.raises(ValueError, match="batch_per_chip"):
        generator.load(str(p))
    p.write_text(json.dumps({"kind": "images", "seq": 1,
                             "batch_per_chip": 1}))
    with pytest.raises(ValueError, match="kind"):
        generator.load(str(p))


# ---------------------------------------------------------------- FLOPs

def test_bert_large_flops_by_hand():
    h, m, layers, vocab, s, n = 1024, 4096, 24, 30522, 512, 77
    fwd = layers * (6 * h * h + 2 * h * h + 4 * h * m + 4 * s * h) \
        + 2 * h * vocab * n / s
    got = flops.transformer_lm(_config("bert_large_mlm")["sizes"], s, n)
    assert got == pytest.approx(3 * fwd, rel=1e-12)
    assert 1.95e9 < got < 2.05e9            # about 2 GFLOP a token


def test_gpt2_medium_flops_count_the_lower_triangle_only():
    sizes = _config("gpt2_medium_lm")["sizes"]
    h, m, layers, vocab, s = 1024, 4096, 24, 50257, 1024
    tri = (s + 1) / 2                       # mean keys a query attends
    fwd = layers * (8 * h * h + 4 * h * m + 4 * h * tri) \
        + 2 * h * vocab * (s - 1) / s
    got = flops.transformer_lm(sizes, s, s - 1)
    assert got == pytest.approx(3 * fwd, rel=1e-12)
    full = flops.transformer_lm(dict(sizes, causal=False), s, s - 1)
    assert full - got == pytest.approx(3 * layers * 4 * h * (s - tri))


# ---------------------------------------------------- interval arithmetic

def test_merge_length_subtract_on_hand_made_intervals():
    assert reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == \
        [(0, 3), (5, 8)]
    assert reduce.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]
    assert reduce.subtract([(0, 4), (6, 8)], [(0, 10)]) == []
    assert reduce.subtract([(0, 4)], []) == [(0, 4)]


def _trace(ops, async_ops=(), host=(), modules=((0, 100), (100, 100))):
    ev = lambda rows: [reduce.Event(n, s, d) for n, s, d in rows]
    return {"devices": {"/device:TPU:0": {
        reduce.MODULE_LINE: [reduce.Event("jit_step(1)", s, d)
                             for s, d in modules],
        reduce.OP_LINE: ev(ops), reduce.ASYNC_LINE: ev(async_ops)}},
        "host": ev(host)}


def test_exposed_collective_time_on_hand_made_intervals():
    """Two steps of 100 ns. A collective runs 10..40 asynchronously while
    a fusion covers 0..30: 10 ns of it are exposed. In step two the
    collective 150..170 is a plain op with nothing beside it: all 20."""
    trace = _trace(
        ops=[("%f.1 = _ fusion()", 0, 30),
             ("%ar-done.1 = _ all-reduce-done()", 30, 10),
             ("%w.1 = _ while()", 0, 100),       # a container: not work
             ("%k.1 = _ custom-call()", 50, 25),
             ("%f.2 = _ fusion()", 100, 50),
             ("%ar.2 = _ all-reduce()", 150, 20)],
        async_ops=[("%ar-start.1 = _ all-reduce-start()", 10, 30)],
        host=[("bench.step", 70, 40), ("bench.next", 170, 40)])
    chip = reduce.summarize(trace, "/device:TPU:0")
    assert chip.steps == 2 and chip.window == (0, 200)
    assert chip.seconds("collective") == pytest.approx(50e-9)
    assert chip.exposed_seconds("collective") == pytest.approx(30e-9)
    assert chip.seconds("pallas") == pytest.approx(25e-9)
    assert chip.busy_s == pytest.approx((40 + 25 + 70) * 1e-9)
    assert chip.idle_gaps() == [(40, 50), (75, 100), (170, 200)]
    assert chip.top_gaps(2) == [["bench.next", pytest.approx(30e-9)],
                                ["bench.step", pytest.approx(25e-9)]]
    assert chip.top_ops(1) == [["f.2 fusion", pytest.approx(50e-9)]]


@pytest.mark.parametrize("name,op,cat", [
    ("%while.6 = (s32[]{:T(128)}, bf16[64,512]{1,0:T(8,128)(2,1)}) "
     "while((s32[]) %t), condition=%c, body=%b", "while", "container"),
    ("%add_fusion.2 = bf16[64,512,1024]{2,1,0:T(8,128)(2,1)S(1)} "
     "fusion(bf16[64] %g), kind=kOutput, calls=%f", "fusion", "fusion"),
    ("%closed_call.8 = (bf16[64,16,512,64]{3,2,1,0}, f32[64,16,512,1]"
     "{3,2,1,0:T(8,128)}) custom-call(bf16[1] %x), custom_call_target="
     "\"tpu_custom_call\"", "custom-call", "pallas"),
    ("%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024] %x)",
     "all-reduce-start", "collective"),
    ("%copy-start.19 = (f32[512,1024]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
     "copy-start(f32[5", "copy-start", "data_movement"),
    ("%convolution.4 = bf16[8,8]{1,0} convolution(bf16[8,8] %a)",
     "convolution", "matmul"),
])
def test_opcode_and_category_from_the_names_a_tpu_trace_gives(name, op, cat):
    assert reduce.opcode(name) == op
    assert reduce.category(name) == cat


# --------------------------------------------------------- recorded trace

@pytest.mark.parametrize("chips", [1, 4])
def test_reducer_on_the_recorded_trace(chips):
    """A trace of a tiny BERT recorded on the v5e (PR 24) through the
    benchmark's own traced run: planes, lines and names as the chip's
    profiler gives them."""
    trace = reduce.load_fixture(os.path.join(
        FIXTURES, f"tiny_trace_{chips}chip.json.gz"))
    planes = reduce.device_planes(trace)
    assert len(planes) == chips
    for plane in planes:
        chip = reduce.summarize(trace, plane)
        assert chip.step_module.startswith("jit_step")
        assert chip.steps >= 2
        by_cat = {}
        for e in chip.ops:
            by_cat.setdefault(reduce.category(e.name), 0.0)
            by_cat[reduce.category(e.name)] += e.dur_ns / 1e9
        assert "container" not in by_cat
        assert by_cat["fusion"] > 0 and by_cat["pallas"] > 0
        assert 0 < chip.busy_s <= chip.window_s
        assert chip.busy_s <= sum(by_cat.values()) * (1 + 1e-9)
        idle = sum(b - a for a, b in chip.idle_gaps()) / 1e9
        assert chip.busy_s + idle == pytest.approx(chip.window_s)
        assert (chip.seconds("collective") > 0) == (chips > 1)
        assert chip.exposed_seconds("collective") <= \
            chip.seconds("collective") * (1 + 1e-9)
        assert any(e.name == "bench.step" for e in chip.host)


# -------------------------------------------------------------- correct

def test_worst_leaf_gap_is_a_gap_of_norms_over_leaf_or_median_leaf():
    want = np.array([2.0, 2.0, 4.0, 1e-9])
    got = np.array([2.2, 2.0, 4.0, 1e-3])
    r = correct.norm_readings(got, want, list("abcd"))
    # the all-but-zero leaf d is measured against the median leaf (2.0)
    assert r["rel"] == (pytest.approx(0.1), "a")
    assert r["rms_rel"][0] == pytest.approx(
        math.sqrt((0.1 ** 2 + (1e-3 / 2.0) ** 2) / 4), rel=1e-6)
    tot = lambda v: math.sqrt(float((v ** 2).sum()))
    assert r["total_rel"][0] == pytest.approx(
        abs(tot(got) - tot(want)) / tot(want))


def test_compare_holds_each_number_to_its_own_limit():
    ref = {"loss": [10.0, 9.0, 8.0], "grad_norm": np.ones(4),
           "change_norm": np.ones(4), "leaf_names": list("abcd")}
    prog = {"loss": [10.0, 9.0, 8.004], "grad_norm": np.ones(4) * 1.02,
            "change_norm": np.array([1, 1, 1, 0.0])}
    limits = {"loss_rel": 1e-3, "grad_norm_rel": 0.05,
              "change_norm_rel": 0.5, "trainer_vs_plain_loss_rel": 1e-4}
    rows = {r["check"]: r for r in correct.compare(prog, ref, limits)}
    assert set(rows) == {"loss_rel", "grad_norm_rel", "change_norm_rel"}
    assert rows["loss_rel"]["ok"] and rows["loss_rel"]["where"] == "step 3"
    assert rows["grad_norm_rel"]["value"] == pytest.approx(0.02)
    assert not rows["change_norm_rel"]["ok"]
    assert rows["change_norm_rel"]["where"] == "d"
    prog["grad_norm"] = np.array([1, np.nan, 1, 1.0])
    bad = {r["check"]: r for r in correct.compare(prog, ref, limits)}
    assert not bad["grad_norm_rel"]["ok"]
    assert bad["grad_norm_rel"]["value"] == math.inf

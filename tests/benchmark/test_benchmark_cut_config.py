"""A configuration that is cut to one chip's share, whose block is not the
dense one and whose attention calls differ by layer, comes as new files
and manifest entries alone: the rules accept it and refuse what breaks a
floor, its counts are found by name, a kernel's count may be a list of
kinds of call, and the four accepted cells count what they counted."""

import copy
import json
import os
import types

import pytest

import manifest_rules
from manifest_rules import Refused
from tinybench import ROOT, tiny_cut_config

from benchmark import generator, harness, kernel_counts
from benchmark.trace import program

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ------------------------------------------- today's cells count the same

# flops_per_token and each flash kernel's least seconds a call on the TPU
# v5e's peaks, as PR 27's tree computed them (printed there with repr):
# what model.mfu_pct and kernels.flash_roofline_pct multiply by
PINNED = {
    "bert_large_s512_1chip": {
        "flops_per_token": 1991136600.0,
        "bps_flash_fwd": 0.0003488298311472081,
        "bps_flash_bwd_fused": 0.0008720745778680203,
        "bps_flash_bwd_dq": 0.0005232447467208122,
        "bps_flash_bwd_dkv": 0.0006976596622944162},
    "gpt2_medium_s1024_1chip": {
        "flops_per_token": 2271559194.0,
        "bps_flash_fwd": 8.729262131979695e-05,
        "bps_flash_bwd_fused": 0.0002182315532994924,
        "bps_flash_bwd_dq": 0.00013093893197969544,
        "bps_flash_bwd_dkv": 0.0001745852426395939},
    "bert_large_s512_dp4": {
        "flops_per_token": 1991136600.0,
        "bps_flash_fwd": 0.0003488298311472081,
        "bps_flash_bwd_fused": 0.0008720745778680203,
        "bps_flash_bwd_dq": 0.0005232447467208122,
        "bps_flash_bwd_dkv": 0.0006976596622944162},
    "bert_large_s128_1chip": {
        "flops_per_token": 1877524128.0,
        "bps_flash_fwd": 0.0003303206446886447,
        "bps_flash_bwd_fused": 0.0005761406593406594,
        "bps_flash_bwd_dq": 0.0004148212747252747,
        "bps_flash_bwd_dkv": 0.0004967612796092796},
}


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_an_accepted_cell_counts_what_it_counted(workload):
    """Through the names (``harness.named_count``), to the last digit."""
    cell = harness.load_cell(ROOT, workload)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    counts = harness.named_count(cell, "kernel_counts")(
        cell.config["sizes"], cell.mix)
    got = {"flops_per_token": harness.flops_per_token(cell),
           **{k: kernel_counts.least_seconds(c, peaks)[0]
              for k, c in counts.items()}}
    assert got == PINNED[workload]


# ------------------------------------- what a call's least work depends on

@pytest.mark.parametrize("kernel", sorted(kernel_counts.KERNELS))
def test_a_window_counts_the_band_and_the_triangle_before_it(kernel):
    """seq 8, window 3, by hand: rows 1, 2, 3 see 1, 2, 3 keys and the
    five rows past the band 3 each: 21 pairs = 3*8 - 3*2/2."""
    s, w, b, h, d = 8, 3, 2, 4, 128
    pairs = sum(min(i + 1, w) for i in range(s))
    assert pairs == 21 == w * s - w * (w - 1) // 2
    products = kernel_counts.KERNELS[kernel][0]
    banded = kernel_counts.flash_call(kernel, b, h, s, d, True, window=w)
    assert banded["flops"] == products * 2.0 * b * h * pairs * d
    whole = kernel_counts.flash_call(kernel, b, h, s, d, True)
    assert whole["flops"] == products * 2.0 * b * h * (s * (s + 1) / 2) * d
    # the bytes are the tensors': a band moves what the triangle moves
    assert banded["bytes"] == whole["bytes"]
    # a band as long as the row is the triangle
    assert kernel_counts.flash_call(kernel, b, h, s, d, True, window=s) \
        == whole
    with pytest.raises(ValueError, match="causal band"):
        kernel_counts.flash_call(kernel, b, h, s, d, False, window=w)


@pytest.mark.parametrize("kernel,q_side,kv_side,stats", [
    ("bps_flash_fwd", 2, 2, 1), ("bps_flash_bwd_fused", 3, 4, 1),
    ("bps_flash_bwd_dq", 3, 2, 2), ("bps_flash_bwd_dkv", 2, 4, 2)])
def test_grouped_kv_heads_move_k_and_v_once_a_kv_head(kernel, q_side,
                                                      kv_side, stats):
    """32 query heads on 4 kv heads of 128, 2 x 1024, bfloat16: q, out,
    do, dq a query head; k, v, dk, dv a kv head; the statistics a row."""
    b, h, kv, s, d = 2, 32, 4, 1024, 128
    grouped = kernel_counts.flash_call(kernel, b, h, s, d, True, kv_heads=kv)
    assert grouped["bytes"] == (q_side * b * h * s * d * 2
                                + kv_side * b * kv * s * d * 2
                                + stats * b * h * s * 4)
    alike = kernel_counts.flash_call(kernel, b, h, s, d, True)
    assert grouped["flops"] == alike["flops"]       # the products are q's
    assert alike == kernel_counts.flash_call(kernel, b, h, s, d, True,
                                             kv_heads=h)
    assert alike["bytes"] - grouped["bytes"] == \
        kv_side * b * (h - kv) * s * d * 2


# ---------------------------------------------- a count is found by name

def _cell(tiny_root, workload="tiny_cut_cell"):
    return harness.load_cell(tiny_root, workload)


def test_counts_are_found_by_the_name_the_configuration_gives(tiny_root):
    dense, cut = _cell(tiny_root, "tiny_lm_cell"), _cell(tiny_root)
    # a name of the built-in table; no kernel_counts key: the dense count
    assert harness.named_count(dense, "flops_rule") is \
        harness.COUNTS["transformer_lm"]
    assert harness.named_count(dense, "kernel_counts") is \
        kernel_counts.of_cell
    # module:function of a file under the manifest's paths
    rule = harness.named_count(cut, "flops_rule")
    assert rule.__module__ == "benchmark_count_tinybench_counts"
    assert harness.flops_per_token(cut) == harness.COUNTS["transformer_lm"](
        cut.config["sizes"], cut.mix["seq"],
        generator.targets_per_row(cut.mix)) > 0
    counts = harness.named_count(cut, "kernel_counts")(cut.config["sizes"],
                                                       cut.mix)
    assert set(counts) == set(kernel_counts.KERNELS)
    banded, whole = counts["bps_flash_fwd"]
    assert (banded["calls"], whole["calls"]) == (3, 1)
    assert banded["flops"] < whole["flops"]
    assert banded["bytes"] == whole["bytes"]


@pytest.mark.parametrize("name", [
    "benchmark.kernel_counts:flash_call",   # the repo's, outside these paths
    "tinybench.no_such_file:of_cell", "tinybench.counts",
    "tinybench.counts:no_such_function", "tinybench.counts:WINDOW",
    "no_such_rule",
    "tinybench/../../benchmark/kernel_counts:of_cell", None])
def test_a_name_that_is_no_count_of_the_benchmark_is_an_error(tiny_root,
                                                             name):
    cell = _cell(tiny_root)
    cell.config["flops_rule"] = name
    with pytest.raises(ValueError, match="under the manifest's paths"):
        harness.named_count(cell, "flops_rule")


# ------------------------------------------- a count that is a list

@pytest.fixture(scope="module", params=[1, 4], ids=["1chip", "4chip"])
def recorded(request):
    """The tiny MLM cell recorded on the v5e (PR 25): two layers, so four
    calls of the forward kernel and two of the fused backward a step."""
    return program.load_fixture(os.path.join(
        FIXTURES, f"tiny_trace_{request.param}chip_scopes.json.gz"))


RECORDED_SIZES = {"hidden": 128, "heads": 2, "causal": False}
RECORDED_MIX = {"batch_per_chip": 8, "seq": 128}


def test_a_list_of_alike_kinds_reads_what_one_count_reads(recorded):
    one = kernel_counts.of_cell(RECORDED_SIZES, RECORDED_MIX)
    listed = {k: [dict(c, calls=1), dict(c, calls=1)] for k, c in one.items()}
    want = program.roofline(recorded.by_kernel, one, PEAKS, recorded.steps)
    got = program.roofline(recorded.by_kernel, listed, PEAKS, recorded.steps)
    assert set(got) == set(want) == {"bps_flash_fwd", "bps_flash_bwd_fused",
                                     "all"}
    for k in want:
        assert got[k]["pct"] == pytest.approx(want[k]["pct"], rel=1e-12)
        assert got[k].get("bound") == want[k].get("bound")


def test_a_list_of_two_kinds_is_summed_and_scaled_to_the_steps_calls():
    """By hand: a step calls the kernel 8 times; the list holds a kind of
    1 ms called 3 times and one of 3 ms called once, so a step's least is
    2 x (3 x 1 + 1 x 3) = 12 ms. 10 steps took 240 ms: 50 %."""
    counts = {"bps_flash_fwd": [
        {"flops": 197e12 * 1e-3, "bytes": 1.0, "calls": 3},
        {"flops": 1.0, "bytes": 819e9 * 3e-3, "calls": 1}]}
    shares = program.roofline({"bps_flash_fwd": (240e6, 80)}, counts, PEAKS,
                              steps=10)
    assert shares["bps_flash_fwd"] == {"pct": pytest.approx(50.0),
                                       "bound": "mixed"}
    assert shares["all"]["pct"] == pytest.approx(50.0)


@pytest.mark.parametrize("calls,steps", [(70, 10), (81, 10), (40, 10)],
                         ids=["seven_a_step", "no_whole_calls_a_step",
                              "one_period_of_two"])
def test_a_list_that_does_not_fit_the_steps_calls_reads_nothing(calls, steps):
    """A list of 8 calls against a step of 7, of 8.1, or of 4: no share of
    that kernel and none of all together, not a guess."""
    counts = {"bps_flash_fwd": [{"flops": 1e9, "bytes": 1.0, "calls": 6},
                                {"flops": 2e9, "bytes": 1.0, "calls": 2}],
              "bps_flash_bwd_fused": {"flops": 1e9, "bytes": 1.0}}
    shares = program.roofline({"bps_flash_fwd": (1e6, calls),
                               "bps_flash_bwd_fused": (1e6, 10)}, counts,
                              PEAKS, steps)
    assert set(shares) == {"bps_flash_bwd_fused"}


def test_the_reader_leaves_the_metric_out_where_the_list_does_not_fit(
        monkeypatch, recorded, tiny_root):
    """The tiny cut configuration's list holds four calls of each kernel a
    step; the recorded step makes four of the forward's and two of the
    backward's, so ``kernels.flash_roofline_pct`` reads nothing."""
    monkeypatch.setattr(program, "of_run", lambda run: recorded)
    reader = harness.load_metric("kernels.flash_roofline_pct",
                                 [os.path.join(ROOT, "benchmark")])
    run = types.SimpleNamespace(cell=_cell(tiny_root), chips=[object()],
                                peaks=PEAKS)
    assert reader.read(run) is None
    shares = program.flash_roofline(recorded, run.cell, PEAKS)
    assert set(shares) == {"bps_flash_fwd"}
    assert 0 < shares["bps_flash_fwd"]["pct"] < 100


# -------------------------------------- the rules, on a cut configuration

def _entry(doc):
    return {"reduced": list(doc["reduced"])}


def test_the_rules_accept_the_tiny_cut_configuration(tiny_root):
    """The same functions the real manifest's tests call, on the files the
    tiny benchmark wrote."""
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    dirs = [os.path.join(tiny_root, p) for p in manifest["paths"]]
    for entry in manifest["configs"]:
        with open(os.path.join(tiny_root, entry["file"])) as f:
            doc = json.load(f)
        manifest_rules.config_file(doc, entry, dirs)
        if entry["name"] == "tiny_cut":
            assert entry["reduced"] == ["n_layer", "vocab_size"]
            manifest_rules.published_sizes(doc)


def _sparse(doc):
    """The tiny cut file made to look like one chip of eight of a sparse
    model: two leading dense layers and eight periods of three sliding
    layers and a full one, of which one dense layer and one period run;
    128 experts, 16 held; 32 heads of which 4 are held."""
    doc["published"].update(n_layer=34, num_experts=128, n_head=32)
    doc["published_to_sizes"]["num_experts"] = {"sizes": "experts",
                                                "kind": "experts_held"}
    doc["sizes"].update(layers=5, experts=16)
    doc["program"]["config_kwargs"]["layers"] = doc["n_layer"] = 5
    doc["reduced"] = ["n_layer", "vocab_size", "num_experts", "n_head"]
    period = ["sliding", "sliding", "sliding", "full"]
    doc["layer_pattern"] = {"published": ["dense"] * 2 + period * 8,
                            "period": 4, "leading_dense": 2,
                            "run": ["dense"] + period}


def test_the_rules_accept_one_chips_share_of_a_sparse_model():
    doc = tiny_cut_config()
    _sparse(doc)
    manifest_rules.published_sizes(doc)
    doc["layer_pattern"]["run"] = ["dense"] * 2 + doc["layer_pattern"][
        "run"][1:] * 2
    doc["sizes"]["layers"] = doc["n_layer"] = 10
    manifest_rules.published_sizes(doc)     # both dense layers, two periods


def _a_width(doc):
    doc["reduced"].append("n_embd")
    doc["sizes"]["hidden"] = 32


def _a_width_under_another_kind(doc):
    _a_width(doc)
    doc["published_to_sizes"]["n_embd"]["kind"] = "heads_held"


def _positions(doc):
    doc["reduced"].append("n_positions")
    doc["sizes"]["max_seq"] = 32


def _runs_at_its_published_value(doc):
    doc["sizes"]["layers"] = doc["n_layer"] = 8


def _not_in_reduced_and_cut(doc):
    doc["reduced"].remove("vocab_size")


def _seven_experts(doc):
    _sparse(doc)
    doc["published"]["num_experts"], doc["sizes"]["experts"] = 56, 7


def _experts_of_another_deployment(doc):
    _sparse(doc)
    doc["sizes"]["experts"] = 32        # a quarter, where eight chips share


def _a_tenth_of_the_vocabulary(doc):
    doc["published"]["vocab_size"] = 5120


def _three_layers_after_a_dense_one(doc):
    _sparse(doc)
    doc["layer_pattern"]["run"] = ["dense", "sliding", "sliding", "full"]
    doc["sizes"]["layers"] = doc["n_layer"] = 4


def _half_a_period(doc):
    _sparse(doc)
    doc["layer_pattern"]["run"] = ["dense"] + ["sliding"] * 3 + ["full"] \
        + ["sliding"] * 2
    doc["sizes"]["layers"] = doc["n_layer"] = 7


def _no_dense_layer(doc):
    _sparse(doc)
    doc["layer_pattern"]["run"] = ["sliding"] * 3 + ["full"]
    doc["sizes"]["layers"] = doc["n_layer"] = 4


def _three_layers_of_one_kind(doc):
    doc["sizes"]["layers"] = doc["n_layer"] = 3


def _no_deployment(doc):
    del doc["deployment"]


def _top_level_copy_unlike_the_run(doc):
    doc["vocab_size"] = 4096


def _ids(cases):
    return [breakage.__name__.strip("_") for breakage, _ in cases]


BROKEN_CUTS = [
    (_a_width, "'n_embd' .width. is in reduced: no width"),
    (_a_width_under_another_kind, "'n_embd' .heads_held. is in reduced: no "
                                  "width"),
    (_positions, "'n_positions' .positions. is in reduced"),
    (_runs_at_its_published_value, "'n_layer' is in reduced and runs at its "
                                   "published value 8"),
    (_not_in_reduced_and_cut, "'vocab_size' runs at 512, is published as "
                              "4096 and is not in reduced"),
    (_seven_experts, "7 experts held, at least 8"),
    (_experts_of_another_deployment, "32 experts held are not 128 over 8"),
    (_a_tenth_of_the_vocabulary, "512 rows are under one part in 8 of 5120"),
    (_three_layers_after_a_dense_one, "3 layers after the dense ones: at "
                                      "least 4"),
    (_half_a_period, "6 layers after the dense ones: at least 4, in whole "
                     "periods of 4"),
    (_no_dense_layer, "leading dense layers count once"),
    (_three_layers_of_one_kind, "3 layers run, at least 4"),
    (_no_deployment, "states its deployment"),
    (_top_level_copy_unlike_the_run, "'vocab_size' is 4096 at the file's "
                                     "top level and runs at 512"),
]


@pytest.mark.parametrize("breakage,reason", BROKEN_CUTS,
                         ids=_ids(BROKEN_CUTS))
def test_a_cut_that_breaks_a_rule_is_refused(breakage, reason):
    doc = tiny_cut_config()
    manifest_rules.published_sizes(copy.deepcopy(doc))      # sound before
    breakage(doc)
    with pytest.raises(Refused, match=reason):
        manifest_rules.published_sizes(doc)


def _reduced_unlike_the_files(doc, entry):
    entry["reduced"] = ["n_layer"]


def _a_dotted_name_outside_paths(doc, entry):
    doc["kernel_counts"] = "benchmark.kernel_counts:flash_call"


def _kwargs_unlike_the_sizes(doc, entry):
    doc["program"]["config_kwargs"]["layers"] = 2


def _no_flops_rule(doc, entry):
    del doc["flops_rule"]


BROKEN_FILES = [
    (_reduced_unlike_the_files, "reduced in the manifest .'n_layer'. is "
                                "unlike the file's"),
    (_a_dotted_name_outside_paths, "kernel_counts 'benchmark.kernel_counts:"
                                   "flash_call' is neither"),
    (_kwargs_unlike_the_sizes, "config_kwargs.'layers'. is 2, "
                               "sizes.'layers'. 4"),
    (_no_flops_rule, "flops_rule None is neither"),
]


@pytest.mark.parametrize("breakage,reason", BROKEN_FILES,
                         ids=_ids(BROKEN_FILES))
def test_a_file_unlike_its_entry_or_itself_is_refused(tiny_root, breakage,
                                                      reason):
    doc = tiny_cut_config()
    entry, dirs = _entry(doc), [os.path.join(tiny_root, "tinybench")]
    manifest_rules.config_file(doc, entry, dirs)            # sound before
    breakage(doc, entry)
    with pytest.raises(Refused, match=reason):
        manifest_rules.config_file(doc, entry, dirs)

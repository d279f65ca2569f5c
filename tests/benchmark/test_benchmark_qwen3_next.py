"""The configuration ``qwen3_next_80b_a3b_lm`` and its cell as the
benchmark holds them: the file against the source it names and against
``manifest_rules``, the share's parameters counted from the program's own
``init_params``, its counts counted by hand at a tiny size, its three
per-layer readers on a trace made by hand, the harness running a tiny
cell of the same family on the CPU with nothing under ``benchmark/``
edited, and the reference ending at import where the program has no
Gated DeltaNet."""

import importlib.util
import json
import os
import shutil
import time
import types

import jax
import pytest

from tinybench import (OPTIMIZER, ROOT, ROUTED_CELLS, ROUTED_METRICS, TIGHT,
                       write_tiny_benchmark)

import manifest_rules as rules
from benchmark import counts_afmoe, counts_qwen3_next as counts, harness
from benchmark import kernel_counts
from benchmark.trace import program

CELL = "qwen3_next_80b_s8192_1chip"
CONFIG = "qwen3_next_80b_a3b_lm"
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("model.gdn_ms", "model.gdn_scan_ms",
               "model.gdn_scan_roofline_pct")
KINDS = ["gdn_moe"] * 3 + ["gattn_moe"]


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(ROOT, CELL)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the file

def test_the_file_runs_the_published_numbers_but_for_the_share(cell):
    doc = cell.config
    assert doc["reduced"] == list(REDUCED)
    for key, value in doc["published"].items():
        assert doc[key] == REDUCED.get(key, value), key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert doc["published"] == row["config"]
    assert doc["source"].startswith(row["source_url"])
    d = doc["deployment"]
    assert (d["chips_sharing_a_layer"], d["experts_held"],
            d["query_heads_held"], d["kv_heads_held"],
            d["linear_key_heads_held"], d["linear_value_heads_held"],
            d["vocab_rows_held"], d["layers_run"]) == (
                32, 16, 16, 2, 16, 32, 18992, 4)
    sizes = doc["sizes"]
    assert sizes["held"] == list(range(16)) and sizes["router_outputs"] == 512
    assert (sizes["hidden"], sizes["heads"], sizes["kv_heads"],
            sizes["head_dim"], sizes["rotary_dim"]) == (2048, 16, 2, 256, 64)
    assert sizes["rotary_dim"] == sizes["partial_rotary_factor"] * sizes[
        "head_dim"]
    assert (sizes["gdn_key_heads"], sizes["gdn_value_heads"],
            sizes["gdn_head_dim"], sizes["conv_kernel"]) == (16, 32, 128, 4)
    assert (sizes["mlp_dim"], sizes["moe_dim"], sizes["shared_dim"],
            sizes["top_k"], sizes["route_scale"], sizes["rope_theta"],
            sizes["norm_eps"], sizes["max_seq"]) == (
                5120, 512, 512, 10, 1.0, 1e7, 1e-6, 262144)
    assert sizes["chunk"] == 128     # whole lane tiles: the kernels' chunk
    assert sizes["layer_kinds"] == doc["layer_pattern"]["run"] == KINDS
    pub = doc["published"]
    assert doc["layer_pattern"]["published"] == [
        "gattn_moe" if (i + 1) % pub["full_attention_interval"] == 0
        else "gdn_moe" for i in range(pub["num_hidden_layers"])]
    assert (doc["layer_pattern"]["period"],
            doc["layer_pattern"]["leading_dense"]) == (4, 0)
    assert cell.mix == {**cell.mix, "kind": "lm", "batch_per_chip": 2,
                        "seq": 8192, "reference_rows_per_block": 1}
    assert doc["assumed"]
    # every limit with the readings it stands on: the bf16 program's band
    # over its seeds and the float8 control's, read on the chip
    note = doc["limits_set_from"]
    assert "PR 54" in note and "sound" in note and "float8" in note
    for name in doc["limits"]:
        assert name in note, name
    # what described_as names and no key of config carries is a departure
    assert any("multi-token-prediction" in d for d in doc["departures"])


def test_the_file_keeps_the_manifests_rules(cell, manifest):
    doc = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    rules.config_file(doc, entry, cell.dirs)
    rules.published_sizes(doc)
    for width in ("hidden_size", "head_dim", "linear_key_head_dim",
                  "linear_value_head_dim", "linear_conv_kernel_dim",
                  "moe_intermediate_size", "partial_rotary_factor",
                  "shared_expert_intermediate_size", "num_experts_per_tok",
                  "max_position_embeddings"):
        with pytest.raises(rules.Refused):
            rules.published_sizes(dict(doc, reduced=doc["reduced"] + [width]))
    # every head count runs whole, so none is in reduced; one whole period
    # of four is the floor: three layers, or four that are no period, are
    # refused
    for key, size in (("num_attention_heads", "heads"),
                      ("num_key_value_heads", "kv_heads"),
                      ("linear_num_key_heads", "gdn_key_heads"),
                      ("linear_num_value_heads", "gdn_value_heads")):
        assert doc["sizes"][size] == doc["published"][key]
    short = dict(doc, sizes=dict(doc["sizes"], layers=3),
                 num_hidden_layers=3,
                 layer_pattern=dict(doc["layer_pattern"], run=KINDS[:3]))
    with pytest.raises(rules.Refused, match="whole periods"):
        rules.published_sizes(short)
    other = dict(doc, layer_pattern=dict(doc["layer_pattern"],
                                         run=["gdn_moe"] * 4))
    with pytest.raises(rules.Refused, match="whole periods"):
        rules.published_sizes(other)
    with pytest.raises(rules.Refused, match="512 over 16 chips"):
        rules.published_sizes(dict(doc, deployment=dict(
            doc["deployment"], chips_sharing_a_layer=16)))


def test_the_share_is_424_million_parameters(cell):
    """ISSUE 54's reckoning, from the program's own ``init_params``: a
    Gated DeltaNet mixer 33.72 M, a full-attention mixer 27.26 M, a routed
    half 54.53 M (4.20 outside its 16 experts of 3.146 M), embedding and
    head 38.90 M each; the reference's tree is the same tree."""
    from byteps_tpu.models import decoder
    from benchmark.reference import qwen3_next_share as ref
    cfg, _, _ = harness.build_program(cell)
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    linear, full = tree["layers"][0], tree["layers"][3]
    norm = 2048
    assert round((count(linear["attn"]) - norm) / 1e6, 2) == 33.72
    assert round((count(full["attn"]) - norm) / 1e6, 2) == 27.26
    assert round(count(linear["ffn"]["experts"]) / 16e6, 3) == 3.146
    assert round((count(linear["ffn"]) - norm
                  - count(linear["ffn"]["experts"])) / 1e6, 2) == 4.20
    assert round(count(tree["embed"]) / 1e6, 2) == 38.90
    assert round(sum(count(layer) - count(layer["ffn"]["experts"])
                     for layer in tree["layers"]) / 1e6, 2) == 145.22
    assert round(count(tree) / 1e6, 1) == 424.3
    assert "424.3 M" in cell.config["deployment"]["parameters"]
    theirs = jax.eval_shape(lambda: ref.make_params(0, cell.config["sizes"]))
    assert jax.tree_util.tree_structure(theirs) == (
        jax.tree_util.tree_structure(tree))
    assert [x.shape for x in jax.tree_util.tree_leaves(theirs)] == [
        x.shape for x in jax.tree_util.tree_leaves(tree)]


def test_the_program_is_built_from_the_files_sizes(cell):
    cfg, _, _ = harness.build_program(cell)
    assert cfg.layer_kinds == tuple(KINDS)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim,
            cfg.rotary_dim) == (2048, 16, 2, 256, 64)
    assert (cfg.gdn.key_heads, cfg.gdn.value_heads, cfg.gdn.head_dim,
            cfg.gdn.conv_kernel, cfg.gdn.chunk) == (
                16, 32, 128, 4, cell.config["sizes"]["chunk"])
    assert (cfg.gdn.key_dim, cfg.gdn.value_dim, cfg.gdn.conv_dim) == (
        2048, 4096, 8192)
    assert cfg.routed.held == tuple(range(16))
    assert (cfg.routed.num_experts, cfg.routed.top_k, cfg.routed.route_scale,
            cfg.routed.act, cfg.routed.score, cfg.moe_dim,
            cfg.routed.shared_dim) == (512, 10, 1.0, "gated_silu", "softmax",
                                       512, 512)
    assert cfg.routed.balanced and cfg.zero_centred
    assert not cfg.scale_embedding
    assert (cfg.rope_theta, cfg.norm_eps, cfg.max_seq) == (1e7, 1e-6, 262144)
    assert cfg.lm_head_chunk == 2048 and cfg.dtype == "bfloat16"
    # a silent fall-back of any kernel the step runs fails correct
    assert {"tpu_custom_call", "bps_flash_fwd", "bps_flash_bwd_dq",
            "bps_flash_bwd_dkv", "bps_gmm", "bps_gmm_dx", "bps_gmm_dw",
            "bps_moe_take", "bps_moe_combine", "bps_moe_act_fwd",
            "bps_moe_act_bwd", "bps_embed_dw", "bps_ssm_conv_fwd",
            "bps_ssm_conv_bwd", "bps_ssm_norm_fwd", "bps_ssm_norm_bwd",
            "bps_gdn_inverse", "bps_gdn_inverse_bwd", "bps_gdn_fwd",
            "bps_gdn_bwd"} == set(
                cell.config["program"]["step_must_contain"])


# ---------------------------------------------------------- the counts

TINY = dict(hidden=4, heads=2, kv_heads=1, head_dim=2, moe_dim=2,
            shared_dim=3, top_k=2, router_outputs=8, experts_held=4,
            gdn_key_heads=1, gdn_value_heads=2, gdn_head_dim=2,
            vocab_size=16, layer_kinds=["gdn_moe", "gattn_moe"])


def test_required_operations_counted_by_hand():
    """seq 4, the triangle 2.5 keys. The routed half: router 2*4*8 = 64,
    shared 6*4*3 = 72 and its gate 2*4 = 8, routed 6*4*2 x 2*4/8 = 48:
    192. Gated DeltaNet: in_proj_qkvz 2*4*(2*2 + 2*4) = 96, in_proj_ba
    2*4*4 = 32, out_proj 2*4*4 = 32, the rule 6*2*2*2 = 48: 208. Gated
    attention: q, gate and o 3 x 2*4*4 = 96, k and v 2 x 2*4*2 = 32,
    scores and values 4*2*2*2.5 = 40: 168. Head on 3 of 4 positions:
    2*4*16*3/4 = 96. Times 3."""
    assert counts.delta_flops_per_token(TINY) == 48
    assert counts.routed_forward(TINY) == 192
    assert counts.layer_forward(TINY, "gdn_moe", 4) == 208 + 192
    assert counts.layer_forward(TINY, "gattn_moe", 4) == 168 + 192
    assert counts.flops_per_token(TINY, 4, 3) == 3 * (400 + 360 + 96)
    with pytest.raises(ValueError):
        counts.layer_forward(TINY, "moe_full", 4)


def test_the_kinds_of_call_in_a_step_and_the_delta_rules_count():
    mix = {"batch_per_chip": 2, "seq": 4}
    got = counts.kernel_counts(TINY, mix)
    assert set(got) == set(kernel_counts.KERNELS) | set(
        counts_afmoe.GMM_KERNELS)
    assert got["bps_flash_bwd_dq"] == [dict(kernel_counts.flash_call(
        "bps_flash_bwd_dq", 2, 2, 4, 2, True, kv_heads=1), calls=1)]
    rows = 2 * 4 * 1            # one routed row a token on average
    assert got["bps_gmm_dw"] == [
        dict(counts_afmoe.gmm_call("bps_gmm_dw", rows, 4, 4, 4), calls=1),
        dict(counts_afmoe.gmm_call("bps_gmm_dw", rows, 2, 4, 4), calls=1)]
    # one Gated DeltaNet layer, 8 tokens: 3 x 48 operations a token; q, k
    # (2 lanes each), v and o (4 each) in 2 bytes, g and beta (2 heads) in
    # 4: 16 + 16 in, 8 out forward; in, o's cotangent, and five cotangents
    # out backward
    assert counts.delta_count(TINY, mix) == {
        "flops": 3.0 * 48 * 8,
        "bytes": float(((32 + 8) + (32 + 8 + 32)) * 8)}


def test_the_cells_count(cell):
    """ISSUE 54: 0.453 GFLOP a token forward, 1.36 GFLOP required: the
    three Gated DeltaNet mixers 47 % (their recurrence 2 %), full
    attention 27 %, the four routed halves 9 %, the head 17 %; one
    triangle call of each flash kernel a step at 16 heads of 256 over 2,
    flops-bound; the grouped products at [2048, 1024] and [512, 2048],
    5,120 mean rows a step."""
    per_token = harness.flops_per_token(cell)
    assert round(per_token / 3e6) == 452 and round(per_token / 1e7) == 136
    z = cell.config["sizes"]
    routed = 3 * 4 * counts.routed_forward(z)
    mixers = 3 * 3 * (counts.layer_forward(z, "gdn_moe", 8192)
                      - counts.routed_forward(z))
    rule = 3 * 3 * counts.delta_flops_per_token(z)
    attention = 3 * (counts.layer_forward(z, "gattn_moe", 8192)
                     - counts.routed_forward(z))
    head = 3 * 2 * 2048 * 18992 * 8191 / 8192
    assert mixers + attention + routed + head == pytest.approx(per_token)
    assert [round(100 * x / per_token) for x in (
        mixers, rule, attention, routed, head)] == [47, 2, 27, 9, 17]
    assert counts_afmoe.routed_rows_per_token(z) == 10 * 16 / 512
    calls = harness.named_count(cell, "kernel_counts")(z, cell.mix)
    for kernel in ("bps_flash_fwd", "bps_flash_bwd_dq", "bps_flash_bwd_dkv"):
        (kind,) = calls[kernel]
        assert kind["calls"] == 1
        assert kernel_counts.least_seconds(kind, PEAKS)[1] == "flops"
    assert calls["bps_flash_fwd"][0]["flops"] == (
        2 * 2.0 * 2 * 16 * 8192 * 4096.5 * 256)
    assert [c["flops"] for c in calls["bps_gmm"]] == [
        2.0 * 5120 * 2048 * 1024, 2.0 * 5120 * 512 * 2048]
    delta = harness.named_count(cell, "delta_count")(z, cell.mix)
    assert delta["flops"] == 3 * 3 * 6.0 * 32 * 128 * 128 * 16384
    # HBM-bound: 3.9 ms a step is the least the three rules can take
    least, bound = kernel_counts.least_seconds(delta, PEAKS)
    assert bound == "hbm" and 3.5e-3 < least < 4.5e-3


# --------------------------------------------------------- the readers

def _trace(steps=2):
    """A trace made by hand: a step runs one Gated DeltaNet half forward
    and backward (projections, convolution, the rule, the norm) and a
    routed half; times in ns."""
    ops, t = [], 0.0

    def op(name, path, ns):
        nonlocal t
        ops.append((name, path, t, t + ns))
        t += ns

    fwd = "jit(step)/bps.model/jvp(bps.gdn)/"
    bwd = ("jit(step)/bps.model/transpose(jvp(bps.model))/jvp()/checkpoint/"
           "bps.gdn/")
    for _ in range(steps):
        op("%fusion.1 = norm", fwd + "rsqrt", 5e5)
        op("%fusion.2 = dot", fwd + "bps.gdn.proj/dot_general", 4e6)
        op("%bps_ssm_conv_fwd.1 = custom-call",
           fwd + "bps.gdn.conv/bps_ssm_conv_fwd/pallas_call", 1e6)
        op("%fusion.3 = exp", fwd + "bps.gdn.scan/exp", 5e5)
        op("%fusion.4 = dot", fwd + "bps.gdn.scan/bps_gdn_xla/dot_general",
           6e6)
        op("%while.1 = while", fwd + "bps.gdn.scan/bps_gdn_xla/while", 4e6)
        op("%bps_ssm_norm_fwd.1 = custom-call",
           fwd + "bps.gdn.norm/bps_ssm_norm_fwd/pallas_call", 1.5e6)
        op("%fusion.5 = dot", bwd + "bps.gdn.scan/bps_gdn_xla/dot_general",
           1.2e7)
        op("%fusion.6 = dot", bwd + "bps.gdn.proj/dot_general", 8e6)
        op("%fusion.7 = dot", "jit(step)/bps.model/jvp(bps.mlp)/bps.moe/dot",
           7e6)
    return program.Program("/device:TPU:0", (0.0, t), steps, ops, [], [],
                           "tf_op")


def test_the_readers_on_the_handmade_trace(cell, monkeypatch):
    run = types.SimpleNamespace(cell=cell, peaks=PEAKS, chips=[object()])
    read = {m: harness.load_metric(m, cell.dirs).read for m in NEW_METRICS}
    monkeypatch.setattr(program, "of_run", lambda run: _trace())
    assert read["model.gdn_ms"](run) == (0.5 + 4 + 1 + 0.5 + 6 + 4 + 1.5
                                         + 12 + 8)
    assert read["model.gdn_scan_ms"](run) == 0.5 + 6 + 4 + 12
    delta = counts.delta_count(cell.config["sizes"], cell.mix)
    least = delta["bytes"] / PEAKS["hbm_bytes_per_s"]
    share = read["model.gdn_scan_roofline_pct"](run)
    assert share == pytest.approx(100 * least / 22.5e-3)
    assert 0 < share < 100
    # no peaks (off the chip), or a configuration that names no count
    assert read["model.gdn_scan_roofline_pct"](
        types.SimpleNamespace(cell=cell, peaks=None, chips=[])) is None
    bare_cell = types.SimpleNamespace(
        config={k: v for k, v in cell.config.items() if k != "delta_count"},
        mix=cell.mix, dirs=cell.dirs)
    assert read["model.gdn_scan_roofline_pct"](types.SimpleNamespace(
        cell=bare_cell, peaks=PEAKS, chips=[object()])) is None
    # a program from before the scopes reports nothing, and does not raise
    bare = _trace()
    bare.ops = [(n, p.replace("bps.gdn", "x"), s, e)
                for n, p, s, e in bare.ops]
    monkeypatch.setattr(program, "of_run", lambda run: bare)
    assert [r(run) for r in read.values()] == [None, None, None]
    monkeypatch.setattr(program, "of_run", lambda run: None)
    assert [r(run) for r in read.values()] == [None, None, None]


def test_the_new_entries_are_the_new_cells_alone(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    mine = [by_name[name] for name in NEW_METRICS]
    assert all(m["workloads"] == [CELL] for m in mine)
    assert all(m["moves"] == "tokens_per_s_chip" and m["layer"] == "model"
               and m["source"] == "device_trace" for m in mine)
    assert [(m["unit"], m["better"]) for m in mine] == [
        ("ms", "lower"), ("ms", "lower"), ("%", "higher")]
    (entry,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "lm_b2_s8192", 1)
    (config,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == list(REDUCED)
    assert config["source"].startswith(
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json") and len(config["source"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the accepted metrics that have no list reach it by themselves; the
    # routed layers' and the grouped products' LIST three cells and wait
    # for a benchmark issue to take this one; the state-space and latent
    # layers' do not reach it
    cell = harness.load_cell(ROOT, CELL)
    assert {"model.mfu_pct", "kernels.flash_fwd_ms", "kernels.flash_bwd_ms",
            "kernels.flash_roofline_pct", "kernels.fallback_sites",
            "model.head_ms", "model.fwd_ms", "model.remat_ms",
            "model.bwd_ms", *NEW_METRICS} <= set(cell.per_layer)
    assert not set(ROUTED_METRICS) & set(cell.per_layer)
    assert CELL not in ROUTED_CELLS
    assert not {"model.ssm_ms", "model.ssm_scan_ms", "model.mla_ms"} & set(
        cell.per_layer)
    # and the new ones reach no other cell
    for w in manifest["workloads"]:
        if w["name"] != CELL:
            assert not set(NEW_METRICS) & set(
                harness.load_cell(ROOT, w["name"]).per_layer)


# ------------------------------------------- the harness, on the CPU

TINY_SIZES = dict(
    vocab_size=512, hidden=64, heads=4, kv_heads=2, head_dim=16, rotary_dim=4,
    moe_dim=24, shared_dim=24, top_k=3, router_outputs=8, held=[0, 1, 2, 3],
    gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=8, conv_kernel=4,
    chunk=16, route_scale=1.0, max_seq=64, rope_theta=1e7, norm_eps=1e-6,
    balanced=True, layer_kinds=KINDS)


def _write_tiny_qwen3_next(root):
    """``write_tiny_benchmark``'s manifest plus a cell of the qwen3_next
    family cut the same way (4 of 8 experts), all new files."""
    write_tiny_benchmark(root)
    bench = os.path.join(root, "tinybench")
    shutil.copy(os.path.join(ROOT, "benchmark", "counts_qwen3_next.py"),
                bench)
    for metric in NEW_METRICS:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics",
                                 metric + ".py"),
                    os.path.join(bench, "metrics"))
    doc = {"reduced": [], "optimizer": OPTIMIZER,
           "sizes": dict(TINY_SIZES, layers=4, experts_held=4),
           "program": {
               "config": "byteps_tpu.models.decoder:qwen3_next_config",
               "config_kwargs": dict(TINY_SIZES, dtype="float32",
                                     routed_kw={"row_tile": 8},
                                     lm_head_chunk=32),
               "loss": "byteps_tpu.models.decoder:causal_lm_loss",
               "loss_kwargs": {}, "step_must_contain": ["tpu_custom_call"]},
           "reference": "benchmark.reference.qwen3_next_share",
           "flops_rule": "tinybench.counts_qwen3_next:flops_per_token",
           "kernel_counts": "tinybench.counts_qwen3_next:kernel_counts",
           "delta_count": "tinybench.counts_qwen3_next:delta_count",
           "limits": dict(TIGHT, grad_norm_rel=3e-4, change_norm_rel=3e-2)}
    with open(os.path.join(bench, "configs", "tiny_qwen3_next.json"),
              "w") as f:
        json.dump(doc, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny_qwen3_next", "source": "test", "reduced": [],
        "why": "test", "file": "tinybench/configs/tiny_qwen3_next.json"})
    manifest["workloads"].append({
        "name": "tiny_qwen3_next_cell", "config": "tiny_qwen3_next",
        "traffic": "lm_tiny", "chips": 1, "why": "test"})
    manifest["per_layer"] = [
        dict(m, workloads=["tiny_qwen3_next_cell"])
        if m["name"] in NEW_METRICS else m for m in manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return str(root)


@pytest.mark.parametrize("trace", [False, True])
def test_the_harness_runs_a_cell_of_the_family_unchanged(tmp_path, trace,
                                                         capsys):
    root = _write_tiny_qwen3_next(tmp_path)
    result = harness.run_cell(root, "tiny_qwen3_next_cell", 2**31 + 54, 0.3,
                              trace, time.time(), require_chip=False)
    assert result["correct"] is True, capsys.readouterr().out
    assert result["failed"] == 0 and result["attempted"] > 2
    assert {"loss_rel", "grad_norm_rel", "change_norm_rel",
            "compiles_in_window"} <= set(result["checks"])
    if trace:       # no device trace on the CPU: the new readers say nothing
        assert not set(NEW_METRICS) & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"tokens_per_s_chip", "step_ms_p95",
                                          "setup_s"}
    cell = harness.load_cell(root, "tiny_qwen3_next_cell")
    assert set(NEW_METRICS) <= set(cell.per_layer)
    assert harness.flops_per_token(cell) == counts.flops_per_token(
        cell.config["sizes"], 64, 63)


# ------------------------------------ where the program has no such model

def test_the_reference_ends_at_import_where_the_program_has_no_delta_net(
        monkeypatch):
    """The parent of the PR that brought the configuration, with the
    benchmark's new files laid over it: the harness imports the reference
    before it builds anything, and the import fails at once."""
    real = importlib.util.find_spec
    spec = real("benchmark.reference.qwen3_next_share")
    monkeypatch.setattr(
        importlib.util, "find_spec", lambda name, *a: (
            None if name == "byteps_tpu.models.gated_delta_net"
            else real(name, *a)))
    t0 = time.time()
    with pytest.raises(ImportError,
                       match=r"no byteps_tpu\.models\.gated_delta_net"):
        spec.loader.exec_module(importlib.util.module_from_spec(spec))
    assert time.time() - t0 < 5

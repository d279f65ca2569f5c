"""The configuration ``kanana2_30b_a3b_lm`` and its cell as the benchmark
holds them: the file against the source it names and against
``manifest_rules``, the share's parameters counted from the program's own
``init_params``, its counts counted by hand at a tiny size, its two
per-layer readers on a trace made by hand, the harness running a tiny
cell of the same family on the CPU with nothing under ``benchmark/``
edited, and the reference ending at import where the program has no
latent attention."""

import importlib.util
import json
import os
import shutil
import time
import types

import jax
import pytest

from tinybench import (OPTIMIZER, ROOT, ROUTED_METRICS, TIGHT,
                       write_tiny_benchmark)

import manifest_rules as rules
from benchmark import counts_afmoe, counts_deepseek_v3 as counts, harness
from benchmark import kernel_counts
from benchmark.trace import program

CELL = "kanana2_30b_s8192_1chip"
CONFIG = "kanana2_30b_a3b_lm"
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 16,
           "vocab_size": 16032}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("model.mla_ms", "model.mla_latent_ms")
KINDS = ["mla_dense"] + ["mla_moe"] * 4


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
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert doc["published"] == row["config"]
    assert doc["source"].startswith(row["source_url"])
    d = doc["deployment"]
    assert (d["chips_sharing_a_layer"], d["experts_held"],
            d["query_heads_held"], d["kv_heads_held"], d["vocab_rows_held"],
            d["layers_run"]) == (8, 16, 32, 32, 16032, 5)
    sizes = doc["sizes"]
    assert sizes["held"] == list(range(16)) and sizes["router_outputs"] == 128
    assert (sizes["hidden"], sizes["heads"], sizes["qk_nope_dim"],
            sizes["qk_rope_dim"], sizes["v_head_dim"],
            sizes["kv_lora_rank"]) == (2048, 32, 128, 64, 128, 512)
    assert (sizes["mlp_dim"], sizes["moe_dim"], sizes["shared_experts"],
            sizes["top_k"], sizes["route_scale"], sizes["rope_theta"],
            sizes["norm_eps"], sizes["max_seq"]) == (
                6144, 768, 2, 6, 2.448, 1e6, 1e-6, 32768)
    assert sizes["layer_kinds"] == doc["layer_pattern"]["run"] == KINDS
    pub = doc["published"]
    assert doc["layer_pattern"]["published"] == [
        "mla_dense" if i < pub["first_k_dense_replace"] else "mla_moe"
        for i in range(pub["num_hidden_layers"])]
    assert cell.mix == {**cell.mix, "kind": "lm", "batch_per_chip": 2,
                        "seq": 8192, "reference_rows_per_block": 1}
    assert doc["assumed"] and doc["departures"] and doc["limits_set_from"]


def test_the_file_keeps_the_manifests_rules(cell, manifest):
    doc = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    rules.config_file(doc, entry, cell.dirs)
    rules.published_sizes(doc)
    for width in ("kv_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
                  "v_head_dim", "hidden_size", "moe_intermediate_size",
                  "num_experts_per_tok", "max_position_embeddings"):
        with pytest.raises(rules.Refused):
            rules.published_sizes(dict(doc, reduced=doc["reduced"] + [width]))
    # the heads are run whole, so they are not in reduced; four layers
    # after the dense one is the floor, three are refused
    assert doc["sizes"]["heads"] == doc["published"]["num_attention_heads"]
    short = dict(doc, sizes=dict(doc["sizes"], layers=4),
                 num_hidden_layers=4,
                 layer_pattern=dict(doc["layer_pattern"], run=KINDS[:4]))
    with pytest.raises(rules.Refused, match="after the dense"):
        rules.published_sizes(short)


def test_the_share_is_576_million_parameters(cell):
    """ISSUE 44's reckoning, from the program's own ``init_params``:
    attention 26.35 M a layer, the dense layer 64.10 M, a routed layer
    111.55 M, embedding and head 32.83 M each; the reference's tree is the
    same tree."""
    from byteps_tpu.models import decoder
    from benchmark.reference import deepseek_v3_share as ref
    cfg, _, _ = harness.build_program(cell)
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    assert round(count(tree["layers"][0]["attn"]) / 1e6, 2) == 26.35
    assert round(count(tree["layers"][0]) / 1e6, 2) == 64.10
    assert round(count(tree["layers"][1]) / 1e6, 2) == 111.55
    assert round(count(tree["embed"]) / 1e6, 2) == 32.83
    assert round(count(tree) / 1e6, 1) == 576.0
    theirs = jax.eval_shape(lambda: ref.make_params(0, cell.config["sizes"]))
    assert jax.tree_util.tree_structure(theirs) == (
        jax.tree_util.tree_structure(tree))
    assert [x.shape for x in jax.tree_util.tree_leaves(theirs)] == [
        x.shape for x in jax.tree_util.tree_leaves(tree)]


def test_the_program_is_built_from_the_files_sizes(cell):
    cfg, _, _ = harness.build_program(cell)
    assert cfg.layer_kinds == tuple(KINDS)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim) == (
        2048, 32, 32, 192)
    assert (cfg.mla.latent, cfg.mla.nope_dim, cfg.mla.rope_dim,
            cfg.mla.v_dim) == (512, 128, 64, 128)
    assert cfg.routed.held == tuple(range(16))
    assert (cfg.routed.num_experts, cfg.routed.top_k, cfg.routed.route_scale,
            cfg.routed.act, cfg.moe_dim, cfg.shared_experts, cfg.mlp_dim) == (
                128, 6, 2.448, "gated_silu", 768, 2, 6144)
    assert cfg.routed.balanced and not cfg.scale_embedding
    assert (cfg.rope_theta, cfg.norm_eps, cfg.max_seq) == (1e6, 1e-6, 32768)
    assert cfg.lm_head_chunk == 2048 and cfg.dtype == "bfloat16"
    # a silent fall-back of any kernel the step runs fails correct
    assert {"tpu_custom_call", "bps_flash_fwd", "bps_flash_bwd_dq",
            "bps_flash_bwd_dkv", "bps_gmm", "bps_gmm_dx", "bps_gmm_dw",
            "bps_moe_take", "bps_moe_combine", "bps_moe_act_fwd",
            "bps_moe_act_bwd", "bps_embed_dw"} == set(
                cell.config["program"]["step_must_contain"])


# ---------------------------------------------------------- the counts

TINY = dict(hidden=4, heads=2, qk_nope_dim=2, qk_rope_dim=2, v_head_dim=3,
            kv_lora_rank=5, mlp_dim=3, moe_dim=2, top_k=2, router_outputs=8,
            experts_held=4, shared_experts=2, vocab_size=16,
            layer_kinds=["mla_dense", "mla_moe"])


def test_required_operations_counted_by_hand():
    """seq 4, the triangle 2.5 keys. Attention: q 2*4*2*4 = 64, the
    compression 2*4*(5 + 2) = 56, the expansion 2*5*2*(2 + 3) = 100, o
    2*2*3*4 = 48, scores and values 2*2*2.5*(4 + 3) = 70: 338. Dense:
    + 6*4*3 = 410. Routed: + router 2*4*8 + (2 shared + 2*4/8 routed) x
    6*4*2 = 546. Head on 3 of 4 positions: 2*4*16*3/4 = 96. Times 3."""
    assert counts.layer_forward(TINY, "mla_dense", 4) == 410
    assert counts.layer_forward(TINY, "mla_moe", 4) == 546
    assert counts.flops_per_token(TINY, 4, 3) == 3 * (410 + 546 + 96)
    with pytest.raises(ValueError):
        counts.layer_forward(TINY, "moe_full", 4)


@pytest.mark.parametrize("kernel,flops,moved,stats", [
    # 2 x 2 heads x 4 rows = 16 rows, 8 positions, 16 x 2.5 = 40 pairs;
    # q 64, k 16 x 2 + 8 x 2 = 48, v = out = do = dv 48 numbers
    ("bps_flash_fwd", 2 * 40 * (4 + 3), 64 + 48 + 48 + 48, 1),
    ("bps_flash_bwd_dq", 2 * 40 * (4 + 3 + 4), 64 + 48 + 48 + 48 + 64, 2),
    ("bps_flash_bwd_dkv", 2 * 40 * (4 + 3 + 3 + 4),
     64 + 48 + 48 + 48 + 48 + 48, 2),
    ("bps_flash_bwd_fused", 2 * 40 * (3 * 4 + 2 * 3),
     64 + 48 + 48 + 48 + 64 + 48 + 48, 1)])
def test_a_flash_call_at_two_widths_counted_by_hand(kernel, flops, moved,
                                                    stats):
    """The keys' rotary lanes and their gradient move once a POSITION."""
    assert counts.flash_call(kernel, 2, 2, 4, 2, 2, 3) == {
        "flops": float(flops), "bytes": float(2 * moved + stats * 16 * 4)}
    # with no rotary lane and v as wide as q it is the equal-width count
    assert counts.flash_call(kernel, 2, 2, 4, 6, 0, 6) == (
        kernel_counts.flash_call(kernel, 2, 2, 4, 6, True))


def test_the_kinds_of_call_in_a_step():
    got = counts.kernel_counts(TINY, {"batch_per_chip": 2, "seq": 4})
    assert set(got) == set(counts.FLASH_KERNELS) | set(
        counts_afmoe.GMM_KERNELS)
    assert got["bps_flash_bwd_dq"] == [dict(counts.flash_call(
        "bps_flash_bwd_dq", 2, 2, 4, 2, 2, 3), calls=2)]
    rows = 2 * 4 * 1            # one routed row a token on average
    assert got["bps_gmm_dw"] == [
        dict(counts_afmoe.gmm_call("bps_gmm_dw", rows, 4, 4, 4), calls=1),
        dict(counts_afmoe.gmm_call("bps_gmm_dw", rows, 2, 4, 4), calls=1)]


def test_the_cells_count(cell):
    """ISSUE 44: 930 MFLOP a token forward, 2.79 GFLOP required: the
    scores and weighted values 45 %, the attention's projections 28 %,
    the routed layers 11 %, the dense feed-forward 8 %, the head 7 %; five
    triangle calls of each flash kernel a step, flops-bound."""
    per_token = harness.flops_per_token(cell)
    assert round(per_token / 3e6) == 930 and round(per_token / 1e7) == 279
    z = cell.config["sizes"]
    scores = 3 * 5 * 2 * 32 * 4096.5 * (192 + 128)
    attention = 3 * 5 * (counts.layer_forward(z, "mla_dense", 8192)
                         - 6 * 2048 * 6144) - scores
    routed = 3 * 4 * (counts.layer_forward(z, "mla_moe", 8192)
                      - counts.layer_forward(z, "mla_dense", 8192)
                      + 6 * 2048 * 6144)
    head = 3 * 2 * 2048 * 16032 * 8191 / 8192
    assert [round(100 * x / per_token) for x in (
        scores, attention, routed, 3 * 6 * 2048 * 6144, head)] == [
            45, 28, 11, 8, 7]
    flash = harness.named_count(cell, "kernel_counts")(z, cell.mix)
    for kernel in ("bps_flash_fwd", "bps_flash_bwd_dq", "bps_flash_bwd_dkv"):
        (kind,) = flash[kernel]
        assert kind["calls"] == 5
        assert kernel_counts.least_seconds(kind, PEAKS)[1] == "flops"
    assert flash["bps_flash_fwd"][0]["flops"] == (
        2.0 * 2 * 32 * 8192 * 4096.5 * 320)
    assert flash["bps_gmm"][0]["flops"] == 2.0 * 16384 * 0.75 * 2048 * 1536


# --------------------------------------------------------- the readers

def _trace(steps=2):
    """A trace made by hand: a step runs one latent attention half forward
    and backward (projections, the latent's part, the kernels) and a
    routed layer; times in ns."""
    ops, t = [], 0.0

    def op(name, path, ns):
        nonlocal t
        ops.append((name, path, t, t + ns))
        t += ns

    fwd = "jit(step)/bps.model/jvp(bps.attn)/bps.attn.mla/"
    bwd = ("jit(step)/bps.model/transpose(jvp(bps.model))/jvp()/checkpoint/"
           "bps.attn/bps.attn.mla/")
    for _ in range(steps):
        op("%fusion.1 = norm", "jit(step)/bps.model/jvp(bps.attn)/rsqrt", 5e5)
        op("%fusion.2 = dot", fwd + "dot_general", 4e6)         # q
        op("%fusion.3 = dot", fwd + "bps.attn.mla.latent/dot_general", 2e6)
        op("%fusion.4 = concat", fwd + "bps.attn.mla.latent/concatenate", 1e6)
        op("%bps_flash_fwd.1 = custom-call",
           fwd + "bps_flash_fwd/pallas_call", 1e7)
        op("%fusion.5 = dot", fwd + "dot_general", 3e6)         # o
        op("%bps_flash_bwd_dq.1 = custom-call",
           bwd + "bps_flash_bwd_dq/pallas_call", 1.5e7)
        op("%fusion.6 = add", bwd + "bps.attn.mla.latent/add_any", 2.5e6)
        op("%fusion.7 = dot", "jit(step)/bps.model/jvp(bps.mlp)/bps.moe/dot",
           7e6)
    return program.Program("/device:TPU:0", (0.0, t), steps, ops, [], [],
                           "tf_op")


def test_the_readers_on_the_handmade_trace(cell, monkeypatch):
    run = types.SimpleNamespace(cell=cell, peaks=PEAKS, chips=[object()])
    read = {m: harness.load_metric(m, cell.dirs).read for m in NEW_METRICS}
    monkeypatch.setattr(program, "of_run", lambda run: _trace())
    assert read["model.mla_ms"](run) == 4 + 2 + 1 + 10 + 3 + 15 + 2.5
    assert read["model.mla_latent_ms"](run) == 2 + 1 + 2.5
    # the accepted flash readers read the cell through its own counts
    roofline = harness.load_metric("kernels.flash_roofline_pct", cell.dirs)
    assert roofline.read(run) is None       # one call a step is no five
    # a program from before the scopes reports nothing, and does not raise
    bare = _trace()
    bare.ops = [(n, p.replace("bps.attn.mla", "x"), s, e)
                for n, p, s, e in bare.ops]
    monkeypatch.setattr(program, "of_run", lambda run: bare)
    assert [r(run) for r in read.values()] == [None, None]
    monkeypatch.setattr(program, "of_run", lambda run: None)
    assert [r(run) for r in read.values()] == [None, None]


def test_the_flash_share_of_the_roofline_at_two_widths(cell):
    """Five calls of each kernel a step at the cell's counts: the share is
    the counts' least seconds over the seconds taken, under 100 %."""
    z, steps = cell.config["sizes"], 2
    flash = counts.kernel_counts(z, cell.mix)
    took = {"bps_flash_fwd": 20e-3, "bps_flash_bwd_dq": 25e-3,
            "bps_flash_bwd_dkv": 35e-3}      # seconds a call
    by_kernel = {k: (s * 1e9 * 5 * steps, 5 * steps) for k, s in took.items()}
    got = program.roofline(by_kernel, flash, PEAKS, steps)
    least = {k: flash[k][0]["flops"] / 197e12 for k in took}
    for k in took:
        assert got[k]["bound"] == "flops"
        assert got[k]["pct"] == pytest.approx(100 * least[k] / took[k])
    assert got["all"]["pct"] == pytest.approx(
        100 * sum(least.values()) / sum(took.values()))
    assert 0 < got["all"]["pct"] < 100


def test_the_new_entries_are_the_new_cells_alone(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    mine = [by_name[name] for name in NEW_METRICS]
    assert all(m["workloads"] == [CELL] for m in mine)
    assert all(m["moves"] == "tokens_per_s_chip" and m["layer"] == "model"
               and m["unit"] == "ms" and m["source"] == "device_trace"
               for m in mine)
    (entry,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "lm_b2_s8192", 1)
    (config,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == list(REDUCED)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the accepted flash metrics, which have no list, reach it; so do,
    # since PR 50, the routed layers' (its routed half is afmoe's code);
    # the state-space layers' do not
    cell = harness.load_cell(ROOT, CELL)
    assert {"kernels.flash_fwd_ms", "kernels.flash_bwd_ms",
            "kernels.flash_roofline_pct", "kernels.fallback_sites",
            *NEW_METRICS, *ROUTED_METRICS} <= set(cell.per_layer)
    assert not {"model.ssm_ms", "model.ssm_scan_ms"} & set(cell.per_layer)


# ------------------------------------------- the harness, on the CPU

TINY_SIZES = dict(
    vocab_size=512, hidden=64, heads=4, kv_lora_rank=32, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=16, mlp_dim=96, moe_dim=24, top_k=2,
    router_outputs=8, held=[0, 1, 2, 3], shared_experts=2, route_scale=2.448,
    max_seq=64, rope_theta=1e6, norm_eps=1e-6, balanced=True,
    layer_kinds=["mla_dense", "mla_moe", "mla_moe"])


def _write_tiny_deepseek_v3(root):
    """``write_tiny_benchmark``'s manifest plus a cell of the deepseek_v3
    family cut the same way (4 of 8 experts), all new files."""
    write_tiny_benchmark(root)
    bench = os.path.join(root, "tinybench")
    shutil.copy(os.path.join(ROOT, "benchmark", "counts_deepseek_v3.py"),
                bench)
    for metric in NEW_METRICS:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics",
                                 metric + ".py"),
                    os.path.join(bench, "metrics"))
    doc = {"reduced": [], "optimizer": OPTIMIZER,
           "sizes": dict(TINY_SIZES, layers=3, experts_held=4),
           "program": {
               "config": "byteps_tpu.models.decoder:deepseek_v3_config",
               "config_kwargs": dict(TINY_SIZES, dtype="float32",
                                     routed_kw={"row_tile": 8},
                                     lm_head_chunk=32),
               "loss": "byteps_tpu.models.decoder:causal_lm_loss",
               "loss_kwargs": {}, "step_must_contain": ["tpu_custom_call"]},
           "reference": "benchmark.reference.deepseek_v3_share",
           "flops_rule": "tinybench.counts_deepseek_v3:flops_per_token",
           "kernel_counts": "tinybench.counts_deepseek_v3:kernel_counts",
           "limits": TIGHT}
    with open(os.path.join(bench, "configs", "tiny_deepseek_v3.json"),
              "w") as f:
        json.dump(doc, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny_deepseek_v3", "source": "test", "reduced": [],
        "why": "test", "file": "tinybench/configs/tiny_deepseek_v3.json"})
    manifest["workloads"].append({
        "name": "tiny_deepseek_v3_cell", "config": "tiny_deepseek_v3",
        "traffic": "lm_tiny", "chips": 1, "why": "test"})
    manifest["per_layer"] = [
        dict(m, workloads=["tiny_deepseek_v3_cell"])
        if m["name"] in NEW_METRICS else m for m in manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return str(root)


@pytest.mark.parametrize("trace", [False, True])
def test_the_harness_runs_a_cell_of_the_family_unchanged(tmp_path, trace,
                                                         capsys):
    root = _write_tiny_deepseek_v3(tmp_path)
    result = harness.run_cell(root, "tiny_deepseek_v3_cell", 2**31 + 44, 0.3,
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
    cell = harness.load_cell(root, "tiny_deepseek_v3_cell")
    assert set(NEW_METRICS) <= set(cell.per_layer)
    assert harness.flops_per_token(cell) == counts.flops_per_token(
        cell.config["sizes"], 64, 63)


# ------------------------------------ where the program has no such model

def test_the_reference_ends_at_import_where_the_program_has_no_mla(
        monkeypatch):
    """The parent of the PR that brought the configuration, with the
    benchmark's new files laid over it: the harness imports the reference
    before it builds anything, and the import fails at once."""
    real = importlib.util.find_spec
    spec = real("benchmark.reference.deepseek_v3_share")
    monkeypatch.setattr(
        importlib.util, "find_spec", lambda name, *a: (
            None if name == "byteps_tpu.models.mla" else real(name, *a)))
    t0 = time.time()
    with pytest.raises(ImportError, match=r"no byteps_tpu\.models\.mla"):
        spec.loader.exec_module(importlib.util.module_from_spec(spec))
    assert time.time() - t0 < 5

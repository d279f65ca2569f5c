"""The configuration ``trinity_mini_lm`` and its cell as the benchmark
holds them: the file against the source it names, its counts counted by
hand at a tiny size, its four per-layer readers on a trace made by hand,
and the harness running a tiny cell of the same family on the CPU with
nothing under ``benchmark/`` edited."""

import json
import os
import shutil
import time
import types

import pytest

from tinybench import (OPTIMIZER, ROOT, ROUTED_CELLS, ROUTED_METRICS, TIGHT,
                       write_tiny_benchmark)

from benchmark import counts_afmoe, harness, kernel_counts
from benchmark.trace import named, program

CELL = "trinity_mini_s8192_1chip"
REDUCED = {"num_hidden_layers": 5, "num_experts": 16,
           "num_attention_heads": 8, "num_key_value_heads": 1,
           "vocab_size": 25024}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(ROOT, CELL)


# ------------------------------------------------------------ the file

def test_the_file_runs_the_published_numbers_but_for_the_share(cell):
    doc = cell.config
    assert set(doc["reduced"]) == set(REDUCED)
    for key, value in doc["published"].items():
        assert doc[key] == REDUCED.get(key, value), key
    assert doc["published"]["layer_types"] == doc["layer_types"]
    d = doc["deployment"]
    assert (d["chips_sharing_a_layer"], d["experts_held"],
            d["query_heads_held"], d["kv_heads_held"],
            d["vocab_rows_held"]) == (8, 16, 8, 1, 25024)
    sizes = doc["sizes"]
    assert sizes["held"] == list(range(16)) and sizes["router_outputs"] == 128
    assert sizes["layer_kinds"] == doc["layer_pattern"]["run"] == [
        "dense_sliding", "moe_sliding", "moe_full", "moe_sliding",
        "moe_sliding"]
    # the pattern's published kinds are the source's own two lists
    pub = doc["published"]
    assert doc["layer_pattern"]["published"] == [
        ("dense" if i < pub["num_dense_layers"] else "moe") + (
            "_sliding" if t == "sliding_attention" else "_full")
        for i, t in enumerate(pub["layer_types"])]
    assert cell.mix == {**cell.mix, "kind": "lm", "batch_per_chip": 2,
                        "seq": 8192, "reference_rows_per_block": 1}


def test_the_share_is_603_million_parameters(cell):
    """ISSUE 29's reckoning: attention 6.82 M a layer, the dense layer
    44.6 M, a routed layer 114.0 M, embedding and head 51.2 M each."""
    import jax
    from benchmark.reference import afmoe_share
    tree = jax.eval_shape(lambda: afmoe_share.make_params(
        0, cell.config["sizes"]))
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    assert round(count(tree["layers"][0]["attn"]) / 1e6, 2) == 6.82
    assert round(count(tree["layers"][0]) / 1e6, 1) == 44.6
    assert round(count(tree["layers"][1]) / 1e6, 1) == 114.0
    assert round(count(tree["embed"]) / 1e6, 1) == 51.2
    assert round(count(tree) / 1e6) == 603


def test_the_program_is_built_from_the_files_sizes(cell):
    cfg, _, _ = harness.build_program(cell)
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.window) == (
        8, 1, 128, 2048)
    assert cfg.routed.held == tuple(range(16))
    assert (cfg.routed.num_experts, cfg.routed.top_k,
            cfg.routed.route_scale) == (128, 8, 2.826)
    assert cfg.lm_head_chunk == 2048 and cfg.dtype == "bfloat16"


# ---------------------------------------------------------- the counts

TINY = dict(hidden=4, head_dim=2, heads=2, kv_heads=1, mlp_dim=3, moe_dim=2,
            window=2, top_k=2, router_outputs=8, experts_held=4,
            shared_experts=1, vocab_size=16,
            layer_kinds=["dense_sliding", "moe_full"])


def test_required_operations_counted_by_hand():
    """seq 4, window 2: rows see 1, 2, 2, 2 keys = 1.75 on average; the
    triangle 2.5. Projections 2*4*2*(3*2 + 2*1) = 128 a layer. Dense
    layer: 128 + 4*2*2*1.75 + 6*4*3 = 228. Routed layer: 128 + 4*2*2*2.5
    + router 2*4*8 + (1 shared + 2*4/8 routed) x 6*4*2 = 328. Head on 3
    of 4 positions: 2*4*16*3/4 = 96. Times 3."""
    assert counts_afmoe.window_keys(4, 2) == 1.75
    assert counts_afmoe.layer_forward(TINY, "dense_sliding", 4) == 228
    assert counts_afmoe.layer_forward(TINY, "moe_full", 4) == 328
    assert counts_afmoe.flops_per_token(TINY, 4, 3) == 3 * (228 + 328 + 96)


def test_grouped_products_counted_by_hand():
    """10 rows of [4] against 2 weights of [4, 6]: 2*10*4*6 operations;
    rows in and out and both weights once, in bf16."""
    for kernel in counts_afmoe.GMM_KERNELS:
        assert counts_afmoe.gmm_call(kernel, 10, 4, 6, 2) == {
            "flops": 480.0, "bytes": 296.0}
    with pytest.raises(ValueError):
        counts_afmoe.gmm_call("bps_gmm_other", 1, 1, 1, 1)
    counts = counts_afmoe.kernel_counts(TINY, {"batch_per_chip": 2, "seq": 4})
    rows = 2 * 4 * 1            # one routed row a token on average
    assert counts["bps_gmm"] == [
        dict(counts_afmoe.gmm_call("bps_gmm", rows, 4, 4, 4), calls=1),
        dict(counts_afmoe.gmm_call("bps_gmm", rows, 2, 4, 4), calls=1)]
    band, triangle = counts["bps_flash_bwd_dq"]
    assert band == dict(kernel_counts.flash_call(
        "bps_flash_bwd_dq", 2, 2, 4, 2, True, kv_heads=1, window=2), calls=1)
    assert triangle["flops"] / band["flops"] == 2.5 / 1.75


def test_the_cells_count(cell):
    """ISSUE 29: 395 MFLOP a token forward, 1.19 GFLOP required; four
    band calls and one triangle call of each flash kernel a step."""
    per_token = harness.flops_per_token(cell)
    assert round(per_token / 3e6) == 395 and round(per_token / 1e7) == 119
    counts = harness.named_count(cell, "kernel_counts")(
        cell.config["sizes"], cell.mix)
    assert [kind["calls"] for kind in counts["bps_flash_fwd"]] == [4, 1]
    assert counts["bps_gmm"][0]["flops"] == 2.0 * 16384 * 2048 * 2048


# --------------------------------------------------------- the readers

def _trace(steps=2):
    """A trace made by hand: a step runs 2 band and 1 triangle forward
    flash calls, 4 ``bps_gmm`` calls under the experts' scope and some
    routing; times in ns."""
    ops, t = [], 0.0

    def op(name, path, ns):
        nonlocal t
        ops.append((name, path, t, t + ns))
        t += ns

    root = "jit(step)/bps.model/jvp(bps.mlp)/bps.moe/"
    for _ in range(steps):
        for i in range(4):
            op(f"%bps_gmm.{i} = bf16[] custom-call()",
               root + "bps.moe.experts/pallas_call", 1e6)
        op("%bps_gmm_dw = bf16[] custom-call()",
           root + "bps.moe.experts/pallas_call", 2e6)
        op("%fusion.9 = gather", root + "bps.moe.route/gather", 3e6)
        op("%sort.2 = sort", root + "bps.moe.route/bps.moe.route.plan/sort",
           2.5e5)
        op("%fusion.3 = dot", root + "bps.moe.shared/dot_general", 5e5)
        op("%fusion.4 = dot", "jit(step)/bps.model/jvp(bps.attn)/dot", 7e6)
    return program.Program("/device:TPU:0", (0.0, t), steps, ops, [], [],
                           "tf_op")


def test_scopes_and_kernels_are_read_by_name():
    trace = _trace()
    assert named.scope_ms(trace, "bps.moe") == 4 + 2 + 3 + 0.25 + 0.5
    assert named.scope_ms(trace, "bps.moe.route") == 3 + 0.25
    assert named.scope_ms(trace, "bps.moe.route.plan") == 0.25
    assert named.scope_ms(trace, "bps.moe.nothing") is None
    assert named.ns_by_kernel(trace, "bps_gmm") == {
        "bps_gmm": (8e6, 8), "bps_gmm_dw": (4e6, 2)}
    assert named.kernel_of("%bps_gmm_dx.12 = x", "bps_gmm") == "bps_gmm_dx"
    assert named.kernel_of("%fusion.1 = x", "bps_gmm") is None


@pytest.mark.parametrize("metric,want", [
    ("model.moe_ms", 9.75), ("model.moe_route_ms", 3.25),
    ("model.moe_plan_ms", 0.25), ("kernels.gmm_ms", 6.0)])
def test_the_readers_on_the_handmade_trace(cell, monkeypatch, metric, want):
    reader = harness.load_metric(metric, cell.dirs)
    run = types.SimpleNamespace(cell=cell, peaks=PEAKS, chips=[object()])
    monkeypatch.setattr(program, "of_run", lambda run: _trace())
    assert reader.read(run) == want
    # a program from before the scopes and the kernels reports nothing
    bare = _trace()
    bare.ops = [(n.replace("bps_gmm", "other"), p.replace("bps.moe", "x"),
                 s, e) for n, p, s, e in bare.ops]
    monkeypatch.setattr(program, "of_run", lambda run: bare)
    assert reader.read(run) is None
    monkeypatch.setattr(program, "of_run", lambda run: None)
    assert reader.read(run) is None


def test_the_roofline_reader_needs_the_counts_to_fit_the_calls(
        cell, monkeypatch):
    """4 ``bps_gmm`` calls a step are two of the list (up, down), 1
    ``bps_gmm_dw`` call is half of it: no share. With 2, the share is the
    lists' least seconds over the seconds taken."""
    reader = harness.load_metric("kernels.gmm_roofline_pct", cell.dirs)
    run = types.SimpleNamespace(cell=cell, peaks=PEAKS, chips=[object()])
    trace = _trace()
    monkeypatch.setattr(program, "of_run", lambda run: trace)
    assert reader.read(run) is None
    trace.ops += [("%bps_gmm_dw.7 = bf16[] custom-call()", p, s + 1, e + 1)
                  for n, p, s, e in trace.ops if n.startswith("%bps_gmm_dw")]
    counts = counts_afmoe.kernel_counts(cell.config["sizes"], cell.mix)
    least = sum(
        kernel_counts.least_seconds(kind, PEAKS)[0] * calls
        for kernel, calls in (("bps_gmm", 2), ("bps_gmm_dw", 1))
        for kind in counts[kernel])
    assert reader.read(run) == pytest.approx(100.0 * least / 8e-3)
    assert 0 < reader.read(run) < 100


@pytest.mark.parametrize("name", ROUTED_CELLS)
@pytest.mark.parametrize("metric", ROUTED_METRICS)
def test_the_routed_metrics_read_every_routed_cell(metric, name):
    """Each lists the three cells whose steps run ``models/moe.py``, by
    name and wherever its entry stands, has its reader, and the cell's
    configuration names a count with the grouped products in it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == metric]
    assert entry["workloads"] == list(ROUTED_CELLS)
    assert (entry["moves"], entry["source"]) == ("tokens_per_s_chip",
                                                 "device_trace")
    routed = harness.load_cell(ROOT, name)
    assert metric in routed.per_layer
    assert callable(harness.load_metric(metric, routed.dirs).read)
    found = harness.named_count(routed, "kernel_counts")(
        routed.config["sizes"], routed.mix)
    assert set(counts_afmoe.GMM_KERNELS) <= set(found)
    assert all(kind["calls"] == 1 and kind["flops"] > 0 and kind["bytes"] > 0
               for kernel in counts_afmoe.GMM_KERNELS
               for kind in found[kernel])


def test_no_dense_cell_lists_a_routed_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in set(names) - set(ROUTED_CELLS):
        assert not set(ROUTED_METRICS) & set(
            harness.load_cell(ROOT, name).per_layer)


# ------------------------------------------- the harness, on the CPU

def _write_tiny_afmoe(root):
    """``write_tiny_benchmark``'s manifest plus a cell of the afmoe family
    cut the same way (4 of 8 experts, half the heads), all new files."""
    write_tiny_benchmark(root)
    bench = os.path.join(root, "tinybench")
    shutil.copy(os.path.join(ROOT, "benchmark", "counts_afmoe.py"), bench)
    sizes = dict(vocab_size=512, hidden=64, heads=4, kv_heads=2, head_dim=16,
                 mlp_dim=96, moe_dim=32, window=16, top_k=2,
                 router_outputs=8, held=[0, 1, 2, 3], balanced=True,
                 shared_experts=1, route_scale=2.0, max_seq=64,
                 rope_theta=10000, norm_eps=1e-5,
                 layer_kinds=["dense_sliding", "moe_sliding", "moe_full"])
    doc = {"reduced": [], "optimizer": OPTIMIZER,
           "sizes": dict(sizes, layers=3, experts_held=4),
           "program": {
               "config": "byteps_tpu.models.decoder:afmoe_config",
               "config_kwargs": dict(sizes, dtype="float32",
                                     routed_kw={"row_tile": 8},
                                     lm_head_chunk=32),
               "loss": "byteps_tpu.models.decoder:causal_lm_loss",
               "loss_kwargs": {}, "step_must_contain": ["tpu_custom_call"]},
           "reference": "benchmark.reference.afmoe_share",
           "flops_rule": "tinybench.counts_afmoe:flops_per_token",
           "kernel_counts": "tinybench.counts_afmoe:kernel_counts",
           "limits": TIGHT}
    with open(os.path.join(bench, "configs", "tiny_afmoe.json"), "w") as f:
        json.dump(doc, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny_afmoe", "source": "test", "reduced": [], "why": "test",
        "file": "tinybench/configs/tiny_afmoe.json"})
    manifest["workloads"].append({
        "name": "tiny_afmoe_cell", "config": "tiny_afmoe",
        "traffic": "lm_tiny", "chips": 1, "why": "test"})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    manifest["per_layer"] += [
        dict(by_name[name], workloads=["tiny_afmoe_cell"])
        for name in ROUTED_METRICS]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return str(root)


@pytest.mark.parametrize("trace", [False, True])
def test_the_harness_runs_a_cell_of_the_family_unchanged(tmp_path, trace,
                                                         capsys):
    root = _write_tiny_afmoe(tmp_path)
    result = harness.run_cell(root, "tiny_afmoe_cell", 2**31 + 29, 0.3, trace,
                              time.time(), require_chip=False)
    assert result["correct"] is True, capsys.readouterr().out
    assert result["failed"] == 0 and result["attempted"] > 2
    assert {"loss_rel", "grad_norm_rel", "change_norm_rel",
            "compiles_in_window"} <= set(result["checks"])
    if trace:       # no device trace on the CPU: the new readers say nothing
        assert not set(ROUTED_METRICS) & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"tokens_per_s_chip", "step_ms_p95",
                                          "setup_s"}
    cell = harness.load_cell(root, "tiny_afmoe_cell")
    assert harness.flops_per_token(cell) == counts_afmoe.flops_per_token(
        cell.config["sizes"], 64, 63)

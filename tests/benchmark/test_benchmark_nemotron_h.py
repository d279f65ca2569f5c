"""The configuration ``nemotron3_nano_lm`` and its cell as the benchmark
holds them: the file against the source it names and against what
``manifest_rules.depth_floor`` would hold if the published pattern
repeated to its end, its counts counted by hand at a tiny size, its
three per-layer readers on a trace made by hand, the harness running a
tiny cell of the same family on the CPU with nothing under ``benchmark/``
edited, and a tiny float8 control that fails a limit the program keeps."""

import json
import os
import shutil
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tinybench import (OPTIMIZER, ROOT, ROUTED_METRICS, TIGHT,
                       write_tiny_benchmark)

import manifest_rules as rules
from benchmark import correct, counts_afmoe, counts_nemotron_h as counts
from benchmark import harness
from benchmark import kernel_counts
from benchmark.reference import nemotron_h_share as ref
from benchmark.trace import program

CELL = "nemotron3_nano_s8192_1chip"
REDUCED = {"num_hidden_layers": 7, "n_routed_experts": 8,
           "vocab_size": 16384}
KIND_OF = {"M": "ssm", "E": "moe", "*": "attn"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("model.ssm_ms", "model.ssm_scan_ms",
               "model.ssm_scan_roofline_pct")


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(ROOT, CELL)


# ------------------------------------------------------------ the file

def test_the_file_runs_the_published_numbers_but_for_the_share(cell):
    doc = cell.config
    assert set(doc["reduced"]) == set(REDUCED)
    for key, value in doc["published"].items():
        assert doc[key] == REDUCED.get(key, value), key
    d = doc["deployment"]
    assert (d["chips_sharing_a_layer"], d["experts_held"],
            d["query_heads_held"], d["kv_heads_held"], d["vocab_rows_held"],
            d["layers_run"]) == (16, 8, 32, 2, 16384, 7)
    sizes = doc["sizes"]
    assert sizes["held"] == list(range(8)) and sizes["router_outputs"] == 128
    assert (sizes["ssm_heads"] * sizes["ssm_head_dim"], sizes["ssm_groups"],
            sizes["ssm_state"], sizes["chunk"], sizes["conv_kernel"]) == (
                4096, 8, 128, 128, 4)
    assert (sizes["moe_dim"], sizes["shared_dim"], sizes["top_k"],
            sizes["route_scale"]) == (1856, 3712, 6, 2.5)
    assert cell.mix == {**cell.mix, "kind": "lm", "batch_per_chip": 2,
                        "seq": 8192, "reference_rows_per_block": 1}
    assert doc["assumed"] and doc["departures"] and doc["limits_set_from"]


def test_the_layers_run_are_one_whole_unit_of_the_published_pattern(cell):
    """What ``depth_floor`` holds a ``layer_pattern`` to, held here for a
    pattern that does not repeat to its end: the layers run are published
    layers 0 to 6 of the published string, layers 0 to 34 repeat that
    unit five times exactly, the rest does not, every kind is present."""
    doc = cell.config
    assert "layer_pattern" not in doc
    layers, pattern = doc["layers"], doc["published"][
        "hybrid_override_pattern"]
    assert layers["published_pattern"] == pattern
    assert len(pattern) == doc["published"]["num_hidden_layers"] == 52
    assert [pattern.count(c) for c in "ME*"] == [23, 23, 6]
    assert layers["run"] == list(range(7)) and layers["kind_of"] == KIND_OF
    unit = pattern[:7]
    assert unit == layers["unit"] == "MEMEM*E"
    assert doc["sizes"]["layer_kinds"] == [KIND_OF[c] for c in unit]
    assert doc["sizes"]["layers"] == len(unit) >= rules.LAYERS_AFTER_DENSE
    first, last = layers["unit_repeats_exactly_over"]
    assert pattern[first:last + 1] == unit * 5 and last + 1 == 35
    assert pattern[35:42] != unit           # why no layer_pattern is declared
    assert set(doc["sizes"]["layer_kinds"]) == set(KIND_OF.values())
    with pytest.raises(rules.Refused, match="do not repeat"):
        rules.depth_floor(dict(doc, layer_pattern={
            "published": [KIND_OF[c] for c in pattern], "period": 7,
            "leading_dense": 0, "run": doc["sizes"]["layer_kinds"]}), 7, 52)


def test_the_floors_of_the_cut(cell):
    doc = cell.config
    rules.published_sizes(doc)
    chips = doc["deployment"]["chips_sharing_a_layer"]
    assert doc["sizes"]["experts_held"] * chips == doc["published"][
        "n_routed_experts"]
    assert doc["sizes"]["experts_held"] >= rules.EXPERTS_HELD
    assert doc["sizes"]["vocab_size"] * rules.VOCAB_SHARE >= doc[
        "published"]["vocab_size"]
    for width in ("hidden_size", "moe_intermediate_size", "mamba_head_dim",
                  "ssm_state_size", "head_dim", "num_experts_per_tok"):
        with pytest.raises(rules.Refused):
            rules.published_sizes(dict(doc, reduced=doc["reduced"] + [width]))


def test_the_share_is_528_million_parameters(cell):
    """ISSUE 34's reckoning: a state-space layer 38.74 M, the attention
    layer 23.40 M, a routed layer 100.13 M, embedding and head 44.04 M
    each."""
    tree = jax.eval_shape(lambda: ref.make_params(0, cell.config["sizes"]))
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    kinds = cell.config["sizes"]["layer_kinds"]
    assert round(count(tree["layers"][kinds.index("ssm")]) / 1e6, 2) == 38.74
    assert round(count(tree["layers"][kinds.index("attn")]) / 1e6, 2) == 23.40
    assert round(count(tree["layers"][kinds.index("moe")]) / 1e6, 2) == 100.13
    assert round(count(tree["embed"]) / 1e6, 2) == 44.04
    assert round(count(tree) / 1e6, 1) == 528.1


def test_the_program_is_built_from_the_files_sizes(cell):
    cfg, _, _ = harness.build_program(cell)
    assert cfg.layer_kinds == ("ssm", "moe", "ssm", "moe", "ssm", "attn",
                               "moe")
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.hidden) == (
        32, 2, 128, 2688)
    assert (cfg.ssm.heads, cfg.ssm.head_dim, cfg.ssm.groups, cfg.ssm.state,
            cfg.ssm.conv_kernel, cfg.ssm.chunk) == (64, 64, 8, 128, 4, 128)
    assert cfg.routed.held == tuple(range(8))
    assert (cfg.routed.num_experts, cfg.routed.top_k, cfg.routed.route_scale,
            cfg.routed.act, cfg.routed.shared_dim, cfg.moe_dim) == (
                128, 6, 2.5, "relu2", 3712, 1856)
    assert cfg.routed.balanced and not cfg.scale_embedding
    assert cfg.lm_head_chunk == 2048 and cfg.dtype == "bfloat16"
    # a silent fall-back to lax.ragged_dot and XLA's gathers fails correct
    assert {"bps_gmm", "bps_gmm_dx", "bps_gmm_dw", "bps_moe_take",
            "bps_moe_combine", "bps_flash_fwd"} <= set(
                cell.config["program"]["step_must_contain"])


# ---------------------------------------------------------- the counts

TINY = dict(hidden=4, head_dim=2, heads=2, kv_heads=1, moe_dim=3,
            shared_dim=5, shared_experts=1, top_k=2, router_outputs=8,
            experts_held=4, ssm_heads=2, ssm_head_dim=3, ssm_groups=1,
            ssm_state=5, vocab_size=16, layer_kinds=["ssm", "moe", "attn"])


def test_required_operations_counted_by_hand():
    """seq 4. ``ssm``: inner 6; in_proj 2*4*(6 + 6 + 2*5 + 2) = 192,
    out_proj 2*6*4 = 48, the recurrence 4*2*3*5 = 120. ``attn``:
    projections 2*4*2*(2*2 + 2*1) = 96, the triangle 4*2*2*2.5 = 40.
    ``moe``: router 2*4*8 = 64, shared 4*4*5 = 80, routed 2*4/8 = 1 row
    x 4*4*3 = 48. Head on 3 of 4 positions: 2*4*16*3/4 = 96. Times 3."""
    assert counts.layer_forward(TINY, "ssm", 4) == 192 + 48 + 120
    assert counts.layer_forward(TINY, "attn", 4) == 96 + 40
    assert counts.layer_forward(TINY, "moe", 4) == 64 + 80 + 48
    assert counts.flops_per_token(TINY, 4, 3) == 3 * (360 + 136 + 192 + 96)
    with pytest.raises(ValueError):
        counts.layer_forward(TINY, "dense_full", 4)


def test_the_scan_and_the_flash_calls_counted_by_hand():
    """2 x 4 tokens, one ``ssm`` layer: 3 x 120 operations a token; a
    token's operands 6 + 2*5 in bf16 and 2 steps in float32 = 40 bytes,
    its result 12: forward 52, backward 40 + 12 + 40."""
    mix = {"batch_per_chip": 2, "seq": 4}
    assert counts.scan_count(TINY, mix) == {
        "flops": 3.0 * 120 * 8, "bytes": float((52 + 92) * 8)}
    flash = counts.kernel_counts(TINY, mix)
    assert flash["bps_flash_bwd_dq"] == [dict(kernel_counts.flash_call(
        "bps_flash_bwd_dq", 2, 2, 4, 2, True, kv_heads=1), calls=1)]
    assert set(flash) == set(kernel_counts.KERNELS) | set(
        counts_afmoe.GMM_KERNELS)
    # a token sends 2 * 4 / 8 = 1 row to the 4 experts held: 8 rows of
    # [.., 4] against 4 weights [4, 3], not gated, and back
    for kernel in counts_afmoe.GMM_KERNELS:
        assert flash[kernel] == [
            {"flops": 2.0 * 8 * 4 * 3, "calls": 1,
             "bytes": float((8 * (4 + 3) + 4 * 4 * 3) * 2)}] * 2


def test_the_grouped_products_at_the_cells_own_widths(cell):
    """Ungated relu2 experts: up 2688 -> 1856 and down 1856 -> 2688 over
    the 8 experts held at 768 mean rows each (16,384 tokens x 6 of 128
    outputs x 8 held = 6,144 rows); a step runs ``bps_gmm`` 12 times
    (three layers, forward and recompute), ``_dx`` and ``_dw`` 6 times:
    24 calls of 0.311 ms by operations, 7.47 ms."""
    sizes = cell.config["sizes"]
    assert (sizes["hidden"], sizes["moe_dim"], sizes["experts_held"],
            sizes["layer_kinds"].count("moe")) == (2688, 1856, 8, 3)
    assert 16384 * counts.routed_rows_per_token(sizes) == 8 * 768
    found = harness.named_count(cell, "kernel_counts")(sizes, cell.mix)
    for kernel in counts_afmoe.GMM_KERNELS:
        up, down = found[kernel]
        assert up == down == {
            "flops": 2.0 * 6144 * 2688 * 1856, "calls": 1,
            "bytes": float((6144 * (2688 + 1856) + 8 * 2688 * 1856) * 2)}
        assert kernel_counts.least_seconds(up, PEAKS) == (
            pytest.approx(0.3112e-3, rel=1e-3), "flops")
    took = {"bps_gmm": (8.0e6 * 2, 12 * 2), "bps_gmm_dx": (3.8e6 * 2, 6 * 2),
            "bps_gmm_dw": (6.1e6 * 2, 6 * 2)}       # (ns, calls), 2 steps
    got = program.roofline(took, found, PEAKS, 2)
    assert got["all"]["pct"] == pytest.approx(
        100 * 24 * 61303947264.0 / 197e12 / 17.9e-3)
    assert 0 < got["all"]["pct"] < 100
    # 5 calls a step are no whole multiple of (up, down): no share
    odd = dict(took, bps_gmm_dw=(6.1e6 * 2, 5 * 2))
    assert "all" not in program.roofline(odd, found, PEAKS, 2)


def test_the_cells_count(cell):
    """1.754 GFLOP a token required: ISSUE 34 reckoned 1.77 with the
    chunked form's products, the count holds the scan to the recurrence's
    own 2.1 MFLOP a token and layer (``counts_nemotron_h``'s docstring);
    one triangle call of each flash kernel a pass."""
    per_token = harness.flops_per_token(cell)
    assert round(per_token / 1e6) == 1754
    sizes = cell.config["sizes"]
    share = {kind: 3 * counts.layer_forward(sizes, kind, 8192)
             * sizes["layer_kinds"].count(kind) / per_token
             for kind in KIND_OF.values()}
    assert [round(100 * share[k]) for k in ("ssm", "attn", "moe")] == [
        41, 19, 25]
    flash = harness.named_count(cell, "kernel_counts")(sizes, cell.mix)
    assert [kind["calls"] for kind in flash["bps_flash_fwd"]] == [1]
    scan = harness.named_count(cell, "scan_count")(sizes, cell.mix)
    assert kernel_counts.least_seconds(scan, PEAKS)[1] == "hbm"
    assert round(1e3 * kernel_counts.least_seconds(scan, PEAKS)[0], 2) == 3.24


# --------------------------------------------------------- the readers

def _trace(steps=2):
    """A trace made by hand: a step runs a state-space layer forward and
    backward (projections, convolution, scan, norm) and an attention
    layer; times in ns."""
    ops, t = [], 0.0

    def op(name, path, ns):
        nonlocal t
        ops.append((name, path, t, t + ns))
        t += ns

    fwd = "jit(step)/bps.model/jvp(bps.ssm)/"
    bwd = "jit(step)/bps.model/transpose(jvp(bps.ssm))/"
    for _ in range(steps):
        op("%fusion.1 = dot", fwd + "bps.ssm.proj/dot_general", 4e6)
        op("%fusion.2 = conv", fwd + "bps.ssm.conv/mul", 1e6)
        op("%fusion.3 = dot", fwd + "bps.ssm.scan/dot_general", 6e6)
        op("%bps_ssd_state.1 = custom-call", fwd + "bps.ssm.scan/pallas_call",
           2e6)
        op("%fusion.4 = norm", fwd + "bps.ssm.norm/rsqrt", 5e5)
        op("%fusion.5 = norm", fwd + "rsqrt", 5e5)      # the layer's norm
        op("%fusion.6 = dot", bwd + "bps.ssm.scan/dot_general", 1.2e7)
        op("%fusion.7 = dot", "jit(step)/bps.model/jvp(bps.attn)/dot", 7e6)
    return program.Program("/device:TPU:0", (0.0, t), steps, ops, [], [],
                           "tf_op")


def test_the_readers_on_the_handmade_trace(cell, monkeypatch):
    run = types.SimpleNamespace(cell=cell, peaks=PEAKS, chips=[object()])
    read = {m: harness.load_metric(m, cell.dirs).read for m in NEW_METRICS}
    monkeypatch.setattr(program, "of_run", lambda run: _trace())
    assert read["model.ssm_ms"](run) == 4 + 1 + 6 + 2 + 0.5 + 0.5 + 12
    assert read["model.ssm_scan_ms"](run) == 6 + 2 + 12
    scan = counts.scan_count(cell.config["sizes"], cell.mix)
    assert read["model.ssm_scan_roofline_pct"](run) == pytest.approx(
        100.0 * (scan["bytes"] / 819e9) / 20e-3)
    assert 0 < read["model.ssm_scan_roofline_pct"](run) < 100
    # a program from before the scopes reports nothing, and does not raise
    bare = _trace()
    bare.ops = [(n, p.replace("bps.ssm", "x"), s, e)
                for n, p, s, e in bare.ops]
    monkeypatch.setattr(program, "of_run", lambda run: bare)
    assert [r(run) for r in read.values()] == [None] * 3
    monkeypatch.setattr(program, "of_run", lambda run: None)
    assert [r(run) for r in read.values()] == [None] * 3
    # nor does a configuration that names no count of the scan
    other = types.SimpleNamespace(
        cell=harness.load_cell(ROOT, "trinity_mini_s8192_1chip"),
        peaks=PEAKS, chips=[object()])
    monkeypatch.setattr(program, "of_run", lambda run: _trace())
    assert read["model.ssm_scan_roofline_pct"](other) is None


def test_the_new_metrics_are_the_new_cells_alone():
    """By name, wherever each stands: later PRs append entries."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    mine = [by_name[name] for name in NEW_METRICS]
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "tokens_per_s_chip" and m["layer"] == "model"
               for m in mine)
    (entry,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron3_nano_lm", "lm_b2_s8192", 1)
    (config,) = [c for c in manifest["configs"]
                 if c["name"] == "nemotron3_nano_lm"]
    assert config["file"] == "benchmark/configs/nemotron3_nano_lm.json"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the routed layers' metrics, which list their cells, reach this one
    assert set(ROUTED_METRICS) <= set(harness.load_cell(ROOT, CELL).per_layer)


# ------------------------------------------- the harness, on the CPU

TINY_SIZES = dict(
    vocab_size=512, hidden=64, heads=4, kv_heads=2, head_dim=16, moe_dim=24,
    shared_dim=40, top_k=2, router_outputs=8, held=[0, 1, 2, 3],
    route_scale=2.5, ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
    conv_kernel=4, chunk=16, max_seq=64, norm_eps=1e-5, balanced=True,
    layer_kinds=["ssm", "moe", "ssm", "attn", "moe"])


def _write_tiny_nemotron_h(root):
    """``write_tiny_benchmark``'s manifest plus a cell of the nemotron_h
    family cut the same way (4 of 8 experts), all new files."""
    write_tiny_benchmark(root)
    bench = os.path.join(root, "tinybench")
    shutil.copy(os.path.join(ROOT, "benchmark", "counts_nemotron_h.py"),
                bench)
    for metric in NEW_METRICS:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics",
                                 metric + ".py"),
                    os.path.join(bench, "metrics"))
    doc = {"reduced": [], "optimizer": OPTIMIZER,
           "sizes": dict(TINY_SIZES, layers=5, experts_held=4,
                         shared_experts=1),
           "program": {
               "config": "byteps_tpu.models.decoder:nemotron_h_config",
               "config_kwargs": dict(TINY_SIZES, dtype="float32",
                                     routed_kw={"row_tile": 8},
                                     lm_head_chunk=32),
               "loss": "byteps_tpu.models.decoder:causal_lm_loss",
               "loss_kwargs": {}, "step_must_contain": ["tpu_custom_call"]},
           "reference": "benchmark.reference.nemotron_h_share",
           "flops_rule": "tinybench.counts_nemotron_h:flops_per_token",
           "kernel_counts": "tinybench.counts_nemotron_h:kernel_counts",
           "scan_count": "tinybench.counts_nemotron_h:scan_count",
           "limits": TIGHT}
    with open(os.path.join(bench, "configs", "tiny_nemotron_h.json"),
              "w") as f:
        json.dump(doc, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny_nemotron_h", "source": "test", "reduced": [],
        "why": "test", "file": "tinybench/configs/tiny_nemotron_h.json"})
    manifest["workloads"].append({
        "name": "tiny_nemotron_h_cell", "config": "tiny_nemotron_h",
        "traffic": "lm_tiny", "chips": 1, "why": "test"})
    manifest["per_layer"] = [
        dict(m, workloads=["tiny_nemotron_h_cell"])
        if m["name"] in NEW_METRICS else m for m in manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return str(root)


@pytest.mark.parametrize("trace", [False, True])
def test_the_harness_runs_a_cell_of_the_family_unchanged(tmp_path, trace,
                                                         capsys):
    root = _write_tiny_nemotron_h(tmp_path)
    result = harness.run_cell(root, "tiny_nemotron_h_cell", 2**31 + 29, 0.3,
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
    cell = harness.load_cell(root, "tiny_nemotron_h_cell")
    assert set(NEW_METRICS) <= set(cell.per_layer)
    assert harness.flops_per_token(cell) == counts.flops_per_token(
        cell.config["sizes"], 64, 63)


# ------------------------------------------------- the control, tiny

# At THIS size (hidden 64, five layers, 2 x 64 tokens; CPU, PR 34): over
# seeds 1 to 4 the root mean square of the 38 leaves' gradient-norm gaps
# read 1.4e-3 to 4.6e-3 for the bfloat16 program and 1.2e-2 to 2.1e-2 for
# the float8 control (the WORST leaf swings too far at this size: an
# expert of 24 columns gains or loses a row to bf16 rounding, 0.6 to
# 2.5 % against the control's 3.6 to 6.7 %). The cell's own limits are
# set the same way from chip readings at its own size (PERF.md).
TINY_GRAD_NORM_RMS_LIMIT = 7.5e-3


def test_bfloat16_program_passes_where_the_float8_control_fails():
    from byteps_tpu.models import decoder
    cfg = decoder.nemotron_h_config(**dict(
        TINY_SIZES, dtype="bfloat16", routed_kw={"row_tile": 8}))
    sizes = dict(TINY_SIZES, layers=5, experts_held=4)
    opt = {k: v for k, v in OPTIMIZER.items() if k != "name"}
    limits = {"grad_norm_rms_rel": TINY_GRAD_NORM_RMS_LIMIT}
    for seed in (1, 2, 3):
        rng = np.random.RandomState(seed)
        batches = [rng.randint(1, 512, (2, 64)).astype(np.int32)
                   for _ in range(3)]
        params0 = ref.make_params(seed, sizes)
        want = ref.train_steps(params0, batches, sizes, opt, "lm", 1)
        control = ref.train_steps(params0, batches, sizes, opt, "lm", 1,
                                  "float8")
        tx = optax.adamw(**opt)
        p, state, got = params0, tx.init(params0), {"loss": []}
        for i, batch in enumerate(batches):
            loss, g = jax.value_and_grad(decoder.causal_lm_loss)(
                p, cfg, jnp.asarray(batch))
            if i == 0:
                got["grad_norm"] = np.asarray(ref.leaf_norms(g), np.float64)
            updates, state = tx.update(g, state, p)
            p = optax.apply_updates(p, updates)
            got["loss"].append(float(loss))
        got["change_norm"] = np.asarray(ref.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, params0)), np.float64)
        (sound,) = correct.compare(got, want, limits)
        (broken,) = correct.compare(control, want, limits)
        assert sound["ok"] and sound["value"] < limits[sound["check"]] / 1.4
        assert not broken["ok"]
        assert broken["value"] > limits[broken["check"]] * 1.4

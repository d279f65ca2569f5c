"""A tiny benchmark in a temporary directory, made of NEW files only (a
manifest, three configurations, two traffic mixes, a per-layer metric and
a file of counts), which the harness under ``benchmark/`` runs unchanged
on the CPU. The third configuration is written the way a cut one will be:
a made-up ``published`` of which it runs half the depth and an eighth of
the vocabulary, a ``deployment``, and counts of its own, found by name."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the routed layers' per-layer metrics and the cells that list them (PR 50)
ROUTED_METRICS = ("model.moe_ms", "model.moe_route_ms", "model.moe_plan_ms",
                  "kernels.gmm_ms", "kernels.gmm_roofline_pct")
ROUTED_CELLS = ("trinity_mini_s8192_1chip", "nemotron3_nano_s8192_1chip",
                "kanana2_30b_s8192_1chip")

TINY_SIZES = {"vocab_size": 512, "hidden": 64, "layers": 2, "heads": 4,
              "mlp_dim": 256, "max_seq": 64, "ln_eps": 1e-5}
OPTIMIZER = {"name": "adamw", "learning_rate": 1e-4, "b1": 0.9,
             "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}
# float32 program against the float32 reference: rounding only
TIGHT = {"loss_rel": 1e-5, "grad_norm_rel": 1e-4, "change_norm_rel": 1e-3,
         "trainer_vs_plain_loss_rel": 1e-5}


def tiny_config(kind: str, dtype: str = "float32", limits=None) -> dict:
    causal = kind == "lm"
    family = "gpt2:gpt2_config" if causal else "bert:bert_config"
    loss = "gpt2:causal_lm_loss" if causal else "bert:mlm_loss"
    return {
        "reduced": [],
        "sizes": dict(TINY_SIZES, causal=causal),
        "optimizer": OPTIMIZER,
        "program": {
            "config": "byteps_tpu.models." + family,
            "config_kwargs": {"hidden": 64, "layers": 2, "heads": 4,
                              "vocab_size": 512, "max_seq": 64,
                              "dtype": dtype},
            "loss": "byteps_tpu.models." + loss,
            "loss_kwargs": ({} if causal else
                            {"max_predictions": "$max_predictions_per_seq"}),
            "step_must_contain": ["tpu_custom_call"]},
        "reference": "benchmark.reference.pre_ln_transformer",
        "flops_rule": "transformer_lm",
        "limits": limits or TIGHT}


CUT_PUBLISHED = {"n_layer": 8, "n_embd": 64, "n_head": 4, "n_inner": 256,
                 "vocab_size": 4096, "n_positions": 64}
CUT_TO_SIZES = {
    "n_layer": {"sizes": "layers", "kind": "depth"},
    "n_embd": {"sizes": "hidden", "kind": "width"},
    "n_head": {"sizes": "heads", "kind": "heads_held"},
    "n_inner": {"sizes": "mlp_dim", "kind": "width"},
    "vocab_size": {"sizes": "vocab_size", "kind": "vocab_rows"},
    "n_positions": {"sizes": "max_seq", "kind": "positions"}}


def tiny_cut_config(dtype: str = "float32", limits=None) -> dict:
    """The causal tiny configuration as one chip's share of a made-up
    model twice as deep with eight times the vocabulary."""
    doc = tiny_config("lm", dtype, limits)
    doc["sizes"]["layers"] = doc["program"]["config_kwargs"]["layers"] = 4
    doc.update(
        published=dict(CUT_PUBLISHED),
        published_to_sizes={k: dict(v) for k, v in CUT_TO_SIZES.items()},
        reduced=["n_layer", "vocab_size"],
        deployment={"chips_sharing_a_layer": 8,
                    "held_here": "an eighth of the vocabulary's rows; the "
                                 "layers left out lie on further chips"},
        n_layer=4, vocab_size=512,      # the published keys as they are run
        flops_rule="tinybench.counts:flops_per_token",
        kernel_counts="tinybench.counts:two_kinds_of_call")
    return doc


TINY_COUNTS = '''"""The tiny cut configuration's counts, added as a file and found by the
name its configuration gives."""
from benchmark import flops, kernel_counts

WINDOW = 16


def flops_per_token(sizes, seq, targets_per_row):
    return flops.transformer_lm(sizes, seq, targets_per_row)


def two_kinds_of_call(sizes, mix):
    """Made up like the published sizes: as if three layers of four
    attended a causal band of WINDOW keys and the fourth the triangle."""
    def call(kernel, window):
        return kernel_counts.flash_call(
            kernel, mix["batch_per_chip"], sizes["heads"], mix["seq"],
            sizes["hidden"] // sizes["heads"], True, window=window)
    return {kernel: [dict(call(kernel, WINDOW), calls=3),
                     dict(call(kernel, None), calls=1)]
            for kernel in kernel_counts.KERNELS}
'''

TINY_MIXES = {
    "mlm_tiny": {"kind": "mlm", "batch_per_chip": 8, "seq": 64,
                 "masked_lm_prob": 0.15, "max_predictions_per_seq": 12,
                 "mask_token_id": 103, "reference_rows_per_block": 4},
    "lm_tiny": {"kind": "lm", "batch_per_chip": 4, "seq": 64,
                "reference_rows_per_block": 2},
}

NEW_METRIC = '''"""A per-layer metric added as a file: steps the traced
window attempted."""
UNIT, LAYER, MOVES, SOURCE = "steps", "trainer", "tokens_per_s_chip", "program_counter"


def read(run):
    return float(len(run.spans.get("step", [])))
'''


def write_tiny_benchmark(root, chips=1, dtype="float32", limits=None):
    """A whole benchmark under ``root``: three configurations, three
    cells, the repo's per-layer metrics and one new one."""
    bench = os.path.join(root, "tinybench")
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(bench, sub))
    for name, mix in TINY_MIXES.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    configs = {"tiny_mlm": tiny_config("mlm", dtype, limits),
               "tiny_lm": tiny_config("lm", dtype, limits),
               "tiny_cut": tiny_cut_config(dtype, limits)}
    for name, doc in configs.items():
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(doc, f)
    with open(os.path.join(bench, "counts.py"), "w") as f:
        f.write(TINY_COUNTS)
    with open(os.path.join(bench, "metrics", "trainer.steps_traced.py"),
              "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    cells = ["tiny_mlm_cell", "tiny_lm_cell", "tiny_cut_cell"]
    per_layer = [dict(m, workloads=cells[:1]) if "workloads" in m else m
                 for m in real["per_layer"]]
    per_layer.append({"name": "trainer.steps_traced", "unit": "steps",
                      "better": "higher", "source": "program_counter",
                      "layer": "trainer", "moves": "tokens_per_s_chip"})
    manifest = dict(
        real, paths=["tinybench"],
        configs=[{"name": n, "source": "test", "reduced": doc["reduced"],
                  "why": "test", "file": f"tinybench/configs/{n}.json"}
                 for n, doc in configs.items()],
        workloads=[
            {"name": cells[0], "config": "tiny_mlm", "traffic": "mlm_tiny",
             "chips": chips, "why": "test"},
            {"name": cells[1], "config": "tiny_lm", "traffic": "lm_tiny",
             "chips": chips, "why": "test"},
            {"name": cells[2], "config": "tiny_cut", "traffic": "lm_tiny",
             "chips": chips, "why": "test"}],
        per_layer=per_layer)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return str(root)

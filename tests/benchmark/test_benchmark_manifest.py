"""``BENCHMARK.json`` against the contract it is written to, and against
the files it names."""

import json
import os
import re

import pytest

from tinybench import ROOT

from benchmark import generator, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_configs(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["reduced"] == c["reduced"] == []
        # the sizes the program is built with are the sizes the reference
        # and the FLOPs count are given
        kw, sizes = doc["program"]["config_kwargs"], doc["sizes"]
        for key in ("hidden", "layers", "heads", "vocab_size", "max_seq"):
            assert kw[key] == sizes[key]
        assert sizes["mlp_dim"] == 4 * sizes["hidden"]
        assert doc["flops_rule"] in __import__(
            "benchmark.flops", fromlist=["RULES"]).RULES
        for limit in ("loss_rel", "trainer_vs_plain_loss_rel"):
            assert doc["limits"][limit] > 0


def test_published_sizes_are_the_sizes_run(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        pub, sizes = doc["published"], doc["sizes"]
        if "hidden_size" in pub:                 # BERT's config.json
            assert (pub["hidden_size"], pub["num_hidden_layers"],
                    pub["num_attention_heads"], pub["intermediate_size"],
                    pub["vocab_size"], pub["max_position_embeddings"]) == (
                sizes["hidden"], sizes["layers"], sizes["heads"],
                sizes["mlp_dim"], sizes["vocab_size"], sizes["max_seq"])
        else:                                    # GPT-2's
            assert (pub["n_embd"], pub["n_layer"], pub["n_head"],
                    pub["vocab_size"], pub["n_positions"]) == (
                sizes["hidden"], sizes["layers"], sizes["heads"],
                sizes["vocab_size"], sizes["max_seq"])


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    dirs = [os.path.join(ROOT, p) for p in manifest["paths"]]
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        cell = harness.load_cell(ROOT, w["name"])
        assert cell.mix["seq"] <= cell.config["sizes"]["max_seq"]
        assert cell.rows % (cell.mix["reference_rows_per_block"]
                            * cell.chips) == 0
        assert generator.find(w["traffic"], dirs)
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer


def test_metrics_and_their_readers(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"] for w in manifest["workloads"]}
    names = set()
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    dirs = [os.path.join(ROOT, p) for p in manifest["paths"]]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        reader = harness.load_metric(m["name"], dirs)
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert callable(reader.read)
        if m["name"].endswith(("_roofline", "mfu_pct")):
            assert m["unit"] == "%"


def test_every_file_under_paths_is_named_from_a_names_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel

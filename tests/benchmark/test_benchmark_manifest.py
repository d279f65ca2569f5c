"""``BENCHMARK.json`` against the contract it is written to, and against
the files it names."""

import json
import os
import re

import pytest

import manifest_rules
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


def _configs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["configs"]


def _file(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def test_config_entries(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert 1 <= len(files) <= 24 and len(set(files)) == len(files)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(key) for key in c["reduced"])


@pytest.mark.parametrize("entry", _configs(), ids=lambda c: c["name"])
def test_configs(manifest, entry):
    """One case a configuration: the file agrees with its manifest entry
    and with itself (``manifest_rules.config_file``)."""
    dirs = [os.path.join(ROOT, p) for p in manifest["paths"]]
    manifest_rules.config_file(_file(entry), entry, dirs)


@pytest.mark.parametrize("entry", _configs(), ids=lambda c: c["name"])
def test_published_sizes_are_the_sizes_run(entry):
    """One rule, no branch on a family: what the file publishes is what
    it runs, but for the keys it lists in ``reduced``, which run at less
    and keep to the floors (``manifest_rules.published_sizes``)."""
    manifest_rules.published_sizes(_file(entry))


@pytest.mark.parametrize("entry", [c for c in _configs() if not c["reduced"]],
                         ids=lambda c: c["name"])
def test_an_uncut_dense_file_keeps_the_fourfold_feed_forward(entry):
    """The two dense configurations' own: GPT-2 publishes no inner width
    (``n_inner`` null means four times the hidden size), BERT publishes
    exactly that."""
    sizes = _file(entry)["sizes"]
    assert sizes["mlp_dim"] == 4 * sizes["hidden"]


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

"""``model.head_ms``: device time a step under the program's ``bps.head``
scope. Its entry in the manifest, its reader in every cell, on a trace
made by hand (the head's three phases, and the gradient's scope inside
the forward), and on the traces recorded on the chip."""

import json
import os
import types

import pytest

from tinybench import ROOT

from benchmark import harness
from benchmark.trace import program

METRIC = "model.head_ms"
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_the_entry_lists_no_cells():
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "model",
                     "moves": "tokens_per_s_chip"}


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_reads_the_head(name):
    """Every cell's program opens ``bps.head``, so the entry has no
    ``workloads`` and each cell loads the reader."""
    cell = harness.load_cell(ROOT, name)
    assert METRIC in cell.per_layer
    reader = harness.load_metric(METRIC, cell.dirs)
    assert callable(reader.read)
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        "ms", "model", "tokens_per_s_chip", "device_trace")


def _trace(head="bps.head", steps=2):
    """A trace made by hand: a step runs the final norm, the head's
    product, the gradient formed beside it, what is left of the backward,
    and an attention half; times in ns."""
    ops, t = [], 0.0

    def op(name, path, ns):
        nonlocal t
        ops.append((name, path, t, t + ns))
        t += ns

    fwd = f"jit(step)/bps.model/jvp({head})/"
    bwd = f"jit(step)/bps.model/transpose(jvp({head}))/"
    for _ in range(steps):
        op("%fusion.1 = dot", "jit(step)/bps.model/jvp(bps.attn)/dot", 7e6)
        op("%fusion.2 = norm", fwd + "rsqrt", 5e5)
        op("%fusion.3 = dot", fwd + "while/body/dot_general", 8e6)
        op("%fusion.4 = dot",
           fwd + f"while/body/{head}.grad/dot_general", 1.6e7)
        op("%fusion.5 = mul", bwd + "mul", 2.5e5)
        op("%fusion.6 = dot", "jit(step)/bps.model/transpose(jvp(bps.model))/"
           f"rematted_computation/{head}/dot_general", 1e6)
    return program.Program("/device:TPU:0", (0.0, t), steps, ops, [], [],
                           "tf_op")


@pytest.mark.parametrize("name", CELLS[:1] + CELLS[-1:])
def test_the_reader_on_the_handmade_trace(monkeypatch, name):
    cell = harness.load_cell(ROOT, name)
    reader = harness.load_metric(METRIC, cell.dirs)
    run = types.SimpleNamespace(cell=cell, peaks=PEAKS, chips=[object()])
    trace = _trace()
    monkeypatch.setattr(program, "of_run", lambda run: trace)
    assert reader.read(run) == 0.5 + 8 + 16 + 0.25 + 1
    # the table of part x phase says where: the gradient's products stand
    # in the forward, under the head
    table = program.ns_by_part_and_phase(trace.ops)
    assert table["bps.head", "forward"] == 2 * (5e5 + 8e6 + 1.6e7)
    assert table["bps.head", "backward"] == 2 * 2.5e5
    assert table["bps.head", "remat"] == 2 * 1e6
    # a program from before the scope reports nothing, and does not raise
    bare = _trace(head="x")
    monkeypatch.setattr(program, "of_run", lambda run: bare)
    assert reader.read(run) is None
    monkeypatch.setattr(program, "of_run", lambda run: None)
    assert reader.read(run) is None


@pytest.mark.parametrize("chips", [1, 4])
def test_the_reader_on_the_recorded_trace(monkeypatch, chips):
    """The tiny MLM cell as the chip ran it (PR 25's fixtures): the head's
    time is what the part x phase table gives ``bps.head``, above nothing
    and under the step's busy time."""
    recorded = program.load_fixture(os.path.join(
        FIXTURES, f"tiny_trace_{chips}chip_scopes.json.gz"))
    monkeypatch.setattr(program, "of_run", lambda run: recorded)
    reader = harness.load_metric(METRIC, [os.path.join(ROOT, "benchmark")])
    run = types.SimpleNamespace(cell=None, chips=[object()], peaks=PEAKS)
    got = reader.read(run)
    table = program.ns_by_part_and_phase(recorded.ops)
    of_part = sum(ns for (part, _), ns in table.items() if part == "bps.head")
    assert got == pytest.approx(recorded.ms_per_step(of_part), rel=1e-9)
    assert 0 < got < 1e3 * recorded.busy_ns / 1e9 / recorded.steps

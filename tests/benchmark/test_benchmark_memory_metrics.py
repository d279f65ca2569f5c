"""The four per-layer metrics that read the program's own account of its
step's memory (``benchmark/trace/account.py``,
``benchmark/metrics/trainer.step_peak_gb.py`` and its neighbours; the
set-up record's ``step_memory`` and ``kept``), through the harness on the
CPU at a tiny size: reported in bytes that add up, the peak the harness's
own to the byte, the walk of the step's jaxpr made in a traced run alone,
and nothing where the program keeps no such record."""

import contextlib
import io
import json
import math
import time

import pytest

from tinybench import ROOT, write_tiny_benchmark

from benchmark import harness
from benchmark.trace import account

SEED = 2**31 + 57
STEP = ("trainer.step_peak_gb", "trainer.step_state_gb",
        "trainer.step_temp_gb")
NAMES = STEP + ("model.kept_gb",)


def _run(root, trace):
    """(result line, the ``account`` lines, walks of the step's jaxpr, the
    trainer's record) of one run."""
    import byteps_tpu as bps
    from byteps_tpu.common import kept_values
    from byteps_tpu.common.global_state import GlobalState
    walks, records, out = [], [], io.StringIO()
    walk, shutdown = kept_values.kept, bps.shutdown

    def counted(jaxpr):
        walks.append(jaxpr)
        return walk(jaxpr)

    def keep_the_record():      # the harness's last call: the state goes
        records.append(GlobalState._instance.setup_record)
        shutdown()

    with pytest.MonkeyPatch.context() as patch, \
            contextlib.redirect_stdout(out):
        patch.setattr(kept_values, "kept", counted)
        patch.setattr(bps, "shutdown", keep_the_record)
        result = harness.run_cell(root, "tiny_lm_cell", SEED, 0.3, trace,
                                  time.time(), require_chip=False)
    lines = [json.loads(x) for x in out.getvalue().splitlines()
             if x.startswith("{")]
    return (result, [x for x in lines if x.get("phase") == "account"],
            walks, records[0])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _run(write_tiny_benchmark(tmp_path_factory.mktemp("memory")),
                True)


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return _run(write_tiny_benchmark(tmp_path_factory.mktemp("memory0")),
                False)


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_is_reported_finite_and_positive_in_gb(traced, name):
    result = traced[0]
    assert result["correct"] is True
    got = result["metrics"][name]
    assert got["unit"] == "GB" and math.isfinite(got["value"])
    assert got["value"] > 0


def test_the_peak_is_the_harnesss_own_to_the_byte(traced):
    result, _, _, rec = traced
    sizes = rec["step_memory"]["step"]
    assert sizes["peak"] == result["device"]["memory_peak_bytes"]
    assert result["metrics"]["trainer.step_peak_gb"]["value"] * 1e9 == \
        pytest.approx(sizes["peak"], abs=0.5)


def test_the_parts_add_up(traced):
    result, _, _, rec = traced
    peak, state, temp = (result["metrics"][n]["value"] for n in STEP)
    assert state + temp <= peak
    sizes, kept = rec["step_memory"]["step"], rec["kept"]
    assert sizes["alias"] <= sizes["out"] and sizes["alias"] <= sizes["args"]
    # what the forward keeps for the backward lies in the temporaries
    assert 0 < kept["bytes"] < sizes["temp"]
    assert result["metrics"]["model.kept_gb"]["value"] * 1e9 == \
        pytest.approx(kept["bytes"], abs=0.5)


def test_a_traced_run_walks_the_jaxpr_once_and_prints_the_account(traced):
    _, lines, walks, rec = traced
    assert len(walks) == 1
    line, = lines
    assert line["kept"]["bytes"] == rec["kept"]["bytes"]
    assert line["step_memory"] == rec["step_memory"] and line["walk_s"] > 0
    # the tiny cell's blocks run under one scan, checkpointed: an input a
    # layer, by name, and the head's residuals beside them
    assert rec["kept"]["by_name"]["layer_input"][0] == 2
    assert set(rec["kept"]["by_scope"]) <= {
        "", "bps.model", "bps.embed", "bps.attn", "bps.mlp", "bps.head",
        "bps.head.grad"}


def test_an_untraced_run_never_walks(untraced):
    result, lines, walks, rec = untraced
    assert result["correct"] is True and not walks and not lines
    assert rec["kept"] is None and rec["step_memory"]["step"]["peak"] == \
        result["device"]["memory_peak_bytes"]
    assert not set(NAMES) & set(result["metrics"])


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_a_record_reads_as_nothing(monkeypatch, name):
    """What the parent commit's program looks like to these readers: no
    ``GlobalState.setup_record``, or one without the account's keys."""
    from byteps_tpu.common.global_state import GlobalState

    class Older:
        setup_record = {"closed": True, "spans": [], "compiles": [],
                        "fallbacks": {}, "step_funs": ("step",)}

    reader = harness.load_metric(name, [])
    for instance in (object(), Older()):
        monkeypatch.setattr(GlobalState, "_instance", instance)
        assert reader.read(None) is None


def test_an_open_record_reads_as_nothing(monkeypatch):
    from byteps_tpu.common.global_state import GlobalState

    class Holder:
        setup_record = {"closed": False, "step_funs": ("step",),
                        "step_memory": {"step": {"peak": 1, "args": 1}},
                        "kept": {"bytes": 1}, "trainer": None}

    monkeypatch.setattr(GlobalState, "_instance", Holder())
    assert account.step_gb("peak") is None and account.kept_gb() is None


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_each_metric_for_every_cell(name):
    with open(ROOT + "/BENCHMARK.json") as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "GB", "better": "lower",
                     "source": "program_counter",
                     "layer": name.split(".")[0],
                     "moves": "tokens_per_s_chip"}

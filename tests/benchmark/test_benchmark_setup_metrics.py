"""The seven per-layer metrics that read the program's own set-up record
(``benchmark/trace/setup.py``, ``benchmark/metrics/trainer.init_s.py`` and
its neighbours), through the harness on the CPU at a tiny size: reported
with finite values, no more than the harness's own laps around the same
work, and nothing where the program keeps no record."""

import json
import math
import time

import pytest

from benchmark import harness
from benchmark.trace import setup

SEED = 2**31 + 11
TIMES = ("trainer.init_s", "trainer.step_trace_s", "trainer.step_lower_s",
         "trainer.step_compile_s", "trainer.other_compile_s")
COUNTS = ("trainer.step_lowerings", "kernels.fallback_sites")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of a tiny cell: (result line, the ``setup`` line)."""
    import contextlib
    import io
    from tinybench import write_tiny_benchmark
    root = write_tiny_benchmark(tmp_path_factory.mktemp("setup_metrics"))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = harness.run_cell(root, "tiny_lm_cell", SEED, 0.3, True,
                                  time.time(), require_chip=False)
    lines = [json.loads(x) for x in out.getvalue().splitlines()
             if x.startswith("{")]
    laps = next(x for x in lines if x.get("phase") == "setup")
    return result, laps


@pytest.mark.parametrize("name", TIMES + COUNTS)
def test_each_metric_is_reported_with_a_finite_value(traced, name):
    result, _ = traced
    assert result["correct"] is True
    got = result["metrics"][name]
    assert math.isfinite(got["value"]) and got["value"] >= 0
    assert got["unit"] == ("count" if name in COUNTS else "s")
    if name in ("trainer.init_s", "trainer.step_trace_s",
                "trainer.step_lower_s", "trainer.step_compile_s"):
        assert got["value"] > 0


def test_the_times_are_no_more_than_the_laps_around_them(traced):
    """The constructor lies in ``trainer_s``, the step's lowering (the
    harness's own call of ``lower``) in ``lower_s``, the first step in
    ``check_steps_s``; the laps hold the harness's work besides."""
    result, laps = traced
    total = sum(result["metrics"][name]["value"] for name in TIMES)
    assert 0 < total <= (laps["trainer_s"] + laps["lower_s"]
                         + laps["check_steps_s"])


def test_the_harness_lowering_is_the_one_the_first_step_runs(traced):
    result, _ = traced
    assert result["metrics"]["trainer.step_lowerings"]["value"] == 1.0


def test_no_fall_back_is_counted_off_the_tpu(traced):
    """The tiny cell's attention takes XLA's form here, as every kernel
    site does on the CPU: a choice, and no fall-back."""
    result, _ = traced
    assert result["metrics"]["kernels.fallback_sites"]["value"] == 0.0


@pytest.mark.parametrize("name", TIMES + COUNTS)
def test_a_program_without_a_record_reads_as_nothing(monkeypatch, name):
    """What the parent commit's program looks like to these readers: no
    ``GlobalState.setup_record``. Each returns None and does not raise."""
    from byteps_tpu.common.global_state import GlobalState
    monkeypatch.setattr(GlobalState, "_instance", object())
    assert setup.record() is None
    reader = harness.load_metric(name, [])
    assert reader.read(None) is None


def test_an_open_record_reads_as_nothing(monkeypatch):
    from byteps_tpu.common.global_state import GlobalState

    class Holder:
        setup_record = {"closed": False, "spans": [], "compiles": [],
                        "fallbacks": {}}

    monkeypatch.setattr(GlobalState, "_instance", Holder())
    assert setup.record() is None and setup.span_s("bps.setup.init") is None

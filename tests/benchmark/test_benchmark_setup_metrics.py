"""The seven per-layer metrics that read the program's own set-up record
(``benchmark/trace/setup.py``, ``benchmark/metrics/trainer.init_s.py`` and
its neighbours), through the harness on the CPU at a tiny size: reported
with finite values, no more than the harness's own laps around the same
work, and nothing where the program keeps no record. And ``setup_s``
itself (PR 50): the sum of the laps that lines of this repository own,
which a lap named as outside does not move, every lap still printed."""

import contextlib
import io
import json
import math
import os
import subprocess
import sys
import time

import pytest

from tinybench import ROOT, write_tiny_benchmark

from benchmark import harness
from benchmark.trace import setup

SEED = 2**31 + 11
INSIDE = ("imports_py_s", "weights_s", "trainer_s", "lower_s",
          "check_steps_s", "warm_s")
OUTSIDE = ("runtime_s", "reference_s", "step_text_s")
TIMES = ("trainer.init_s", "trainer.step_trace_s", "trainer.step_lower_s",
         "trainer.step_compile_s", "trainer.other_compile_s")
COUNTS = ("trainer.step_lowerings", "kernels.fallback_sites")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of a tiny cell: (result line, the ``setup`` line)."""
    root = write_tiny_benchmark(tmp_path_factory.mktemp("setup_metrics"))
    return _run(root, True, time.time())


def _run(root, trace, t_start):
    """(result line, the ``setup`` line, the ``cell`` line) of one run."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = harness.run_cell(root, "tiny_lm_cell", SEED, 0.3, trace,
                                  t_start, require_chip=False)
    lines = [json.loads(x) for x in out.getvalue().splitlines()
             if x.startswith("{")]
    laps = next(x for x in lines if x.get("phase") == "setup")
    laps["cell_line"] = next(x for x in lines if x.get("phase") == "cell")
    return result, laps


@pytest.fixture(scope="module")
def slow_runtime(tmp_path_factory):
    """One untraced run whose look for the devices takes 2 s longer: as if
    the TPU runtime had started that much later."""
    root = write_tiny_benchmark(tmp_path_factory.mktemp("setup_late"))
    real = harness.open_mesh

    def slow_start(cell, require_chip):
        time.sleep(2.0)
        return real(cell, require_chip)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "open_mesh", slow_start)
        return _run(root, False, time.time())


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


# ------------------------------------------------- setup_s itself (PR 50)

def test_the_harness_names_the_laps_outside_as_written_here():
    assert harness.OUTSIDE_LAPS == OUTSIDE


def test_setup_s_is_the_sum_of_the_laps_that_define_it(slow_runtime):
    result, laps = slow_runtime
    assert result["correct"] is True
    inside = sum(laps[name] for name in INSIDE)
    assert result["metrics"]["setup_s"] == {
        "value": pytest.approx(inside, abs=1e-9), "unit": "s"}
    assert laps["setup_s"] == result["metrics"]["setup_s"]["value"]
    assert all(laps[name] > 0 for name in INSIDE)


def test_a_traced_run_prints_the_same_sum(traced):
    _, laps = traced
    assert laps["setup_s"] == pytest.approx(
        sum(laps[name] for name in INSIDE), abs=1e-9)


@pytest.mark.parametrize("name", INSIDE + OUTSIDE)
def test_every_lap_is_still_on_the_setup_line(slow_runtime, name):
    _, laps = slow_runtime
    assert math.isfinite(laps[name]) and laps[name] >= 0
    assert set(laps) == {"phase", "cell_line", "setup_s",
                         "process_to_window_s", *INSIDE, *OUTSIDE}


def test_a_lap_named_as_outside_does_not_move_setup_s(slow_runtime, traced):
    """The 2 s lie in ``runtime_s`` and in the old reading, process start
    to window less the reference, and not in ``setup_s``."""
    _, laps = slow_runtime
    assert laps["runtime_s"] > 2.0 > traced[1]["runtime_s"]
    assert laps["process_to_window_s"] == pytest.approx(
        laps["setup_s"] + laps["runtime_s"] + laps["step_text_s"], abs=1e-9)
    assert laps["process_to_window_s"] - laps["setup_s"] > 2.0
    cell = laps["cell_line"]
    assert cell["imports_s"] == cell["imports_py_s"] + cell["runtime_s"]
    assert (cell["imports_py_s"], cell["runtime_s"]) == (
        laps["imports_py_s"], laps["runtime_s"])


@pytest.mark.parametrize("name", OUTSIDE)
def test_the_sum_leaves_out_each_lap_named_outside(name):
    laps = harness.Laps(0.0)
    laps.seconds = {lap: 1.0 for lap in INSIDE + OUTSIDE}
    before = laps.total(harness.OUTSIDE_LAPS)
    laps.seconds[name] += 50.0
    assert laps.total(harness.OUTSIDE_LAPS) == before == len(INSIDE)
    assert laps.total() == len(INSIDE + OUTSIDE) + 50.0


# What Python's imports pull in before the runtime starts (the lap
# ``imports_py_s``, the first of ``setup_s``): ``jax`` (with numpy and its
# own dependencies), then these, and no third party's package. A heavy
# import added to either package fails here, without a chip.
BYTEPS_TPU_IMPORTS = {
    "byteps_tpu", "byteps_tpu.common", "byteps_tpu.common.config",
    "byteps_tpu.common.global_state", "byteps_tpu.common.logging",
    "byteps_tpu.common.naming", "byteps_tpu.common.partition",
    "byteps_tpu.common.setup_record", "byteps_tpu.parallel",
    "byteps_tpu.parallel.collectives", "byteps_tpu.parallel.mesh",
    "byteps_tpu.version"}
BENCHMARK_IMPORTS = {
    "benchmark", "benchmark.correct", "benchmark.flops",
    "benchmark.generator", "benchmark.harness", "benchmark.kernel_counts",
    "benchmark.trace", "benchmark.trace.program", "benchmark.trace.reduce",
    "benchmark.trace.xspace"}
IMPORTS_PROBE = """
import json, sys
import jax
before = set(sys.modules)
import byteps_tpu, byteps_tpu.parallel.mesh
from benchmark import harness
print(json.dumps(sorted(set(sys.modules) - before)))
"""


def test_the_first_imports_pull_in_no_module_beyond_the_list():
    out = subprocess.run(
        [sys.executable, "-c", IMPORTS_PROBE], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, check=True)
    new = set(json.loads(out.stdout.splitlines()[-1]))
    ours = {m for m in new if m.split(".")[0] in ("byteps_tpu", "benchmark")}
    assert ours == BYTEPS_TPU_IMPORTS | BENCHMARK_IMPORTS
    # beside them the standard library alone: no optax, flax, pallas or
    # model family before the look for the devices
    assert {m.split(".")[0] for m in new - ours} <= set(
        sys.stdlib_module_names)

"""The harness end to end on the CPU at a tiny size: a benchmark made of
new files only runs unchanged; a broken timed path comes out as not
correct; without a TPU the command prints no result."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from tinybench import ROOT, write_tiny_benchmark

from benchmark import harness

SEED = 2**31 + 5


def _run(root, cell, trace=False, seconds=0.3):
    return harness.run_cell(root, cell, SEED, seconds, trace, time.time(),
                            require_chip=False)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", ["tiny_mlm_cell", "tiny_lm_cell",
                                  "tiny_cut_cell"])
def test_cell_config_and_metric_added_as_new_files_only(tiny_root, cell,
                                                        trace, capsys):
    """``tiny_root`` holds a manifest, three configurations (one of them
    cut, with counts of its own in a file it names), two traffic mixes
    and one per-layer metric that the repo does not have; nothing under
    ``benchmark/`` was edited to run them."""
    result = _run(tiny_root, cell, trace)
    assert result["correct"] is True, capsys.readouterr().out
    assert result["failed"] == 0 and result["attempted"] > 3
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    got = result["metrics"]
    if not trace:
        assert set(got) == {"tokens_per_s_chip", "step_ms_p95", "setup_s"}
        assert all(m["value"] > 0 for m in got.values())
    else:
        assert got["trainer.steps_traced"] == {
            "value": float(result["attempted"]), "unit": "steps"}
        assert {"input.wait_ms", "trainer.dispatch_ms"} <= set(got)
        # read from the device trace or the peaks: nothing to read here
        assert not {"device.idle_pct", "kernels.pallas_ms",
                    "model.mfu_pct"} & set(got)
        # the metric that lists its cells is reported in those alone
        assert ("trainer.vs_plain_pct" in got) == (cell == "tiny_mlm_cell")
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    checks = {x["check"]: x for x in lines if x.get("phase") == "check"}
    assert {"loss_rel", "grad_norm_rel", "change_norm_rel",
            "compiles_in_window"} <= set(checks)
    assert all("limit" in c and "value" in c for c in checks.values())
    assert checks["compiles_in_window"]["value"] == 0
    # the result line carries them too, each beside its limit, and last
    assert result["checks"] == {name: {"value": c["value"],
                                       "limit": c["limit"]}
                                for name, c in checks.items()}


def _freeze_the_step(monkeypatch):
    from byteps_tpu.training import DistributedTrainer

    def build(self, donate):
        loss_fn = self._loss_fn
        return jax.jit(lambda p, s, b: (p, s, loss_fn(p, b)))

    monkeypatch.setattr(DistributedTrainer, "_build_step", build)


def _halve_the_batch(monkeypatch):
    real = harness.build_program

    def build(cell):
        cfg, loss_fn, tx = real(cell)

        def half(p, batch):
            n = cell.mix["batch_per_chip"] // 2
            return loss_fn(p, jax.tree_util.tree_map(lambda x: x[:n], batch))

        return cfg, half, tx

    monkeypatch.setattr(harness, "build_program", build)


def _drop_the_exchange(monkeypatch):
    import byteps_tpu.training as training
    monkeypatch.setattr(training, "distributed_optimizer",
                        lambda tx, **kw: tx)


@pytest.mark.parametrize("chips,breakage,fails", [
    (1, _freeze_the_step, {"grad_norm_rel", "change_norm_rel"}),
    (1, _halve_the_batch, {"grad_norm_rel"}),
    (4, _drop_the_exchange, {"grad_norm_rel"}),
])
def test_broken_timed_path_comes_out_not_correct(tmp_path, monkeypatch,
                                                 capsys, chips, breakage,
                                                 fails):
    """The rest of a run, driven as ``run.py`` drives it but for the look
    for a chip, with the trainer's step broken underneath."""
    root = write_tiny_benchmark(tmp_path, chips=chips)
    assert _run(root, "tiny_mlm_cell")["correct"] is True
    capsys.readouterr()
    breakage(monkeypatch)
    result = _run(root, "tiny_mlm_cell")
    assert result["correct"] is False
    assert result["failed"] == 0            # every step ran: wrong, not dead
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    failed = {x["check"] for x in lines
              if x.get("phase") == "check" and not x["ok"]}
    assert fails <= failed


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "bert_large_s512_1chip", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "needs 1 TPU chip" in out.stderr
    assert '"correct"' not in out.stdout and '"metrics"' not in out.stdout


def test_an_unknown_workload_is_an_error(tiny_root):
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(tiny_root, "no_such_cell")

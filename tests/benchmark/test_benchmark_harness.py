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


def _name_the_step_must_hold(root, names):
    path = os.path.join(root, "tinybench", "configs", "tiny_mlm.json")
    with open(path) as f:
        doc = json.load(f)
    doc["program"]["step_must_contain"] = names
    with open(path, "w") as f:
        json.dump(doc, f)


@pytest.mark.parametrize("names,correct", [
    (["ENTRY"], True),
    (["ENTRY", "bps_flash_fwd"], False),
])
def test_a_listed_name_missing_from_the_steps_text_is_not_correct(
        tmp_path, monkeypatch, capsys, names, correct):
    """The rest of a run as on the chip but for the look for one: every
    name of ``step_must_contain`` is searched in the compiled step's text,
    which here holds an ``ENTRY`` and no kernel."""
    root = write_tiny_benchmark(tmp_path)
    _name_the_step_must_hold(root, names)
    real = harness.open_mesh
    monkeypatch.setattr(harness, "open_mesh",
                        lambda cell, require_chip: real(cell, False))
    result = harness.run_cell(root, "tiny_mlm_cell", SEED, 0.3, False,
                              time.time(), require_chip=True)
    assert result["correct"] is correct, capsys.readouterr().out
    assert result["failed"] == 0
    assert {k: v["value"] for k, v in result["checks"].items()
            if k.startswith("step_contains:")} == {
                "step_contains:" + n: int(n == "ENTRY") for n in names}


def test_a_name_is_searched_whole(capsys):
    """``bps_gmm_dx`` does not stand for ``bps_gmm``, an instruction's
    number and an ``op_name``'s slashes end a name; the line before the
    rows counts the custom calls by the kernel's name."""
    text = ('%bps_gmm_dx.3 = bf16[8] custom-call(), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(step)/bps.moe/'
            'bps_gmm_dx/pallas_call"}\n%bps_flash_fwd = f32[] custom-call(),'
            ' metadata={op_name="jit(step)/bps_flash_fwd/pallas_call"}\n'
            '%fusion.1 = f32[] fusion(%bps_flash_fwd), metadata={op_name='
            '"jit(step)/bps_flash_fwd/pallas_call"}')   # a consumer's line
    rows = harness.step_text_checks(
        text, ["tpu_custom_call", "bps_gmm", "bps_gmm_dx", "bps_flash_fwd"])
    assert [(r["check"], r["value"], r["ok"]) for r in rows] == [
        ("step_contains:tpu_custom_call", 1, True),
        ("step_contains:bps_gmm", 0, False),
        ("step_contains:bps_gmm_dx", 1, True),
        ("step_contains:bps_flash_fwd", 1, True)]
    assert all(r["limit"] == 1 for r in rows)
    assert json.loads(capsys.readouterr().out) == {
        "phase": "step_kernels", "bps_gmm_dx": 1, "bps_flash_fwd": 1}


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

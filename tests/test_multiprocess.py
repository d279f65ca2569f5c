"""Two real JAX processes over a localhost coordinator — the analog of
the reference's meta_test.py strategy (SURVEY §4: same binaries, real
rendezvous/collectives, one machine, no cluster).

Both tests drive the launcher's command-fleet path
(``launcher.fleet.run_command_fleet``): the coordinator/rank env
contract is DERIVED, the processes are supervised, and per-rank output
is captured per role — no hand-rolled Popen choreography.

Cross-process computations on the CPU backend need a collectives
implementation; on the supported jax (0.9.0) the public
``jax.config.jax_cpu_collectives_implementation`` defaults to ``gloo``,
so ``bps.init()`` sets nothing.
"""

import os
import sys

import pytest


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_two_process_training_localhost():
    from byteps_tpu.launcher.fleet import run_command_fleet

    worker = os.path.join(ROOT, "tests", "_mp_worker.py")
    # The coordinator port comes from a held-open PortLease, which
    # closes ONE stray-dialer vector (a recycled coordinator port).
    # gloo's pair listeners still bind their own ephemeral ports that
    # nothing can lease, so a lingering redial thread elsewhere in the
    # suite process can still land a PS frame on one and SIGABRT that
    # rank ("op.preamble.length <= op.nbytes") — observed ~1/600 suite
    # runs. Retry ONCE on that exact signature (a rank dead at -6, its
    # peer torn down by the supervisor); anything else fails first try.
    for attempt in (0, 1):
        results = run_command_fleet([sys.executable, worker],
                                    num_processes=2, local_devices=2,
                                    timeout_s=240)
        assert len(results) == 2
        if attempt == 0 and any(r.rc == -6 for r in results):
            continue
        for res in results:
            assert res.rc == 0, f"{res.name} failed:\n{res.output[-4000:]}"
            assert "MP_WORKER_OK" in res.output, res.output[-2000:]
        return


@pytest.mark.slow  # ~68 s of interpreter spawns — the single largest
# tier-1 wall item against the 870 s verify budget (the PR-16 trim
# precedent); the 2-process rendezvous path stays tier-1 above
def test_multiprocess_weak_scaling_2_and_4_procs():
    """Drive the emulated-cluster weak-scaling harness with REAL 2- and
    4-process runs over a (dcn) mesh: both must rendezvous, train, and
    report throughput. (Efficiency thresholds are meaningless on a
    shared-CPU box — N processes split one core, so the ceiling is 1/N —
    the assertion is that the multi-process path works end to end.)"""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "scaling_bench", os.path.join(ROOT, "examples", "scaling_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    try:
        spec.loader.exec_module(sb)
        for n in (2, 4):
            sps = sb.run_multiprocess(n, "bert-tiny", prb=2, seq=32,
                                      iters=2, timeout=420)
            assert sps > 0, (n, sps)
    finally:
        sys.path.remove(os.path.join(ROOT, "examples"))

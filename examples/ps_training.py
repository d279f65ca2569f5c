"""Full PS deployment demo on one machine: a standalone reduction
server process plus N independent worker processes (local meshes, no
collectives between workers) — the reference's worker/server
architecture (reference: docs/step-by-step-tutorial.md distributed mode;
byteps.server role).

Run:  python examples/ps_training.py [--workers 2] [--steps 30]

The driver (this script) starts `bpslaunch-tpu --server`, then launches
the workers with BPS_ENABLE_PS/BPS_SERVER_ADDRS set; each worker trains
a small model with DistributedTrainer — which detects the PS deployment
itself — syncing only through the TCP host service. Flags:
--async-mode (weight-delta async-SGD, no barrier) and --compress
(topk + error-feedback compressed wire).
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

import _bootstrap  # noqa: F401

WORKER_SNIPPET = r"""
import os, sys
sys.path.insert(0, os.path.join(os.environ["BPS_REPO_ROOT"], "examples"))
import _bootstrap  # repo root on sys.path + compile cache
import jax
import numpy as np
import optax
import byteps_tpu as bps
from byteps_tpu.training import DistributedTrainer

wid = int(os.environ["BPS_WORKER_ID"])
steps = int(os.environ["DEMO_STEPS"])
bps.init()
W = np.random.RandomState(0).randn(8, 1).astype(np.float32)

def loss_fn(p, b):
    x, y = b
    return ((x @ p["w"] - y) ** 2).mean()

# the trainer detects BPS_ENABLE_PS / BPS_ENABLE_ASYNC and picks the
# right split itself: jitted grads -> host-service hop -> jitted update
# (sync), or local optimizer step -> weight-delta push -> fresh pull
# (async). Compression kwargs ride the PS wire when given.
compression = None
if os.environ.get("DEMO_COMPRESS") == "1":
    compression = {"compressor_type": "topk", "compressor_k": "0.5",
                   "ef_type": "vanilla"}
tr = DistributedTrainer(loss_fn, {"w": np.zeros((8, 1), np.float32)},
                        optax.sgd(0.05), compression=compression,
                        min_compress_bytes=0 if compression else None)
rng = np.random.RandomState(10 + wid)     # each worker: its OWN data shard
for step in range(steps):
    x = rng.randn(64, 8).astype(np.float32)
    loss = tr.step((x, x @ W))   # returned loss: printed in the summary
err = float(np.abs(np.asarray(tr.params["w"]) - W).max())
mode = "async" if os.environ.get("BPS_ENABLE_ASYNC") == "1" else "sync"
print(f"worker {wid}: {mode} PS training done, final loss "
      f"{float(loss):.5f}, max weight err {err:.5f}")
bps.shutdown()
"""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--async-mode", action="store_true",
                    help="async-SGD: weight-delta push, no worker barrier")
    ap.add_argument("--compress", action="store_true",
                    help="topk+error-feedback compressed PS wire")
    args = ap.parse_args()
    if args.async_mode and args.compress:
        ap.error("--compress is incompatible with --async-mode (the async "
                 "server folds raw weight deltas)")

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    server_env = dict(os.environ, BPS_SERVER_PORT=str(port),
                      BPS_NUM_PROCESSES=str(args.workers))
    if args.async_mode:
        server_env["BPS_ENABLE_ASYNC"] = "1"
    server = subprocess.Popen(
        [sys.executable, "-m", "byteps_tpu.launcher.launch", "--server"],
        env=server_env, cwd=root)
    workers = []
    try:
        # wait until the server actually listens (it has to import the
        # package first) — workers have no connect retry
        import time
        deadline = time.time() + 60
        while True:
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=1).close()
                break
            except OSError:
                if time.time() > deadline:
                    raise SystemExit("server never came up")
                time.sleep(0.3)
        for wid in range(args.workers):
            env = dict(os.environ,
                       BPS_REPO_ROOT=root,
                       BPS_ENABLE_PS="1",
                       BPS_SERVER_ADDRS=f"127.0.0.1:{port}",
                       BPS_NUM_WORKER=str(args.workers),
                       BPS_WORKER_ID=str(wid),
                       DEMO_STEPS=str(args.steps))
            if args.async_mode:
                env["BPS_ENABLE_ASYNC"] = "1"
            if args.compress:
                env["DEMO_COMPRESS"] = "1"
            workers.append(subprocess.Popen(
                [sys.executable, "-c", WORKER_SNIPPET], env=env, cwd=root))
        rc = 0
        for w in workers:
            rc = w.wait() or rc
        if rc:
            raise SystemExit(f"a worker failed (rc={rc})")
        print(f"PS deployment demo OK: {args.workers} workers x "
              f"{args.steps} steps through the TCP host service")
    finally:
        for w in workers:
            if w.poll() is None:
                w.terminate()
        for w in workers:
            try:
                w.wait(timeout=10)
            except subprocess.TimeoutExpired:
                w.kill()
        server.terminate()
        server.wait(timeout=15)


if __name__ == "__main__":
    main()

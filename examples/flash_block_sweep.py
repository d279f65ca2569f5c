"""Flash-kernel block-shape autotune on the REAL chip.

Times the full flagship train step (the honest objective — a
single-dispatch kernel microbench mostly times the dispatch) for a grid of
(block_q, block_k) and head-tile overrides, at both flagship head
geometries:

  - d_head 64  (BERT-large reference headline, 16 heads)
  - d_head 128 (same FLOPs, 8 heads — the MXU-filling variant)

VERDICT r4 #1 asked for exactly this sweep at d=128 (previous sweeps
only covered d=64, split kernels) and a re-sweep at d=64 now that the
backward is the fused single-block kernel.

Usage: python examples/flash_block_sweep.py [--iters 8] [--quick]
Prints one row per config + a JSON summary of the best per geometry.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--warm", type=int, default=2)
    ap.add_argument("--quick", action="store_true",
                    help="only (512,512) and (256,256)")
    args = ap.parse_args()

    import jax
    from bench import make_plain_step, mlm_setup, time_plain_steps
    from byteps_tpu.models import bert

    blocks = ([(512, 512), (256, 256)] if args.quick else
              [(512, 512), (512, 256), (256, 512), (256, 256),
               (128, 128)])
    hts = [0]            # 0 = auto; explicit values added per geometry

    results = {}
    for name, cfg in (
            ("d64", bert.bert_large(max_seq=512)),
            ("d128", dataclasses.replace(bert.bert_large(max_seq=512),
                                         heads=8))):
        rows = []
        for (bq, bk) in blocks:
            for ht in hts + ([2, 4] if name == "d128" else [4, 8]):
                os.environ["BPS_FLASH_BQ"] = str(bq)
                os.environ["BPS_FLASH_BK"] = str(bk)
                if ht:
                    os.environ["BPS_FLASH_HT"] = str(ht)
                else:
                    os.environ.pop("BPS_FLASH_HT", None)
                params = data = None
                try:
                    params, data, loss_fn = mlm_setup(cfg, 64, 512)
                    sps = time_plain_steps(params, data, loss_fn, 64,
                                           args.iters, args.warm)
                except Exception as e:   # noqa: BLE001 — a bad tile is a
                    sps = 0.0            # data point, not a crash
                    print(f"{name} bq={bq} bk={bk} ht={ht or 'auto'}: "
                          f"FAILED {type(e).__name__}: {e}"[:160],
                          flush=True)
                    continue
                finally:
                    # failure path too: a retained params copy would
                    # OOM every subsequent config
                    del params, data
                    gc.collect()
                rows.append({"bq": bq, "bk": bk, "ht": ht or "auto",
                             "sps": round(sps, 2)})
                print(f"{name} bq={bq} bk={bk} ht={ht or 'auto'}: "
                      f"{sps:.2f} samples/s", flush=True)
        best = max(rows, key=lambda r: r["sps"]) if rows else None
        results[name] = {"rows": rows, "best": best}
    for k in ("BPS_FLASH_BQ", "BPS_FLASH_BK", "BPS_FLASH_HT"):
        os.environ.pop(k, None)
    print(json.dumps({"metric": "flash_block_sweep",
                      "best_d64": results["d64"]["best"],
                      "best_d128": results["d128"]["best"]}))


if __name__ == "__main__":
    main()

"""Critical-path attribution: the step-time blame engine.

The registry says how slow each stage is on average; the trace says
when every span ran. Neither answers the operator's actual question:
*what did this step's wall time consist of, and which key / worker /
hop gated it?* This module walks the merged per-step span DAG — worker
timeline spans (bwd-seg → pack → compress → push → pull → decompress →
H2D → apply, plus PP act hops, param-mailbox fetches, and the
cross-step admission gate), the SERVER's per-(key, round) span records
(obs/spans.py, re-based onto the worker timebase by the clock-offset
estimate), and the wire scheduler's admission trace — and extracts the
BLOCKING CHAIN: starting from the span that ends the step, repeatedly
step to the latest-running span that precedes it. Every instant of the
step window lands in exactly one chain segment (or an explicit gap),
and each segment is attributed to a category:

  ============== ====================================================
  compute        model fwd/bwd segments, jit dispatch
  d2h / h2d      device↔host copies
  host           pack/unpack + codec encode/decode CPU
  wire           socket time of push/pull/act/param frames
  server_queue   merged round published late (sum / engine backlog):
                 pull span ∩ [last arrival, first serve end]
  straggler      merge-wait on a slow worker's push: pull span ∩
                 [first arrival, num_workers-th arrival], blamed on
                 the LAST arrival's worker id
  absorbed       bounded-staleness carve (BPS_MAX_LAG>1): a SEALED
                 round's grace wait, plus the merge-wait the seal
                 AVOIDED — the missing worker's eventual arrival
                 minus the sealed serve. At K=1 no round ever seals,
                 so this is always zero and straggler keeps the blame
  admission      the cross-step per-key admission gate (PS_XSTEP_GATE)
  credit         wire-scheduler credit wait carved out of push spans
  apply          optimizer apply
  gap / other    untraced wall / unmapped stages
  ============== ====================================================

Consumed three ways: ``crit/*`` registry gauges + a per-step ``crit``
block in StepStats (obs/stats.py, trace window only), the slow-step
auto-capture's postmortem, and the CLI report::

    python -m byteps_tpu.obs.critpath <trace_dir> [--rank R] [--step N]

The decomposition of a pull span only happens when a server record for
its (key, round) is visible — in-process rings feed it automatically,
remote shards via the fleet scraper's OP_TRACE scrape; without one the
whole pull span is honestly ``wire``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

SCHEMA = "byteps_tpu.CritPath/v1"

# stage → category (stages outside this map count as "other")
CAT_BY_STAGE: Dict[str, str] = {
    "DISPATCH": "compute", "REDUCE": "compute", "REDUCE_WAIT": "compute",
    "PS_BWD_SEG": "compute", "PP_FWD_SEG": "compute",
    "PP_BWD_SEG": "compute",
    "PS_D2H": "d2h", "COPYD2H": "d2h",
    "PS_PACK": "host", "PS_UNPACK": "host", "PS_COMPRESS": "host",
    "PS_COMPRESS_DEV": "host", "PS_DECOMPRESS": "host",
    "PS_PUSH": "wire", "PS_PULL": "wire", "PUSH_PULL": "wire",
    "PS_PUSH_PULL": "wire",
    "PP_ACT_SEND": "wire", "PP_ACT_RECV": "wire",
    "PS_PARAM_PUT": "wire", "PS_PARAM_GET": "wire",
    "PS_H2D": "h2d", "PS_APPLY_CHUNK": "apply",
    "PS_XSTEP_GATE": "admission", "CREDIT_BLOCK": "credit",
}

_EPS_US = 1.0     # sub-microsecond slack: ts are integer microseconds


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


class _Span:
    __slots__ = ("start", "end", "stage", "key", "round", "decl")

    def __init__(self, e: dict) -> None:
        args = e.get("args") or {}
        self.start = float(e.get("ts", 0))
        self.end = self.start + float(e.get("dur", 0))
        self.stage = e.get("name", "")
        self.key = int(e.get("pid", 0))
        self.round = args.get("round")
        self.decl = args.get("name", "")


def _server_index(server_spans, t0_s: float) -> Dict[Tuple, dict]:
    """{(key, round): windows in event-relative µs} from server span
    records (wall-clock seconds, WORKER timebase — already re-based by
    the clock offset)."""
    idx: Dict[Tuple, dict] = {}
    for r in server_spans or ():
        first, complete = r.get("first_t"), r.get("complete_t")
        if first is None:
            continue
        win = {"first": (first - t0_s) * 1e6,
               "complete": (None if complete is None
                            else (complete - t0_s) * 1e6),
               "serve_end": None, "blame": None,
               "sealed": bool(r.get("sealed")),
               "missing": tuple(r.get("missing") or ())}
        serves = r.get("serves") or ()
        if serves:
            s0 = min(serves, key=lambda s: s["t"])
            win["serve_end"] = (s0["t"] + s0["dur"] - t0_s) * 1e6
        arrivals = r.get("arrivals") or ()
        if arrivals:
            last = max(arrivals, key=lambda a: a["t"])
            win["blame"] = last.get("w", 0)
        idx[(int(r.get("key", 0)), int(r.get("round", 0)))] = win
    return idx


def _sched_index(sched_trace, t0_s: float) -> Dict[int, List[Tuple]]:
    """{key: [(a_us, b_us)]} credit-wait intervals from the wire
    scheduler's admission trace (entries carry a wall ``t`` admit stamp
    since the trace plane landed; older entries without one are
    skipped)."""
    idx: Dict[int, List[Tuple]] = {}
    for e in sched_trace or ():
        t, w = e.get("t"), float(e.get("wait_s", 0.0))
        if t is None or w <= 1e-6:
            continue
        b = (t - t0_s) * 1e6
        idx.setdefault(int(e.get("key", 0)), []).append((b - w * 1e6, b))
    return idx


def _add(cats: Dict[str, float], cat: str, us: float) -> None:
    if us > 0:
        cats[cat] = cats.get(cat, 0.0) + us


def _attribute_segment(s: _Span, a: float, b: float, srv: Dict,
                       sched: Dict, cats: Dict[str, float],
                       blame: Dict[int, float]) -> Dict[str, float]:
    """Split one chain segment [a, b] of span ``s`` into categories;
    returns the segment's own breakdown (for the chain listing)."""
    seg: Dict[str, float] = {}
    cat = CAT_BY_STAGE.get(s.stage, "other")
    if s.stage == "PS_PULL" and s.round is not None:
        win = srv.get((s.key, int(s.round)))
        if win is not None:
            first = win["first"]
            complete = win["complete"]
            if complete is not None:
                strag = _overlap(a, b, first, complete)
                if strag > 0 and win.get("sealed"):
                    # the round published WITHOUT the missing worker:
                    # this chain time is the bounded-staleness grace,
                    # not a merge-wait on anyone — no straggler blame
                    _add(seg, "absorbed", strag)
                elif strag > 0:
                    _add(seg, "straggler", strag)
                    if win["blame"] is not None:
                        blame[win["blame"]] = \
                            blame.get(win["blame"], 0.0) + strag
                q_end = win["serve_end"]
                if q_end is not None:
                    _add(seg, "server_queue",
                         _overlap(a, b, complete, q_end))
            covered = sum(seg.values())
            _add(seg, "wire", max(0.0, (b - a) - covered))
        else:
            _add(seg, "wire", b - a)
    elif s.stage == "PS_PUSH":
        credit = sum(_overlap(a, b, c0, c1)
                     for c0, c1 in sched.get(s.key, ()))
        _add(seg, "credit", min(credit, b - a))
        _add(seg, "wire", max(0.0, (b - a) - min(credit, b - a)))
    else:
        _add(seg, cat, b - a)
    for c, us in seg.items():
        _add(cats, c, us)
    return seg


def attribute(events: List[dict], server_spans: Optional[List[dict]] = None,
              sched_trace: Optional[List[dict]] = None,
              step: Optional[int] = None, t0: float = 0.0,
              max_chain: int = 2048) -> Optional[dict]:
    """Blocking-chain attribution of one step's span set.

    ``events``: Chrome-trace X events (ts/dur in µs relative to the
    timeline's t0). ``server_spans``: obs.spans records in WALL seconds
    on the worker timebase (``t0`` — the timeline's wall-clock base —
    maps them into event space). ``step``: restrict to events carrying
    that trace step tag (None = the whole snapshot as one window).
    Returns None when no spans qualify."""
    spans = []
    for e in events:
        if e.get("ph") not in (None, "X"):
            continue
        if step is not None and (e.get("args") or {}).get("step") != step:
            continue
        s = _Span(e)
        if s.end > s.start:
            spans.append(s)
    if not spans:
        return None
    srv = _server_index(server_spans, t0)
    sched = _sched_index(sched_trace, t0)
    t_start = min(s.start for s in spans)
    t_end = max(s.end for s in spans)
    cats: Dict[str, float] = {}
    blame: Dict[int, float] = {}
    key_us: Dict[int, float] = {}
    chain: List[dict] = []
    cursor = t_end
    truncated = False
    # backward sweep: at each point, the chain continues through the
    # span that was still running latest before the cursor; time nobody
    # covers is an explicit gap. Each chosen span moves the cursor to
    # its own start, so segments tile the window exactly once.
    while cursor > t_start + _EPS_US:
        if len(chain) >= max_chain:
            truncated = True
            break
        cands = [s for s in spans if s.start < cursor - _EPS_US]
        if not cands:
            break
        s = max(cands, key=lambda s: (min(s.end, cursor), -s.start))
        top = min(s.end, cursor)
        if top < cursor - _EPS_US:
            _add(cats, "gap", cursor - top)
            chain.append({"stage": "(gap)", "t_us": top,
                          "dur_us": round(cursor - top, 1)})
        seg = _attribute_segment(s, s.start, top, srv, sched, cats, blame)
        if s.key and s.stage.startswith(("PS_", "PP_")):
            key_us[s.key] = key_us.get(s.key, 0.0) + (top - s.start)
        entry = {"stage": s.stage, "key": s.key, "t_us": s.start,
                 "dur_us": round(top - s.start, 1)}
        if s.round is not None:
            entry["round"] = s.round
        if len(seg) > 1:      # decomposed wire span: show the split
            entry["split"] = {c: round(us / 1e3, 3)
                              for c, us in seg.items()}
        chain.append(entry)
        cursor = s.start
    if cursor > t_start + _EPS_US:
        # chain cap hit (or an uncovered head): the remaining window
        # still lands SOMEWHERE — fold it into gap so categories always
        # sum to the window and fracs cannot silently skew toward
        # whatever the walked tail contained
        _add(cats, "gap", cursor - t_start)
    # Bounded-staleness credit (BPS_MAX_LAG>1): a sealed round's pull
    # returns fast and LEAVES the blocking chain, so the wait it
    # avoided is invisible to the backward sweep. Sweep ALL of this
    # step's PS_PULL spans: for each sealed round, the absorbed wait is
    # the missing worker's eventual arrival (its late push, whichever
    # round it folded into) minus the sealed serve — exactly the
    # merge-wait K=1 would have put on the chain as `straggler`. At
    # K=1 no record is ever sealed and this pass contributes nothing.
    absorbed: Dict[int, float] = {}
    arr_by: Dict[Tuple[int, int], List[float]] = {}
    if any(w.get("sealed") for w in srv.values()):
        for r in server_spans or ():
            k = int(r.get("key", 0))
            for a in r.get("arrivals") or ():
                if a.get("t") is not None:
                    arr_by.setdefault((k, int(a.get("w", 0))), []).append(
                        (float(a["t"]) - t0) * 1e6)
        for ts in arr_by.values():
            ts.sort()
        seen_sealed = set()
        for s in spans:
            if s.stage != "PS_PULL" or s.round is None:
                continue
            kr = (s.key, int(s.round))
            win = srv.get(kr)
            if win is None or not win["sealed"] or kr in seen_sealed:
                continue
            seen_sealed.add(kr)
            end = win["serve_end"] or win["complete"] or win["first"]
            for m in win["missing"]:
                later = next((t for t in arr_by.get((s.key, int(m)), ())
                              if t > end), None)
                if later is not None:
                    absorbed[int(m)] = absorbed.get(int(m), 0.0) \
                        + (later - end)
                    _add(cats, "absorbed", later - end)
    total_us = t_end - t_start
    res = {
        "schema": SCHEMA, "step": step,
        "window_s": round(total_us / 1e6, 6),
        "categories": {c: round(us / 1e6, 6)
                       for c, us in sorted(cats.items())},
        "fracs": {c: round(us / total_us, 4)
                  for c, us in sorted(cats.items())} if total_us else {},
        "dominant": (max(cats, key=cats.get) if cats else None),
        "keys": {str(k): round(us / 1e6, 6)
                 for k, us in sorted(key_us.items(),
                                     key=lambda kv: -kv[1])[:16]},
        "chain": list(reversed(chain)),
    }
    if truncated:
        res["truncated"] = True      # chain capped at max_chain; the
        #                              unwalked head is counted as gap
    if blame:
        w, us = max(blame.items(), key=lambda kv: kv[1])
        res["straggler"] = {"worker": w, "wait_s": round(us / 1e6, 6),
                            "by_worker": {str(k): round(v / 1e6, 6)
                                          for k, v in blame.items()}}
    if absorbed:
        w, us = max(absorbed.items(), key=lambda kv: kv[1])
        res["absorbed"] = {"worker": w, "wait_s": round(us / 1e6, 6),
                           "by_worker": {str(k): round(v / 1e6, 6)
                                         for k, v in absorbed.items()}}
    return res


def merge_results(results: List[dict]) -> dict:
    """Sum several steps' attributions into one aggregate view (the
    CLI's and bench rigs' per-run summary)."""
    cats: Dict[str, float] = {}
    blame: Dict[str, float] = {}
    absorbed: Dict[str, float] = {}
    total = 0.0
    for r in results:
        if not r:
            continue
        total += r.get("window_s", 0.0)
        for c, s in (r.get("categories") or {}).items():
            cats[c] = cats.get(c, 0.0) + s
        for w, s in ((r.get("straggler") or {}).get("by_worker")
                     or {}).items():
            blame[w] = blame.get(w, 0.0) + s
        for w, s in ((r.get("absorbed") or {}).get("by_worker")
                     or {}).items():
            absorbed[w] = absorbed.get(w, 0.0) + s
    out = {"schema": SCHEMA, "steps": sum(1 for r in results if r),
           "window_s": round(total, 6),
           "categories": {c: round(s, 6) for c, s in sorted(cats.items())},
           "fracs": ({c: round(s / total, 4)
                      for c, s in sorted(cats.items())} if total else {}),
           "dominant": max(cats, key=cats.get) if cats else None}
    if blame:
        w, s = max(blame.items(), key=lambda kv: kv[1])
        out["straggler"] = {"worker": int(w), "wait_s": round(s, 6),
                            "by_worker": {k: round(v, 6)
                                          for k, v in blame.items()}}
    if absorbed:
        w, s = max(absorbed.items(), key=lambda kv: kv[1])
        out["absorbed"] = {"worker": int(w), "wait_s": round(s, 6),
                           "by_worker": {k: round(v, 6)
                                         for k, v in absorbed.items()}}
    return out


# ------------------------------------------------ live-process helpers

def step_attribution(events: List[dict], step: Optional[int],
                     t0_s: float) -> Optional[dict]:
    """Attribution for one step from THIS process's vantage point:
    worker spans from the live timeline snapshot, server spans from
    every locally visible ring + the fleet scraper's ingested scrapes
    (obs.spans.collected — already worker timebase), credit waits from
    the current wire scheduler. The StepStats/slow-step entry point —
    the chain listing is TRIMMED (the rolling BPS_STATS_FILE must not
    carry hundreds of segments per step; the CLI keeps the full walk)."""
    from ..server.admission import send_scheduler
    from . import spans as _spans
    sch = send_scheduler()
    res = attribute(events, server_spans=_spans.collected(),
                    sched_trace=sch.trace() if sch is not None else None,
                    step=step, t0=t0_s)
    if res is not None and len(res.get("chain", ())) > 16:
        res["chain"] = res["chain"][-16:]
        res["chain_trimmed"] = True
    return res


_last_attr_lock = threading.Lock()
_last_attr: Optional[Tuple[float, dict]] = None


def publish(res: Optional[dict], registry=None) -> None:
    """Land one step's attribution in the registry as ``crit/*``."""
    global _last_attr
    if not res:
        return
    from .metrics import CRIT_CATEGORIES, get_registry
    reg = registry if registry is not None else get_registry()
    cats = res.get("categories") or {}
    total = res.get("window_s") or 0.0
    for c in CRIT_CATEGORIES:
        s = cats.get(c, 0.0)
        reg.gauge(f"crit/{c}_s").set(round(s, 6))
        reg.gauge(f"crit/{c}_frac").set(
            round(s / total, 4) if total else 0.0)
    reg.counter("crit/steps").inc()
    # stash the full result for the watchtower: the gauges above carry
    # only the fractions, but an incident wants the straggler's worker
    # id and the dominant verdict exactly as attributed
    with _last_attr_lock:
        _last_attr = (time.time(), res)


def last_attribution() -> Optional[Tuple[float, dict]]:
    """(wall time, result) of the newest ``publish`` in this process —
    the watchtower's blame source; None before any attributed step."""
    with _last_attr_lock:
        return _last_attr


# ---------------------------------------------------------------- CLI

def format_report(per_step: List[dict], agg: dict,
                  rank: int = 0) -> str:
    """Human report: per-step category split + the aggregate verdict."""
    lines = [f"critical-path attribution (rank {rank}, "
             f"{agg.get('steps', 0)} step(s)):"]
    for r in per_step:
        if not r:
            continue
        cats = sorted((r.get("categories") or {}).items(),
                      key=lambda kv: -kv[1])
        split = "  ".join(f"{c}={s * 1e3:.1f}ms"
                          f"({(r['fracs'] or {}).get(c, 0) * 100:.0f}%)"
                          for c, s in cats[:5])
        lines.append(f"  step {r.get('step')}: "
                     f"wall {r['window_s'] * 1e3:.1f}ms  {split}")
        strag = r.get("straggler")
        if strag:
            lines.append(f"    straggler: worker {strag['worker']:#x} "
                         f"blamed for {strag['wait_s'] * 1e3:.1f}ms")
        if r.get("keys"):
            top = list(r["keys"].items())[:3]
            lines.append("    top keys: " + ", ".join(
                f"{int(k):#x}={v * 1e3:.1f}ms" for k, v in top))
    dom = agg.get("dominant")
    dom_pct = (agg.get("fracs") or {}).get(dom, 0) * 100
    lines.append(f"  == dominant: {dom} ({dom_pct:.0f}% of "
                 f"{agg.get('window_s', 0) * 1e3:.1f}ms)")
    strag = agg.get("straggler")
    if strag:
        lines.append(f"  == straggler: worker {strag['worker']:#x} "
                     f"({strag['wait_s'] * 1e3:.1f}ms merge-wait)")
    absd = agg.get("absorbed")
    if absd:
        lines.append(f"  == absorbed: worker {absd['worker']:#x} "
                     f"({absd['wait_s'] * 1e3:.1f}ms merge-wait absorbed "
                     f"by bounded staleness)")
    return "\n".join(lines)


def analyze_dir(trace_dir: str, rank: int = 0,
                step: Optional[int] = None) -> Tuple[List[dict], dict]:
    """Load ``<trace_dir>/<rank>/comm.json`` (+ every
    ``server_<shard>.json`` span dump beside it) and attribute each
    step found (or just ``step``). Returns (per-step results, aggregate)."""
    path = os.path.join(trace_dir, str(rank), "comm.json")
    with open(path) as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    t0 = (data.get("metadata") or {}).get("t0_unix_s", 0.0)
    server: List[dict] = []
    for entry in sorted(os.listdir(trace_dir)):
        if entry.startswith("server_") and entry.endswith(".json"):
            try:
                with open(os.path.join(trace_dir, entry)) as f:
                    server.extend(json.load(f).get("spans", []))
            except (OSError, ValueError) as e:
                print(f"warning: skipping unreadable span dump "
                      f"{entry}: {e}", file=sys.stderr)
    if server and not t0:
        print("warning: comm.json has no metadata.t0_unix_s (older "
              "trace) — server spans cannot be placed on the worker "
              "timebase and are ignored", file=sys.stderr)
        server = []
    steps = sorted({(e.get("args") or {}).get("step")
                    for e in events
                    if e.get("ph") in (None, "X")} - {None})
    if step is not None:
        steps = [s for s in steps if s == step]
    per_step = [attribute(events, server_spans=server, step=s, t0=t0)
                for s in steps]
    per_step = [r for r in per_step if r]
    return per_step, merge_results(per_step)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m byteps_tpu.obs.critpath",
        description="Critical-path attribution report from a trace "
                    "directory (per-rank comm.json + optional "
                    "server_<shard>.json span dumps).")
    ap.add_argument("trace_dir")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--json", action="store_true",
                    help="emit the structured result instead of the "
                         "human report")
    ap.add_argument("-o", "--out", default=None,
                    help="also write the structured result to a file")
    args = ap.parse_args(argv)
    try:
        per_step, agg = analyze_dir(args.trace_dir, rank=args.rank,
                                    step=args.step)
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if not per_step:
        print("no attributable spans found (is the trace window "
              "empty, or the step tag wrong?)", file=sys.stderr)
        return 1
    payload = {"schema": SCHEMA, "aggregate": agg, "steps": per_step}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(format_report(per_step, agg, rank=args.rank))
    return 0


if __name__ == "__main__":
    sys.exit(main())

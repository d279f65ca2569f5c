"""Causal round tracing + critical-path attribution (ISSUE 14): the
server-side span ring (OP_TRACE), NTP-style clock alignment, the
blocking-chain blame engine, and the satellites (flight endpoint,
send-admission flight events, slow-step auto-capture, merge_trace
server rows).

Tier-1 covers the ring/estimator units, synthetic-DAG attribution with
the blocking chain asserted exactly, clock-offset estimation under
injected skew, the TCP span scrape incl. severed-channel recovery, the
three ground-truth rigs (wire / straggler / compute — shared with
``bench.py critpath``, so bench and tests cannot drift), the
merge_trace server-row fixture, and the StepStats/slow-step/export
satellites."""

import json
import logging
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from byteps_tpu.obs import critpath, flight
from byteps_tpu.obs import metrics as obs_metrics
from byteps_tpu.obs import spans as spans_mod
from byteps_tpu.obs.spans import ClockEstimator, ServerSpanRing
from byteps_tpu.server.engine import HostPSBackend, PSServer
from byteps_tpu.server.transport import PSTransportServer, RemotePSBackend


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Enabled metrics/flight, a clean span plane, no scraper leaks."""
    from byteps_tpu.obs import fleet as fleet_mod
    obs_metrics.configure(True)
    obs_metrics.get_registry().reset()
    flight.configure(enabled=True)
    flight.get_recorder().clear()
    spans_mod.reset()
    fleet_mod.set_current(None)
    yield
    fleet_mod.set_current(None)
    spans_mod.reset()
    obs_metrics.configure(None)
    obs_metrics.get_registry().reset()
    flight.configure()
    flight.get_recorder().clear()


# ------------------------------------------------------ span ring units

def test_span_ring_counts_rounds_and_merge_wait():
    ring = ServerSpanRing(num_workers=2, enabled=True)
    ring.note_arrival(7, 11, 100)
    time.sleep(0.02)
    ring.note_arrival(7, 22, 100)
    ring.note_arrival(7, 11, 100)          # round 2 opens
    recs = ring.snapshot()
    assert [(r["key"], r["round"], len(r["arrivals"])) for r in recs] \
        == [(7, 1, 2), (7, 2, 1)]
    r1 = recs[0]
    assert r1["complete_t"] is not None
    assert r1["merge_wait_s"] >= 0.015
    assert {a["w"] for a in r1["arrivals"]} == {11, 22}
    assert recs[1]["complete_t"] is None   # round 2 incomplete


def test_span_ring_serve_and_queue_derivation():
    ring = ServerSpanRing(num_workers=1, enabled=True)
    ring.note_arrival(3, 5, 64)
    t = time.time()
    ring.note_serve(3, 1, t, 0.01)
    ring.note_serve(3, 0, t + 0.1, 0.002)   # round 0 -> latest round
    rec = ring.snapshot()[0]
    assert len(rec["serves"]) == 2
    # queue_s = first serve END - complete arrival, never negative
    assert rec["queue_s"] >= 0.0


def test_span_ring_bounded_and_disabled():
    ring = ServerSpanRing(num_workers=1, size=16, enabled=True)
    for i in range(50):
        ring.note_arrival(1, 0, 8)
    assert len(ring.snapshot()) <= 16
    off = ServerSpanRing(num_workers=1, enabled=False)
    off.note_arrival(1, 0, 8)
    assert off.snapshot() == []
    # the BPS_STATS master switch shorts it too
    on = ServerSpanRing(num_workers=1, enabled=True)
    obs_metrics.configure(False)
    on.note_arrival(1, 0, 8)
    obs_metrics.configure(True)
    assert on.snapshot() == []


# --------------------------------------------------- clock estimation

def test_clock_estimator_min_rtt_wins():
    est = ClockEstimator()
    # loose probe: rtt 0.2, midpoint offset 0.5
    est.probe("s0", 10.0, 10.2, 10.6)
    off, err = est.offset("s0")
    assert abs(off - 0.5) < 1e-9 and abs(err - 0.1) < 1e-9
    # tighter probe wins (rtt 0.02, offset 0.47)
    est.probe("s0", 20.0, 20.02, 20.48)
    off, err = est.offset("s0")
    assert abs(off - 0.47) < 1e-9 and abs(err - 0.01) < 1e-9
    # a later LOOSER probe must not displace the tight estimate
    est.probe("s0", 30.0, 30.5, 31.0)
    off, err = est.offset("s0")
    assert abs(off - 0.47) < 1e-9
    assert est.offset("s1") is None
    assert est.probe("s1", 1.0, 0.5, 2.0) is None    # recv < send


def test_rebase_shifts_every_timestamp():
    rec = {"key": 1, "round": 1, "first_t": 100.0, "complete_t": 101.0,
           "arrivals": [{"w": 3, "t": 100.5, "b": 8}],
           "serves": [{"t": 101.2, "dur": 0.1}]}
    out = spans_mod.rebase([rec], 5.0)[0]
    assert out["first_t"] == 95.0 and out["complete_t"] == 96.0
    assert out["arrivals"][0]["t"] == 95.5
    assert out["serves"][0]["t"] == 96.2
    assert rec["first_t"] == 100.0       # input untouched


def _tcp_rig(num_workers=1):
    eng = PSServer(num_workers=num_workers, engine_threads=1)
    srv = PSTransportServer(eng, host="127.0.0.1", port=0)
    be = RemotePSBackend([f"127.0.0.1:{srv.port}"])
    return eng, srv, be


def test_clock_offset_under_injected_skew():
    """A server whose OP_TRACE clock claims +5s must estimate to a
    ~+5s offset and have its scraped spans re-based by it."""
    from byteps_tpu.obs.fleet import FleetScraper
    eng, srv, be = _tcp_rig()
    try:
        be.init_key(1, 16, "float32")
        be.push(1, np.ones(4, np.float32))
        out = np.empty(4, np.float32)
        be.pull(1, out, round=1)
        true_first = srv.spans.snapshot()[0]["first_t"]
        srv._trace_now = lambda: time.time() + 5.0    # inject the skew
        sc = FleetScraper(be, interval_sec=5.0)
        sc.scrape_once()
        reg = obs_metrics.get_registry()
        off = reg.gauge("fleet/s0/clock_offset_s").value
        assert 4.5 < off < 5.5, off
        assert reg.gauge("fleet/s0/clock_err_s").value < 1.0
        ing = spans_mod.collected()
        mine = [r for r in ing if r["key"] == 1 and r["round"] == 1]
        assert mine, "scraped spans were not ingested"
        # ingested record re-based by ~the offset (scraped copy wins
        # the dedup over the local ring's un-based copy)
        assert abs((true_first - off) - mine[0]["first_t"]) < 0.6
        sc.stop()
    finally:
        be.close()
        srv.close()
        eng.close()


# ------------------------------------------------- TCP span scrape

def test_server_span_scrape_over_tcp_and_severed_channel():
    """Two workers' staggered pushes land in the server ring with the
    correct per-worker ids; OP_TRACE serves them on the dedicated
    stats channel, surviving a severed connection (one redial)."""
    eng, srv, be1 = _tcp_rig(num_workers=2)
    be2 = RemotePSBackend([f"127.0.0.1:{srv.port}"])
    try:
        for b in (be1, be2):
            b.init_key(7, 16, "float32")
        for r in range(2):
            be1.push(7, np.ones(4, np.float32))
            time.sleep(0.03)
            be2.push(7, np.ones(4, np.float32))
            out = np.empty(4, np.float32)
            be1.pull(7, out, round=r + 1)
        p, t0, t1 = be1.trace_shard(0)
        assert p["schema"] == spans_mod.SCHEMA
        assert p["num_workers"] == 2
        assert abs(p["now"] - (t0 + t1) / 2) <= (t1 - t0) / 2 + 0.2
        recs = [r for r in p["spans"] if r["round"] <= 2]
        assert len(recs) == 2
        for r in recs:
            assert {a["w"] for a in r["arrivals"]} == {be1._wid,
                                                       be2._wid}
            assert r["merge_wait_s"] >= 0.02
        assert any(r["serves"] for r in recs)
        # sever the DEDICATED channel: the next scrape redials
        ch = be1._stats_chans[0]
        assert ch is not None and ch.sock is not None
        ch.sock.close()
        p2, _, _ = be1.trace_shard(0)
        assert p2["schema"] == spans_mod.SCHEMA
        # a push RETRY must not double-count an arrival (dedup-gated)
        n_before = sum(len(r["arrivals"]) for r in p2["spans"])
        assert n_before == 4
    finally:
        be1.close()
        be2.close()
        srv.close()
        eng.close()


def test_host_backend_trace_surface():
    be = HostPSBackend(num_servers=1, num_workers=1)
    try:
        be.init_key(9, 16, "float32")
        be.push(9, np.ones(4, np.float32))
        out = np.empty(4, np.float32)
        be.pull(9, out, round=1)
        tr = be.trace()
        p = tr["s0"]["payload"]
        assert p["schema"] == spans_mod.SCHEMA
        assert p["spans"][0]["key"] == 9
        assert p["spans"][0]["serves"]
        assert tr["s0"]["t_send"] == tr["s0"]["t_recv"]   # zero-width
    finally:
        be.close()


# ------------------------------------------- synthetic-DAG attribution

def _ev(stage, a_ms, b_ms, key=0, step=0, round=None, name="g"):
    args = {"name": name, "step": step}
    if round is not None:
        args["round"] = round
    return {"name": stage, "ph": "X", "pid": key, "tid": 0,
            "ts": a_ms * 1e3, "dur": (b_ms - a_ms) * 1e3, "args": args}


def test_attribute_synthetic_chain_exact():
    """A hand-built linear pipeline with one gap and a decomposed pull:
    every chain segment's category seconds asserted exactly."""
    T0 = 1000.0          # wall base: server records are wall seconds
    events = [
        _ev("DISPATCH", 0, 50),
        _ev("PS_D2H", 50, 58, key=5),
        # [58, 60] is an explicit gap
        _ev("PS_PACK", 60, 65, key=5),
        _ev("PS_PUSH", 65, 85, key=5, round=1),
        _ev("PS_PULL", 85, 125, key=5, round=1),
        _ev("PS_UNPACK", 125, 130, key=5),
        _ev("PS_APPLY_CHUNK", 130, 150, key=5),
    ]
    server = [{
        "key": 5, "round": 1,
        "first_t": T0 + 0.090,
        "arrivals": [{"w": 1, "t": T0 + 0.090, "b": 10},
                     {"w": 7, "t": T0 + 0.105, "b": 10}],
        "complete_t": T0 + 0.105,
        "serves": [{"t": T0 + 0.105, "dur": 0.010}],
    }]
    res = critpath.attribute(events, server_spans=server, step=0, t0=T0)
    cats = {c: round(s * 1e3, 1) for c, s in res["categories"].items()}
    # pull (40ms) decomposes: straggler 15 + server_queue 10 + wire 15;
    # push contributes its full 20ms of wire -> 35ms wire total
    assert cats == {"compute": 50.0, "d2h": 8.0, "gap": 2.0,
                    "host": 10.0, "wire": 35.0, "straggler": 15.0,
                    "server_queue": 10.0, "apply": 20.0}, cats
    assert res["dominant"] == "compute"
    assert abs(res["window_s"] - 0.150) < 1e-6
    # the blocking chain is the pipeline, in order
    stages = [c["stage"] for c in res["chain"]]
    assert stages == ["DISPATCH", "PS_D2H", "(gap)", "PS_PACK",
                      "PS_PUSH", "PS_PULL", "PS_UNPACK",
                      "PS_APPLY_CHUNK"], stages
    # straggler blame: the LAST arrival's worker id
    assert res["straggler"]["worker"] == 7
    assert abs(res["straggler"]["wait_s"] - 0.015) < 1e-6
    # per-key blame covers the PS spans
    assert res["keys"]["5"] > 0.09


def test_attribute_pull_without_server_record_is_wire():
    events = [_ev("PS_PULL", 0, 40, key=5, round=1)]
    res = critpath.attribute(events, server_spans=None, step=0)
    assert res["categories"] == {"wire": 0.04}


def test_attribute_credit_wait_carved_from_push():
    T0 = 2000.0
    events = [_ev("PS_PUSH", 0, 20, key=5, round=1)]
    sched_trace = [{"key": 5, "wait_s": 0.008, "t": T0 + 0.008,
                    "class": "grad", "overtook": False}]
    res = critpath.attribute(events, sched_trace=sched_trace,
                             step=0, t0=T0)
    cats = {c: round(s * 1e3, 1) for c, s in res["categories"].items()}
    assert cats == {"credit": 8.0, "wire": 12.0}, cats


def test_attribute_overlapping_spans_tile_once():
    """Overlapping spans: every instant lands in exactly one chain
    segment (the later-running span wins its tail)."""
    events = [_ev("DISPATCH", 0, 50), _ev("PS_PULL", 40, 100, key=1)]
    res = critpath.attribute(events, step=0)
    total = sum(res["categories"].values())
    assert abs(total - res["window_s"]) < 1e-6
    cats = {c: round(s * 1e3, 1) for c, s in res["categories"].items()}
    assert cats == {"compute": 40.0, "wire": 60.0}, cats


def test_attribute_empty_and_merge_results():
    assert critpath.attribute([], step=0) is None
    a = critpath.attribute([_ev("DISPATCH", 0, 10)], step=0)
    b = critpath.attribute([_ev("PS_PULL", 0, 30, key=1)], step=0)
    agg = critpath.merge_results([a, b, None])
    assert agg["steps"] == 2
    assert agg["dominant"] == "wire"


# -------------------------------------- ground-truth rigs (bench-shared)

def test_ground_truth_wire_bound():
    import bench
    r = bench.critpath_rig("wire", rounds=6, warm=2, elems=1 << 16,
                           server_rate=1.5e7)
    assert r["agg"]["dominant"] == "wire", r["agg"]["fracs"]
    assert r["agg"]["fracs"]["wire"] > 0.5


def test_ground_truth_straggler_blames_slow_worker():
    import bench
    r = bench.critpath_rig("straggler", rounds=6, warm=2,
                           elems=1 << 14, delay=0.06)
    assert r["agg"]["dominant"] == "straggler", r["agg"]["fracs"]
    assert r["agg"]["straggler"]["worker"] == r["slow_wid"]


def test_ground_truth_compute_bound():
    import bench
    r = bench.critpath_rig("compute", rounds=5, warm=2, dim=256,
                           depth=4, batch=4096)
    assert r["agg"]["dominant"] == "compute", r["agg"]["fracs"]


def test_bench_without_a_rig_prints_the_usage(monkeypatch, capsys):
    """``python bench.py`` measures nothing by itself: no rig named, the
    usage with every rig and exit code 2."""
    import bench
    monkeypatch.setattr("sys.argv", ["bench.py"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert all(f"  {name} " in err for name in bench._BREAKDOWNS), err


@pytest.mark.slow
def test_bench_critpath_smoke():
    """The full acceptance breakdown (three asserted rigs + CLI smoke)
    at bench sizes."""
    import bench
    out = bench.critpath_breakdown(rounds=8, warm=2)
    assert out["cli_rc"] == 0


# --------------------------------------------- merge_trace server rows

def test_merge_trace_grows_server_rows(tmp_path, capsys):
    from byteps_tpu.obs.merge_trace import merge_traces
    T0 = 5000.0
    td = str(tmp_path)
    os.makedirs(os.path.join(td, "0"))
    events = [
        _ev("PS_PUSH", 10, 20, key=5, round=1),
        _ev("PS_PULL", 20, 60, key=5, round=1),
    ]
    with open(os.path.join(td, "0", "comm.json"), "w") as f:
        json.dump({"traceEvents": events,
                   "metadata": {"t0_unix_s": T0, "rank": 0}}, f)
    spans_mod.dump_server_trace(td, "s0", [{
        "key": 5, "round": 1, "first_t": T0 + 0.022,
        "arrivals": [{"w": 1, "t": T0 + 0.022, "b": 8}],
        "complete_t": T0 + 0.030,
        "serves": [{"t": T0 + 0.030, "dur": 0.005}],
    }])
    merged = merge_traces(td)
    evs = merged["traceEvents"]
    names = {e.get("args", {}).get("name") for e in evs
             if e.get("name") == "process_name"}
    assert "server s0" in names
    mg = [e for e in evs if e.get("name") == "SRV_MERGE"]
    sv = [e for e in evs if e.get("name") == "SRV_SERVE"]
    assert len(mg) == 1 and len(sv) == 1
    assert mg[0]["args"]["key"] == 5 and mg[0]["args"]["round"] == 1
    assert abs(mg[0]["ts"] - 22e3) < 1.0       # re-based onto rank t0
    # worker->server->worker flow arrows, exact (round-tagged) pairing
    flows = [e.get("name") for e in evs if e.get("ph") == "s"]
    assert "srv-in" in flows and "srv-out" in flows


def test_merge_trace_skips_server_rows_without_t0(tmp_path, capsys):
    from byteps_tpu.obs.merge_trace import merge_traces
    td = str(tmp_path)
    os.makedirs(os.path.join(td, "0"))
    with open(os.path.join(td, "0", "comm.json"), "w") as f:
        json.dump({"traceEvents": [_ev("PS_PUSH", 0, 5, key=1)]}, f)
    spans_mod.dump_server_trace(td, "s0", [{
        "key": 1, "round": 1, "first_t": 1.0, "arrivals": [],
        "complete_t": None, "serves": []}])
    merged = merge_traces(td)
    assert not any(e.get("name") == "SRV_MERGE"
                   for e in merged["traceEvents"])
    assert "t0_unix_s" in capsys.readouterr().err


# ---------------------------------------------------- critpath CLI

def test_critpath_cli_report(tmp_path, capsys):
    td = str(tmp_path)
    os.makedirs(os.path.join(td, "0"))
    T0 = 3000.0
    events = [_ev("DISPATCH", 0, 10), _ev("PS_PULL", 10, 40, key=5,
                                          round=1)]
    with open(os.path.join(td, "0", "comm.json"), "w") as f:
        json.dump({"traceEvents": events,
                   "metadata": {"t0_unix_s": T0, "rank": 0}}, f)
    rc = critpath.main([td])
    assert rc == 0
    out = capsys.readouterr().out
    assert "critical-path attribution" in out
    assert "dominant: wire" in out
    # structured form
    rc = critpath.main([td, "--json", "-o",
                        str(tmp_path / "crit.json")])
    assert rc == 0
    data = json.loads((tmp_path / "crit.json").read_text())
    assert data["aggregate"]["dominant"] == "wire"
    # empty dir: loud, nonzero
    os.makedirs(os.path.join(td, "empty", "0"))
    with open(os.path.join(td, "empty", "0", "comm.json"), "w") as f:
        json.dump({"traceEvents": []}, f)
    assert critpath.main([os.path.join(td, "empty")]) == 1


# ------------------------------------------------- StepStats satellites

def _traced_timeline(tmp_path=None):
    from byteps_tpu.common.config import Config
    from byteps_tpu.timeline import Timeline
    return Timeline(Config(trace_on=True, trace_start_step=0,
                           trace_end_step=1 << 30))


def test_stepstats_carries_crit_block():
    from byteps_tpu.obs.stats import StepStatsEmitter
    tl = _traced_timeline()
    tl.set_step(0)
    now = time.time()
    tl.record("g", "DISPATCH", now - 0.05, 0.04, 0, step=0)
    tl.record("g", "PS_PULL", now - 0.01, 0.01, 5, step=0, round=1)
    em = StepStatsEmitter(stats_file=None)
    st = em.on_step(0, 0.05, timeline=tl)
    assert st is not None and st.crit is not None
    assert st.crit["dominant"] in ("compute", "wire")
    assert "crit=" in st.line()
    reg = obs_metrics.get_registry()
    assert reg.counter("crit/steps").value == 1
    assert reg.gauge("crit/compute_s").value > 0
    assert "crit" in st.to_dict()


def test_slow_step_auto_capture_rate_limited(monkeypatch, caplog):
    from byteps_tpu.obs.stats import StepStatsEmitter
    monkeypatch.setenv("BPS_SLOW_STEP_FACTOR", "3")
    log = logging.getLogger("test-slow-step")   # propagates to caplog
    em = StepStatsEmitter(stats_file=None, logger=log)
    assert em._slow_factor == 3.0
    flight.record("push", key=1, round=2, nbytes=64)
    with caplog.at_level(logging.WARNING, logger="test-slow-step"):
        for i in range(10):
            em.on_step(i, 0.01)
        em.on_step(10, 0.2)          # 20x the median: captured
        em.on_step(11, 0.2)          # rate-limited: silent
    slow = [r for r in caplog.records if "slow step" in r.message]
    assert len(slow) == 1, [r.message for r in slow]
    msg = slow[0].message
    assert "BPS_SLOW_STEP_FACTOR" in msg
    assert "flight recorder" in msg          # postmortem attached
    assert "no critpath attribution" in msg  # no trace window here


def test_slow_step_default_off(monkeypatch):
    from byteps_tpu.obs.stats import StepStatsEmitter
    monkeypatch.delenv("BPS_SLOW_STEP_FACTOR", raising=False)
    em = StepStatsEmitter(stats_file=None)
    assert em._slow_factor == 0.0


# -------------------------------------------- flight export satellites

def test_http_flight_json_endpoint():
    from byteps_tpu.obs.export import MetricsHTTPServer
    flight.record("push", key=3, round=1, nbytes=128)
    srv = MetricsHTTPServer(port=0, host="127.0.0.1").start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/flight.json") as r:
            data = json.loads(r.read().decode())
        assert data["schema"] == "byteps_tpu.FlightDump/v1"
        assert data["enabled"] is True
        assert any(e.get("kind") == "push" and e.get("key") == 3
                   for e in data["events"])
    finally:
        srv.stop()


def test_export_cli_flight_flag(capsys):
    from byteps_tpu.obs.export import main as export_main
    flight.record("pull", key=9, round=4, nbytes=32)
    rc = export_main(["--flight"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert data["schema"] == "byteps_tpu.FlightDump/v1"
    assert any(e.get("key") == 9 for e in data["events"])
    # --flight is local-only: addresses are refused loudly
    assert export_main(["127.0.0.1:1", "--flight"]) == 2


def test_sched_admission_records_flight_event():
    """Send-admission grants land in the flight ring KEY-LESS (context
    for every key's postmortem) with class + overtake flag."""
    from byteps_tpu.server.admission import CLASS_GRAD, SendScheduler
    sc = SendScheduler(credit_bytes=1 << 20)
    t = sc.acquire(CLASS_GRAD, 3, 42, 8192)
    sc.release(t)
    evs = [e for e in flight.get_recorder().events()
           if e["kind"] == "send_admit"]
    assert len(evs) == 1
    e = evs[0]
    assert "key" not in e                   # key-less by design
    assert "key=42" in e["detail"]
    assert "class=grad" in e["detail"]
    assert "overtook=False" in e["detail"]
    # the admission trace now carries the wall admit stamp the
    # critpath credit decomposition joins on
    assert sc.trace()[0]["t"] == pytest.approx(time.time(), abs=5.0)

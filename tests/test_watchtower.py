"""Fleet watchtower (ISSUE 19): the bounded on-disk telemetry ring
(``obs/tsdb.py``), the online detector bank + structured incident
engine (``obs/watchtower.py``), and their surfaces — the FleetScraper
hook, the slow-step reroute, the ``/incidents.json``+``/healthz``
endpoints, and the offline-replay CLI.

Everything here is tier-1 synthetic: detectors are driven by
hand-built frames, the live adapter by a fake ``stats()`` backend, and
the CLI by a ring written in-process — the end-to-end fleet
choreography lives in ``bench.py ps_watch``."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from byteps_tpu.obs import flight
from byteps_tpu.obs import metrics as obs_metrics
from byteps_tpu.obs import spans as obs_spans
from byteps_tpu.obs import tsdb as obs_tsdb
from byteps_tpu.obs import watchtower as wt
from byteps_tpu.obs.export import MetricsHTTPServer
from byteps_tpu.obs.fleet import FleetScraper


@pytest.fixture(autouse=True)
def _fresh_watch(monkeypatch):
    """Zeroed metrics/flight, a fresh incident engine, no leaked span
    rings or tsdb singleton, and detector env pinned to defaults."""
    from byteps_tpu.obs import fleet as fleet_mod
    for var in ("BPS_AUTOTUNE", "BPS_TSDB_DIR", "BPS_TSDB_SIZE",
                "BPS_WATCH_Z", "BPS_WATCH_CONFIRM", "BPS_WATCH_WINDOW",
                "BPS_WATCH_MIN_SAMPLES", "BPS_WATCH_REGIME_FLOOR_MS",
                "BPS_WATCH_BLAME_CONC", "BPS_WATCH_MAX_INCIDENTS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("BPS_TSDB_DIR", "off")
    obs_metrics.configure(True)
    obs_metrics.get_registry().reset()
    flight.configure(enabled=True)
    flight.get_recorder().clear()
    wt.reset_engine()
    obs_spans.reset()
    obs_tsdb.reset_process_sink()
    fleet_mod.set_current(None)
    yield
    fleet_mod.set_current(None)
    wt.reset_engine()
    obs_spans.reset()
    obs_tsdb.reset_process_sink()
    obs_metrics.configure(None)
    obs_metrics.get_registry().reset()
    flight.configure()
    flight.get_recorder().clear()


# ------------------------------------------------------------- tsdb ring

def test_tsdb_roundtrip_oldest_first(tmp_path):
    path = str(tmp_path / "a.tsdb")
    w = obs_tsdb.TsdbWriter(path, size_bytes=1 << 16)
    assert w.append_many(10.0, [("fleet/s0/up", 1.0),
                                ("crit/wire_frac", 0.5)]) == 2
    w.append(11.0, "fleet/s0/up", 0.0)
    w.close()
    recs = obs_tsdb.read_records(path)
    assert recs == [(10.0, "fleet/s0/up", 1.0),
                    (10.0, "crit/wire_frac", 0.5),
                    (11.0, "fleet/s0/up", 0.0)]


def test_tsdb_ring_wraps_bounded(tmp_path):
    # capacity 8: 20 appends must survive as the NEWEST 8, oldest first
    size = obs_tsdb.HEADER_SIZE + 8 * obs_tsdb.RECORD_SIZE
    path = str(tmp_path / "ring.tsdb")
    w = obs_tsdb.TsdbWriter(path, size_bytes=size)
    assert w.capacity == 8
    for i in range(20):
        w.append(float(i), "g", float(i))
    w.close()
    assert os.path.getsize(path) <= size
    recs = obs_tsdb.read_records(path)
    assert [v for _, _, v in recs] == [float(i) for i in range(12, 20)]
    # reopening the ring resumes the monotonic count (geometry wins)
    w2 = obs_tsdb.TsdbWriter(path, size_bytes=1 << 20)
    assert (w2.capacity, w2.written) == (8, 20)
    w2.close()


def test_tsdb_reader_tolerates_garbage(tmp_path):
    empty = tmp_path / "empty.tsdb"
    empty.touch()
    foreign = tmp_path / "foreign.tsdb"
    foreign.write_bytes(b"definitely not a ring header")
    torn = tmp_path / "torn.tsdb"
    torn.write_bytes(b"\x00" * (obs_tsdb.HEADER_SIZE - 5))
    for p in (empty, foreign, torn):
        assert obs_tsdb.read_records(str(p)) == []
    assert obs_tsdb.read_records(str(tmp_path / "missing.tsdb")) == []
    # read_dir renders what survives and skips the rest
    good = str(tmp_path / "good.tsdb")
    w = obs_tsdb.TsdbWriter(good, size_bytes=1 << 14)
    w.append(2.0, "b", 2.0)
    w.close()
    w = obs_tsdb.TsdbWriter(str(tmp_path / "good2.tsdb"),
                            size_bytes=1 << 14)
    w.append(1.0, "a", 1.0)
    w.close()
    merged = obs_tsdb.read_dir(str(tmp_path))
    assert [(t, n) for t, n, _ in merged] == [(1.0, "a"), (2.0, "b")]


def test_tsdb_sink_selection_policy():
    snap = {
        "fleet/s0/up": 0.0,                 # zero IS the signal: kept
        "fleet/s0/server/engine_queue_depth": 3.0,
        "crit/wire_frac": 0.62,
        "crit/steps": 9.0,                  # crit but not *_frac: dropped
        "ps/push_bytes": 4096.0,            # non-fleet scalar: dropped
        "server/merge_wait_s": {"count": 4, "p50_ms": 1.0,
                                "p95_ms": 2.0, "p99_ms": 3.0,
                                "sum_ms": 5.0},
        "server/empty_hist": {"count": 0, "p95_ms": 0.0},
    }
    got = dict(obs_tsdb.TsdbSink._select(snap))
    assert got == {
        "fleet/s0/up": 0.0,
        "fleet/s0/server/engine_queue_depth": 3.0,
        "crit/wire_frac": 0.62,
        "server/merge_wait_s/p50_ms": 1.0,
        "server/merge_wait_s/p95_ms": 2.0,
        "server/merge_wait_s/p99_ms": 3.0,
        "server/merge_wait_s/count": 4.0,
    }


def test_tsdb_process_sink_env_gating(tmp_path, monkeypatch):
    monkeypatch.setenv("BPS_TSDB_DIR", "off")
    assert obs_tsdb.env_dir() is None
    assert obs_tsdb.process_sink() is None
    d = str(tmp_path / "hist")
    monkeypatch.setenv("BPS_TSDB_DIR", d)
    sink = obs_tsdb.process_sink()
    assert sink is not None
    assert obs_tsdb.process_sink() is sink       # singleton per key
    assert sink.sample({"fleet/s0/up": 1.0}, 5.0) == 1
    path = os.path.join(d, f"bps-{os.getpid()}.tsdb")
    assert obs_tsdb.read_records(path) == [(5.0, "fleet/s0/up", 1.0)]
    obs_tsdb.reset_process_sink()


# ------------------------------------------------------------- detectors

def test_change_point_quiet_stream_never_fires():
    det = wt.ChangePointDetector("x", z=4, confirm=3, min_samples=8)
    vals = [10.0, 10.2, 9.8, 10.1, 9.9, 10.0, 10.3, 9.7] * 8
    assert all(det.update(float(i), v) is None
               for i, v in enumerate(vals))
    assert not det.active


def test_change_point_opens_freezes_baseline_and_recovers():
    det = wt.ChangePointDetector("x", z=4, confirm=2, min_samples=4,
                                 min_delta=10.0)
    t = 0.0
    for v in (2.0, 2.1, 1.9, 2.0):
        assert det.update(t, v) is None
        t += 1.0
    assert det.update(t, 80.0) is None           # first breach: unconfirmed
    ev = det.update(t + 1, 80.0)
    assert ev and ev["event"] == "open" and ev["signal"] == "x"
    assert abs(ev["baseline"] - 2.0) < 0.2 and ev["observed"] == 80.0
    assert ev["z"] > 4 and det.active
    # the shift persisting must NOT re-open or become the new normal
    for i in range(10):
        assert det.update(t + 2 + i, 80.0 + i) is None
    assert det.active
    # recovery: confirm calm samples within HALF the open threshold
    assert det.update(t + 20, 2.0) is None
    ev = det.update(t + 21, 2.1)
    assert ev and ev["event"] == "close" and ev["duration_s"] == 20.0
    assert not det.active


def test_change_point_oscillation_never_confirms():
    det = wt.ChangePointDetector("x", z=4, confirm=3, min_samples=4,
                                 min_delta=10.0)
    t = 0.0
    for v in (2.0, 2.0, 2.0, 2.0):
        det.update(t, v)
        t += 1.0
    # breach, calm, breach, calm … — confirm=3 never accumulates
    for i in range(12):
        v = 80.0 if i % 2 == 0 else 2.0
        assert det.update(t + i, v) is None
    assert not det.active


def test_change_point_direction_gates_sign():
    falling = wt.ChangePointDetector("hit", z=3, confirm=2,
                                     min_samples=4, min_delta=0.1,
                                     direction=-1)
    t = 0.0
    for v in (0.95, 0.94, 0.96, 0.95):
        falling.update(t, v)
        t += 1.0
    assert falling.update(t, 1.0) is None        # UP move: ignored
    assert falling.update(t + 1, 1.0) is None
    assert not falling.active
    falling.update(t + 2, 0.3)
    ev = falling.update(t + 3, 0.3)
    assert ev and ev["event"] == "open"


def test_flip_detector_hysteresis():
    fd = wt.FlipDetector(confirm=2)
    assert fd.update("wire") is None
    assert fd.update("wire") is None             # establishment: silent
    assert fd.current == "wire"
    assert fd.update("straggler") is None        # candidate, unconfirmed
    assert fd.update("wire") is None             # reset: same-as-current
    assert fd.update("straggler") is None
    assert fd.update(None) is None               # None also resets
    assert fd.update("straggler") is None
    assert fd.update("straggler") == ("wire", "straggler")
    assert fd.current == "straggler"


# -------------------------------------------------------- incident engine

def test_engine_dedupe_close_reopen_and_bound():
    eng = wt.IncidentEngine(max_incidents=4)
    inc = eng.open_incident("change_point", "x", verdict="wire", at=100.0)
    assert inc["id"] == 1 and inc["opened_t"] == 100.0
    assert inc["closed_t"] is None
    assert inc["remedy"] == dict(wt.REMEDIES["wire"], acted=False)
    assert "flight" in inc                       # postmortem attached
    # one cause, one record: a second open of the same (kind, signal)
    assert eng.open_incident("change_point", "x", at=101.0) is None
    closed = eng.close_incident("change_point", "x",
                                evidence={"recovered": True}, at=105.0)
    assert closed["closed_t"] == 105.0
    assert closed["evidence"]["recovered"] is True
    assert eng.open_incidents() == []
    assert eng.open_incident("change_point", "x", at=110.0)["id"] == 2
    assert eng.close_incident("change_point", "nope") is None
    for i in range(6):                           # bounded ring
        eng.open_incident("change_point", f"sig{i}", at=120.0 + i)
    assert len(eng.incidents()) == 4


def test_engine_callbacks_and_json():
    eng = wt.IncidentEngine(max_incidents=16)
    seen = []
    eng.add_callback(seen.append)
    eng.add_callback(lambda inc: 1 / 0)          # must be swallowed
    inc = eng.open_incident("regime_flip", "crit/dominant",
                            verdict="straggler", resolve=True,
                            evidence={"from": "wire", "to": "straggler"})
    assert [i["id"] for i in seen] == [inc["id"]]
    assert inc["closed_t"] is not None           # point event
    body = eng.to_json()
    assert body["schema"] == "byteps_tpu.Incidents/v1"
    assert body["open"] == 0 and len(body["incidents"]) == 1
    eng.remove_callback(seen.append)
    eng.open_incident("change_point", "y")
    assert len(seen) == 1


def test_slow_step_routes_through_engine():
    crit = {"dominant": "straggler", "straggler": {"worker": 3}}
    inc = wt.slow_step_incident("slow step 12: 500ms vs 100ms",
                                wall_ms=500.0, median_ms=100.0,
                                factor=5.0, crit=crit)
    assert inc["kind"] == "slow_step" and inc["signal"] == "step/wall_s"
    assert inc["verdict"] == "straggler"
    assert inc["blamed"] == {"worker": 3}
    assert inc["closed_t"] is not None           # point event, resolved
    assert inc["evidence"] == {"wall_ms": 500.0, "median_ms": 100.0,
                               "factor": 5.0}
    assert inc["crit"] is crit
    assert inc["remedy"]["knob"] == "BPS_MAX_LAG"
    assert wt.get_engine().incidents()[0]["id"] == inc["id"]


# ------------------------------------------------------- watchtower ticks

_FAST = {"confirm": 2, "min_samples": 4, "window": 16}


def _frames(w, t0, frames):
    opened = []
    for i, f in enumerate(frames):
        opened.extend(w.tick(t0 + float(i), f))
    return opened


def test_tick_change_point_blames_straggler_worker():
    w = wt.Watchtower(engine=wt.IncidentEngine(), params=_FAST)
    calm = {"streams": {"spans/merge_wait_ms": 2.0}, "blame_worker": 7}
    hot = {"streams": {"spans/merge_wait_ms": 80.0}, "blame_worker": 7}
    opened = _frames(w, 100.0, [calm] * 4 + [hot] * 2)
    assert [i["kind"] for i in opened] == ["change_point"]
    inc = opened[0]
    assert inc["signal"] == "spans/merge_wait_ms"
    assert inc["verdict"] == "straggler"         # _category_for default
    assert inc["blamed"] == {"worker": 7}
    assert inc["remedy"]["knob"] == "BPS_MAX_LAG"
    assert inc["opened_t"] == 105.0              # at= rides frame time
    snap = obs_metrics.get_registry().snapshot()
    assert snap["watch/ticks"] == 6.0
    assert snap["watch/incidents"] == 1.0
    assert snap["watch/open_incidents"] == 1.0
    # recovery closes the SAME record
    w.tick(110.0, calm)
    w.tick(111.0, calm)
    rec = w.engine.incidents()[0]
    assert rec["closed_t"] == 111.0
    assert rec["evidence"]["recovered"] is True


def test_tick_shard_liveness_boot_grace_dead_and_recovery():
    w = wt.Watchtower(engine=wt.IncidentEngine(), params=_FAST)
    down = {"shards": {"s0": {"up": 0.0, "stale": 0.0}}}
    up = {"shards": {"s0": {"up": 1.0, "stale": 0.0}}}
    # boot grace: a shard that was NEVER up is still dialing
    assert _frames(w, 10.0, [down] * 6) == []
    # was up, went down: confirm consecutive downs open shard_dead
    opened = _frames(w, 20.0, [up, down, down])
    assert [i["kind"] for i in opened] == ["shard_dead"]
    inc = opened[0]
    assert inc["signal"] == "fleet/s0/up" and inc["verdict"] == "dead"
    assert inc["blamed"] == {"shard": "s0"}
    assert inc["remedy"]["knob"] == "fleet.RESHAPE"
    # still down: no duplicate record
    assert _frames(w, 23.0, [down] * 3) == []
    # confirm consecutive ups close it
    _frames(w, 30.0, [up, up])
    assert w.engine.open_incidents() == []
    # STALE telemetry counts as down too
    stale = {"shards": {"s0": {"up": 1.0, "stale": 1.0}}}
    opened = _frames(w, 40.0, [stale, stale])
    assert [i["kind"] for i in opened] == ["shard_dead"]
    assert opened[0]["evidence"] == {"up": 1, "stale": 1}


def test_tick_regime_flip_incident():
    w = wt.Watchtower(engine=wt.IncidentEngine(), params=_FAST)
    assert _frames(w, 0.0, [{"regime": "wire"}] * 3) == []   # silent
    opened = _frames(w, 10.0, [{"regime": "straggler",
                                "blame_worker": 4}] * 2)
    assert [i["kind"] for i in opened] == ["regime_flip"]
    inc = opened[0]
    assert inc["signal"] == "crit/dominant"
    assert inc["verdict"] == "straggler"
    assert inc["evidence"] == {"from": "wire", "to": "straggler"}
    assert inc["blamed"] == {"worker": 4}
    assert inc["closed_t"] is not None           # flips are point events
    snap = obs_metrics.get_registry().snapshot()
    assert snap["watch/regime_flips"] == 1.0
    kinds = [e for e in flight.get_recorder().events()
             if e["kind"] == "incident"]
    assert kinds and "regime_flip" in kinds[-1]["detail"]


def test_fold_spans_collapses_to_one_sample_per_round():
    w = wt.Watchtower(engine=wt.IncidentEngine(), params=_FAST)
    # two keys of ONE round share the last-arrival worker; the blame
    # window must take a single (worker, max-wait) sample, not two
    obs_spans.ingest("s0", [
        {"key": 1, "round": 1, "complete_t": 10.0, "merge_wait_s": 0.004,
         "queue_s": 0.001,
         "arrivals": [{"t": 1.000, "w": 0}, {"t": 1.004, "w": 2}]},
        {"key": 2, "round": 1, "complete_t": 10.0, "merge_wait_s": 0.009,
         "queue_s": 0.003,
         "arrivals": [{"t": 1.000, "w": 1}, {"t": 1.009, "w": 2}]},
    ])
    wait_ms, queue_ms, n = w._fold_spans()
    assert n == 2
    assert wait_ms == pytest.approx(6.5)
    assert queue_ms == pytest.approx(2.0)
    assert list(w._last_wids) == [(2, pytest.approx(9.0))]
    # round watermark: a second fold sees nothing new
    assert w._fold_spans() == (0.0, 0.0, 0)
    assert len(w._last_wids) == 1
    # a sealed (timed-out) record must not vote for blame
    obs_spans.ingest("s0", [
        {"key": 1, "round": 2, "complete_t": 11.0, "merge_wait_s": 0.5,
         "sealed": True,
         "arrivals": [{"t": 2.0, "w": 0}, {"t": 2.5, "w": 3}]},
    ])
    _, _, n = w._fold_spans()
    assert n == 1 and len(w._last_wids) == 1


# ------------------------------------------------------ live integration

class _FakeStatsBackend:
    """Minimal ``stats()`` surface: one shard, percentile payload."""

    def __init__(self):
        self.dead = False

    def stats(self, timeout_ms=0):
        if self.dead:
            return {"s0": {"error": "ConnectionError: refused"}}
        return {"s0": {
            "schema": "byteps_tpu.ServerStats/v1",
            "heartbeat": {"uptime_s": time.monotonic(), "requests": 1,
                          "keys": 2},
            "queue_depth": 2.0,
            "metrics": {"server/merge_wait_s": {
                "count": 4, "p50_ms": 1.5, "p95_ms": 12.5,
                "p99_ms": 30.0, "sum_ms": 20.0}},
        }}


def test_scraper_publishes_percentiles_and_scrape_duration():
    sc = FleetScraper(_FakeStatsBackend(), interval_sec=5.0)
    sc.scrape_once()
    reg = obs_metrics.get_registry()
    pre = "fleet/s0/server/merge_wait_s"
    assert reg.gauge(f"{pre}/p50_ms").value == 1.5
    assert reg.gauge(f"{pre}/p95_ms").value == 12.5
    assert reg.gauge(f"{pre}/p99_ms").value == 30.0
    assert reg.gauge(f"{pre}/count").value == 4.0
    assert reg.gauge("fleet/s0/scrape_dur_s").value >= 0.0


def test_scraper_persists_history_when_tsdb_on(tmp_path, monkeypatch):
    d = str(tmp_path / "hist")
    monkeypatch.setenv("BPS_TSDB_DIR", d)
    sc = FleetScraper(_FakeStatsBackend(), interval_sec=5.0)
    assert sc.tsdb is not None
    sc.scrape_once()
    sc.scrape_once()
    recs = obs_tsdb.read_dir(d)
    names = {n for _, n, _ in recs}
    assert "fleet/s0/up" in names
    assert "fleet/s0/server/merge_wait_s/p99_ms" in names
    # batches share one stamp per scrape tick: exactly two frame times,
    # compared as stored (float64): two ticks can fall inside a millisecond
    assert len({t for t, _, _ in recs}) == 2


def test_maybe_watchtower_gating(monkeypatch):
    monkeypatch.delenv("BPS_AUTOTUNE", raising=False)
    assert wt.autotune_mode() == "off"
    assert wt.maybe_watchtower() is None
    monkeypatch.setenv("BPS_AUTOTUNE", "tune-everything")  # unknown: off
    assert wt.autotune_mode() == "off"
    assert wt.maybe_watchtower() is None
    monkeypatch.setenv("BPS_AUTOTUNE", "observe")
    w = wt.maybe_watchtower()
    assert isinstance(w, wt.Watchtower)
    assert w.engine is wt.get_engine()
    obs_metrics.configure(False)                 # stats off: no detectors
    assert wt.maybe_watchtower() is None
    obs_metrics.configure(True)


def test_scraper_runs_watchtower_in_observe_mode(monkeypatch):
    monkeypatch.setenv("BPS_AUTOTUNE", "observe")
    be = _FakeStatsBackend()
    sc = FleetScraper(be, interval_sec=5.0,
                      stale_after=60.0)
    assert sc.watch is not None
    for _ in range(3):
        sc.scrape_once()
    snap = obs_metrics.get_registry().snapshot()
    assert snap["watch/ticks"] == 3.0


def test_watch_params_env_overrides(monkeypatch):
    monkeypatch.setenv("BPS_WATCH_Z", "6.5")
    monkeypatch.setenv("BPS_WATCH_CONFIRM", "1")
    monkeypatch.setenv("BPS_WATCH_MIN_SAMPLES", "1")   # floored to 3
    monkeypatch.setenv("BPS_WATCH_BLAME_CONC", "0.9")
    monkeypatch.setenv("BPS_WATCH_WINDOW", "bogus")    # bad value: default
    p = wt.watch_params()
    assert p["z"] == 6.5 and p["confirm"] == 1
    assert p["min_samples"] == 3 and p["window"] == 64
    assert p["blame_conc"] == 0.9
    # explicit params win over env at construction
    w = wt.Watchtower(engine=wt.IncidentEngine(), params={"z": 2.0})
    assert w.params["z"] == 2.0 and w.params["confirm"] == 1


# ----------------------------------------------------- endpoints + health

def test_incidents_and_healthz_endpoints():
    from byteps_tpu.obs import fleet as fleet_mod
    srv = MetricsHTTPServer(0, host="127.0.0.1").start()
    base = f"http://127.0.0.1:{srv.port}"

    def get(path):
        try:
            with urllib.request.urlopen(f"{base}{path}", timeout=5) as r:
                return r.status, json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    try:
        code, hz = get("/healthz")
        assert (code, hz["status"]) == (200, "ok")
        inc = wt.get_engine().open_incident(
            "change_point", "spans/merge_wait_ms", verdict="straggler")
        code, hz = get("/healthz")
        assert (code, hz["status"]) == (503, "degraded")
        assert hz["open_incidents"] == 1
        code, body = get("/incidents.json")
        assert code == 200
        assert body["schema"] == "byteps_tpu.Incidents/v1"
        assert body["open"] == 1
        assert body["incidents"][0]["id"] == inc["id"]
        wt.get_engine().close_incident("change_point",
                                       "spans/merge_wait_ms")
        code, hz = get("/healthz")
        assert (code, hz["status"]) == (200, "ok")

        # stale shard telemetry outranks everything
        class _StaleView:
            def view(self):
                return {"s0": {"up": True, "stale": True}}
        fleet_mod.set_current(_StaleView())
        code, hz = get("/healthz")
        assert (code, hz["status"]) == (503, "stale")
        assert hz["stale"] == ["s0"]
    finally:
        fleet_mod.set_current(None)
        srv.stop()


# ------------------------------------------------- offline replay + CLI

def _write_liveness_ring(dirpath, confirm=3):
    """A ring whose recorded story is: shard s0 up, then gone."""
    os.makedirs(dirpath, exist_ok=True)
    w = obs_tsdb.TsdbWriter(os.path.join(dirpath, "bps-1.tsdb"),
                            size_bytes=1 << 16)
    t = 1000.0
    for _ in range(3):
        w.append_many(t, [("fleet/s0/up", 1.0), ("fleet/s0/stale", 0.0)])
        t += 0.25
    for _ in range(confirm + 1):
        w.append_many(t, [("fleet/s0/up", 0.0), ("fleet/s0/stale", 0.0)])
        t += 0.25
    w.close()


def test_replay_detects_dead_shard_in_ring_time(tmp_path):
    d = str(tmp_path / "rings")
    _write_liveness_ring(d, confirm=2)
    incs = wt.replay(obs_tsdb.read_dir(d), params={"confirm": 2})
    dead = [i for i in incs if i["kind"] == "shard_dead"]
    assert len(dead) == 1
    inc = dead[0]
    assert inc["blamed"] == {"shard": "s0"}
    # the timeline reads in RING time (the at= stamp), not now
    assert 1000.0 <= inc["opened_t"] <= 1003.0


def test_replay_detects_recorded_tail_shift():
    base = [(float(i), "server/merge_wait_s/p99_ms", 3.0 + 0.1 * (i % 3))
            for i in range(10)]
    shifted = [(float(10 + i), "server/merge_wait_s/p99_ms", 90.0)
               for i in range(3)]
    incs = wt.replay(base + shifted,
                     params={"confirm": 2, "min_samples": 4})
    cps = [i for i in incs if i["kind"] == "change_point"]
    assert len(cps) == 1
    assert cps[0]["signal"] == "server/merge_wait_s/p99_ms"
    assert cps[0]["verdict"] == "straggler"


def test_cli_replays_ring_and_exit_codes(tmp_path, capsys):
    assert wt.main([str(tmp_path / "nope")]) == 2        # not a directory
    empty = tmp_path / "empty"
    empty.mkdir()
    assert wt.main([str(empty)]) == 1                    # no records
    capsys.readouterr()
    d = str(tmp_path / "rings")
    _write_liveness_ring(d, confirm=3)
    assert wt.main([d]) == 0
    out = capsys.readouterr().out
    assert "shard_dead" in out and "fleet/s0/up" in out
    assert "remedy=fleet.RESHAPE" in out
    assert wt.main([d, "--json"]) == 0
    body = json.loads(capsys.readouterr().out)
    assert body["schema"] == "byteps_tpu.Incidents/v1"
    assert body["records"] == 14
    assert any(i["kind"] == "shard_dead" for i in body["incidents"])

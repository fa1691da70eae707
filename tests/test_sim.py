"""Fleet-simulator tests (ISSUE 16): the deterministic event engine
(heap tie-break, seeded RNG, virtual clock), the scheduler/hub seams
(real FleetScheduler on zero OS threads, fed MetricsHub series, with the
production defaults pinned), counter-rule parity between SimCenter and
the netps fold functions, the trace-fitted TimingModel over a REAL
traced loopback run (the stream ``sim_drift`` fits), the calibration
gates against the recorded curve in ``fixtures/hier_curve.json``
(held-out band + the flat->hier crossover at the measured W, and no
curve read that the caller did not name), bit-identical scenario determinism under a pinned seed, every scenario's invariant
checks at full scale, and the ``python -m distkeras_tpu.sim`` CLI exit
contract."""

import json
import os
import threading
import time

import numpy as np
import pytest

from distkeras_tpu.netps.fold import counter_staleness
from distkeras_tpu.sim import (
    SimCenter,
    SimEngine,
    SimJobRuntime,
    SimThreadFactory,
    TimingModel,
    hier_crossover,
    run_scenario,
    sim_drift,
)
from distkeras_tpu.sim.__main__ import main as sim_main
from distkeras_tpu.sim.calibrate import predict_throughput
from distkeras_tpu.sim.cluster import LinkClass, SimAggregator, TreeTopology

SUMMARY = os.path.join(os.path.dirname(__file__), "fixtures",
                       "hier_curve.json")


# -- the event engine -------------------------------------------------------

def test_engine_heap_orders_same_time_events_by_schedule_order():
    eng = SimEngine(0)
    seen = []
    for i in range(32):
        eng.at(1.0, seen.append, i)
    eng.run()
    assert seen == list(range(32))
    assert eng.now() == 1.0


def test_engine_past_is_clamped_and_until_advances_clock():
    eng = SimEngine(0)
    eng.at(5.0, lambda: eng.at(1.0, lambda: None))  # schedules "the past"
    eng.run(until=9.0)
    assert eng.now() == 9.0
    assert eng.pending() == 0


def test_engine_rng_is_seed_deterministic():
    a = SimEngine(7)
    b = SimEngine(7)
    assert [a.lognormal(0.0, 0.5) for _ in range(64)] \
        == [b.lognormal(0.0, 0.5) for _ in range(64)]
    assert SimEngine(8).lognormal(0.0, 0.5) != SimEngine(7).lognormal(0.0,
                                                                      0.5)


def test_engine_runaway_backstop_raises():
    eng = SimEngine(0)

    def rearm():
        eng.after(0.1, rearm)

    eng.after(0.0, rearm)
    with pytest.raises(RuntimeError, match="runaway"):
        eng.run(max_events=100)


# -- counter-rule parity: SimCenter vs the netps fold functions -------------

def test_sim_center_staleness_matches_counter_staleness():
    c = SimCenter(discipline="downpour")
    pulled = c.pull()
    for i in range(5):
        c.commit(wid=0, seq=i, pulled=pulled)  # stale pull held across
    # commit i saw i updates land since the pull: the fold rule verbatim
    assert [st for _w, _s, st in c.commit_log] \
        == [counter_staleness(i, 0) for i in range(5)]
    assert c.max_staleness == 4


def test_sim_center_sharded_pull_uses_min_rule():
    c = SimCenter(discipline="downpour", shards=3)
    pulled = c.pull()
    assert pulled == (0, 0, 0)
    c.commit(0, 0, pulled)
    res = c.commit(0, 1, pulled)  # one commit landed on every shard
    assert res["staleness"] == counter_staleness((1, 1, 1), pulled) == 1


def test_sim_center_dedup_and_value_witness():
    c = SimCenter(discipline="downpour")
    c.commit(0, 0, c.pull(), value=1.0)
    dup = c.commit(0, 0, c.pull(), value=1.0)  # retransmit
    assert dup == {"applied": False, "duplicate": True, "staleness": None}
    c.commit(1, 0, c.pull(), value=1.0)
    assert c.duplicates == 1
    assert c.exactly_once()
    assert c.center_value() == float(c.commits_total) == 2.0


def test_sim_center_promote_bumps_epoch_and_keeps_dedup():
    c = SimCenter()
    c.commit(0, 0, c.pull())
    assert c.promote() == 1
    assert c.epoch_history == [0, 1]
    assert c.commit(0, 0, c.pull())["duplicate"]  # dedup carried across


def test_aggregator_flush_policy_and_min_forwarding():
    agg = SimAggregator("a", fan_in=3, flush_s=10.0)
    assert agg.fold(0.0, 7, 1.0) is None
    assert agg.fold(0.1, 2, 1.0) is None
    out = agg.fold(0.2, 5, 1.0)  # fan-in trips
    assert out["count"] == 3 and out["value"] == 3.0
    assert out["pulled"] == 2  # the hier MIN rule
    # age-based flush: one lonely commit past the interval
    assert agg.fold(20.0, 9, 1.0) is None
    assert agg.fold(31.0, 9, 1.0)["count"] == 2
    assert agg.take(31.0) is None  # nothing pending


def test_tree_topology_paths_and_partitions():
    topo = TreeTopology(64, [("host", 8, LinkClass("h", 0.001)),
                             ("pool", 4, LinkClass("p", 0.002))])
    assert topo.group_of(63, 0) == 7 and topo.group_of(63, 1) == 1
    assert [a.name for a in topo.path(0)] == ["host-0", "pool-0"]
    topo.partition(1, 1, 2.0, 4.0)
    assert topo.link_down(1, 1, 3.0) and not topo.link_down(1, 0, 3.0)
    assert topo.heals_at(1, 1, 3.0) == 4.0
    assert topo.heals_at(1, 1, 5.0) == 5.0


# -- the seams --------------------------------------------------------------

def test_scheduler_seam_defaults_are_production():
    from distkeras_tpu.fleet.scheduler import FleetScheduler

    sched = FleetScheduler(capacity=2)
    assert sched._clock is time.monotonic
    assert sched._thread_factory is threading.Thread


def test_hub_feed_seam_series_and_liveness():
    from distkeras_tpu.telemetry.health.hub import MetricsHub

    eng = SimEngine(0)
    hub = MetricsHub(targets={}, interval=1.0, ring=64, down_after=3,
                     use_registry=False, clock=eng.clock())
    hub.feed("t0", "serving.latency", 0.2, role="serving")
    eng._now = 1.0
    hub.feed("t0", "serving.latency", 0.4, role="serving")
    assert hub.measure("serving.latency", stat="mean",
                       window_s=10.0) == pytest.approx(0.3)
    assert not hub.is_down("t0")
    for _ in range(3):
        eng._now += 1.0
        hub.feed_miss("t0", role="serving")
    assert hub.is_down("t0")
    hub.feed("t0", "serving.latency", 0.2, role="serving")
    assert not hub.is_down("t0")


def test_sim_thread_runs_scheduler_worker_synchronously():
    from distkeras_tpu.fleet.job import FleetJob
    from distkeras_tpu.fleet.scheduler import FleetScheduler

    eng = SimEngine(3)
    factory = SimThreadFactory(eng)
    rt = SimJobRuntime(eng, "tiny", lambda e, w: 0.1, rounds_target=40)
    sched = FleetScheduler(capacity=8, tick_s=0.5,
                           clock=eng.clock(), thread_factory=factory)
    job = sched.submit(FleetJob("tiny", "acme", rt, min_gang=2,
                                max_workers=8))

    def tick():
        sched.tick()
        if not sched.all_terminal():
            eng.after(0.5, tick)

    eng.after(0.0, tick)
    eng.run()
    sched.close()
    assert threading.active_count() == 1 or factory.created >= 8
    assert sched.stats()[job.job_id]["state"] == "done"
    assert rt.center.exactly_once()
    assert rt.rounds_done >= 40


def test_sim_runtime_crash_lose_ack_forces_deduped_retransmit():
    eng = SimEngine(1)
    rt = SimJobRuntime(eng, "j", lambda e, w: 0.2, rounds_target=10)
    th = SimThreadFactory(eng)(target=lambda: None)
    eng.current_thread = th
    rt.worker_main(0, lambda: True)
    eng.current_thread = None
    eng.run(until=1.05)  # ~4 commits land
    applied = rt.center.commits_total
    assert rt.crash(0, lose_ack=True)
    # respawn: the scheduler would re-run worker_main with a new thread
    eng.current_thread = SimThreadFactory(eng)(target=lambda: None)
    rt.worker_main(0, lambda: True)
    eng.current_thread = None
    eng.run()
    assert rt.center.duplicates == 1  # the resent seq was absorbed
    assert rt.center.exactly_once()
    assert rt.rounds_done == 10 == rt.center.commits_total
    assert rt.center.commits_total >= applied


# -- the timing model over a REAL traced loopback run -----------------------

@pytest.fixture(scope="module")
def traced_records(tmp_path_factory):
    """One real PSServer/PSClient loopback run with tracing on: the
    stream the timing model fits (the shape ``sim_drift`` takes).
    Returns (records, measured_commits_per_sec)."""
    from distkeras_tpu.netps.client import PSClient
    from distkeras_tpu.netps.server import PSServer
    from distkeras_tpu.telemetry.tracing import context as trace_context
    from distkeras_tpu.telemetry.tracing.collector import TelemetryCollector

    td = str(tmp_path_factory.mktemp("sim-traces"))
    saved = {k: os.environ.get(k) for k in ("DKTPU_TRACE",
                                            "DKTPU_TRACE_DIR")}
    os.environ["DKTPU_TRACE"] = "1"
    os.environ["DKTPU_TRACE_DIR"] = td
    trace_context._reset_stream()
    rounds = 12
    try:
        srv = PSServer(discipline="adag", host="127.0.0.1",
                       port=0).start()
        try:
            tmpl = [np.zeros(64, np.float32)]
            cl = PSClient(srv.endpoint, worker_id=0)
            cl.join(init=tmpl)
            t0 = time.perf_counter()
            for i in range(rounds):
                cl.commit([np.ones_like(a) for a in tmpl], i)
            dt = time.perf_counter() - t0
            cl.leave()
            cl.close()
        finally:
            srv.close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        trace_context._reset_stream()
    return TelemetryCollector.from_dir(td).records(), rounds / dt


def test_timing_model_fits_lifecycle_segments(traced_records):
    records, _rate = traced_records
    model = TimingModel.from_records(records)
    assert model.commits >= 10
    assert {"wire", "fold", "ack"} <= set(model.segments)
    desc = model.describe()
    for info in desc["segments"].values():
        assert info["count"] > 0 and info["mean_s"] >= 0.0
    eng = SimEngine(0)
    assert model.sample_service(eng) >= 0.0
    assert model.sample_commit_client(eng) >= 0.0


def test_sim_drift_predicts_real_loopback_within_structure(traced_records):
    records, rate = traced_records
    out = sim_drift(records, measured_tokens_per_sec=rate,
                    tokens_per_round=1.0)
    assert out["metric"] == "sim_predicted_vs_measured_tokens_per_sec"
    assert out["workers"] == 1 and out["rounds"] >= 10
    assert out["predicted_tokens_per_sec"] > 0
    assert isinstance(out["within_band"], bool)
    # prediction is deterministic given the records and seed
    again = sim_drift(records, measured_tokens_per_sec=rate,
                      tokens_per_round=1.0)
    assert again["predicted_tokens_per_sec"] \
        == out["predicted_tokens_per_sec"]


def test_predict_throughput_infers_workers_and_rounds(traced_records):
    records, _rate = traced_records
    out = predict_throughput(records=records, tokens_per_round=128.0)
    assert out["workers"] == 1
    assert out["commits_per_sec"] > 0
    assert out["tokens_per_sec"] == pytest.approx(
        128.0 * out["commits_per_sec"])


# -- calibration gates vs the recorded curve --------------------------------

def test_hier_crossover_gate_against_bench_summary():
    out = hier_crossover(summary=SUMMARY)
    assert out["within_band"], out
    assert out["crossover_reproduced"], out
    assert out["predicted_crossover_workers"] \
        == out["measured_crossover_workers"] == 4
    held_out = [p for p in out["points"] if p["held_out"]]
    assert len(held_out) >= 2  # flat W=4 and at least one hier point
    assert all(p["error_pct"] <= out["band_pct"] for p in held_out)
    # the topology's point: the root-ingress cut at the crossover
    assert out["measured_ingress_cut"] >= 2.5


def test_hier_crossover_is_seed_deterministic():
    a = hier_crossover(summary=SUMMARY, seed=5)
    b = hier_crossover(summary=SUMMARY, seed=5)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_hier_crossover_requires_its_curve(tmp_path, monkeypatch, capsys):
    """No curve named is an error, in the function and in the CLI, and a
    record lying in the working directory is not picked up in its place."""
    with open(SUMMARY, encoding="utf-8") as f:
        doctored = json.load(f)
    for row in doctored["configs"][0]["hier_curve"]:
        row["tokens_per_sec"] = 1.0
    (tmp_path / "BENCH_SUMMARY.json").write_text(json.dumps(doctored))
    monkeypatch.chdir(tmp_path)
    with pytest.raises(TypeError, match="summary"):
        hier_crossover()
    with pytest.raises(SystemExit) as exc:
        sim_main(["calibrate"])
    assert exc.value.code != 0
    assert "--summary" in capsys.readouterr().err
    out = hier_crossover(summary=SUMMARY)
    assert all(p["measured_tokens_per_sec"] > 1.0 for p in out["points"])


# -- scenario determinism + invariants --------------------------------------

def _canon(out: dict) -> str:
    return json.dumps(out, sort_keys=True)


def test_scenarios_are_bit_identical_per_seed():
    # round_s stretched so the small job is still running at BOTH
    # outages (the one_requeue_per_outage invariant needs a live job)
    a = run_scenario("failover_cascade", workers=24, seed=3, round_s=0.5)
    b = run_scenario("failover_cascade", workers=24, seed=3, round_s=0.5)
    assert _canon(a) == _canon(b)
    c = run_scenario("failover_cascade", workers=24, seed=4, round_s=0.5)
    assert _canon(a) != _canon(c)
    assert a["ok"] and c["ok"]  # every seed must satisfy the invariants


def test_alert_storm_determinism_and_invariants():
    a = run_scenario("alert_storm", seed=0)
    b = run_scenario("alert_storm", seed=0)
    assert _canon(a) == _canon(b)
    assert a["ok"], a["checks"]
    assert a["alerts"]["fired"] == a["alerts"]["cleared"]
    assert any(k.startswith("target_down:")
               for k in a["alerts"]["keys"])


def test_preemption_storm_full_scale_1000_workers():
    t0 = time.perf_counter()
    out = run_scenario("preemption_storm", workers=1000, seed=0)
    wall = time.perf_counter() - t0
    assert out["ok"], out["checks"]
    assert out["workers"] == 1000 and out["regions"] == 3
    assert wall < 60.0  # the acceptance bound, with huge margin
    assert out["checks"]["floors_never_violated"]
    assert out["checks"]["exactly_once"]
    assert out["alerts"]["fired"] >= 1


def test_failover_cascade_invariants():
    out = run_scenario("failover_cascade", seed=0)
    assert out["ok"], out["checks"]
    assert out["center"]["epochs"] == [0, 1, 2]
    assert out["center"]["value"] == float(out["center"]["commits"])
    assert out["center"]["duplicates"] >= 1


def test_region_partition_conserves_value_through_partition():
    out = run_scenario("region_partition", seed=0)
    assert out["ok"], out["checks"]
    st = out["staleness_by_region"]
    part = str(out["partitioned_region"])
    healthy = max(v for g, v in st.items() if g != part)
    assert st[part] > healthy


def test_unknown_scenario_is_a_typed_error():
    with pytest.raises(ValueError, match="unknown scenario"):
        run_scenario("nope")


# -- the CLI ----------------------------------------------------------------

def test_cli_run_and_calibrate_exit_contract(capsys):
    assert sim_main(["run", "alert_storm", "--seed", "0"]) == 0
    assert "OK" in capsys.readouterr().out
    assert sim_main(["calibrate", "--summary", SUMMARY]) == 0
    out = capsys.readouterr().out
    assert "crossover" in out and "OK" in out


def test_cli_run_json_is_parseable(capsys):
    assert sim_main(["run", "alert_storm", "--seed", "0", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] is True


def test_cli_report_renders_fitted_model(tmp_path, traced_records, capsys):
    # re-point report at a dir rebuilt from the fixture's records
    records, _rate = traced_records
    stream = tmp_path / "trace-test-1.jsonl"
    stream.write_text("\n".join(json.dumps(r) for r in records))
    assert sim_main(["report", "--trace-dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "timing model" in out and "fold" in out

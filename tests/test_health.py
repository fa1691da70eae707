"""Fleet health-plane tests (ISSUE 15): target parsing + the in-process
registry, SLO spec parsing and burn-rate math, the multi-window rule over
doctored hub rings, AlertManager fire/clear hysteresis (events, counters,
page -> flight dump), windowed span quantiles and reset-safe rate
derivation, the anomaly sentinels (target_down against a real PS,
drift/shed), readiness over
the stats op (PS primary vs standby, serving warmup) and the
readiness-aware ``ServeClient`` walk, the ``health``/``top``/``scrape``
CLIs (typed errors, ``--json``), the ``report --trace`` exit contract,
process vitals, and the Job/FleetScheduler liveness hooks."""

import json
import os
import socket
import threading
import time
from collections import deque

import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.telemetry.core import BUCKET_BOUNDS
from distkeras_tpu.telemetry.health import (
    AlertManager,
    MetricsHub,
    Sentinels,
    SloEngine,
    SloSpec,
    TargetState,
    parse_slo_specs,
    parse_targets,
    register_target,
    registered_targets,
    unregister_target,
)
from distkeras_tpu.telemetry.health import hub as hub_mod
from distkeras_tpu.telemetry.report import main as report_main
from distkeras_tpu.telemetry.tracing import recorder
from distkeras_tpu.telemetry.tracing import context as trace_context


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("DKTPU_HEALTH_TARGETS", "DKTPU_HEALTH_SLO",
                "DKTPU_TRACE", "DKTPU_TRACE_DIR", "DKTPU_VITALS_S"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    with hub_mod._registry_lock:
        hub_mod._registry.clear()
    trace_context._reset_stream()
    recorder._reset()
    yield
    with hub_mod._registry_lock:
        hub_mod._registry.clear()
    trace_context._reset_stream()
    recorder._reset()
    telemetry.reset()


def _events(kind):
    return [e for e in telemetry.get().events() if e.get("kind") == kind]


def _counters():
    return telemetry.get().snapshot()["counters"]


# ---------------------------------------------------------------------------
# Targets: parsing + the in-process registry
# ---------------------------------------------------------------------------

def test_parse_targets_named_bare_and_separators():
    spec = "ps=10.0.0.1:7077; serve0=10.0.0.2:9000 ,10.0.0.3:9001;;"
    assert parse_targets(spec) == {
        "ps": "10.0.0.1:7077",
        "serve0": "10.0.0.2:9000",
        "10.0.0.3:9001": "10.0.0.3:9001",
    }
    assert parse_targets("") == {}


def test_registry_register_update_unregister():
    assert register_target("h:1", "a") == "a"
    assert register_target("h:2") == "h:2"  # bare endpoint names itself
    register_target("h:9", "a")  # re-register moves the endpoint
    assert registered_targets() == {"a": "h:9", "h:2": "h:2"}
    unregister_target("a")  # by name
    unregister_target("h:2")  # by endpoint
    assert registered_targets() == {}


def test_env_targets_feed_the_hub(monkeypatch):
    monkeypatch.setenv("DKTPU_HEALTH_TARGETS", "adhoc=127.0.0.1:1")
    hub = MetricsHub(targets={"static": "127.0.0.1:2"})
    register_target("127.0.0.1:3", "registered")
    assert hub._known_targets() == {
        "adhoc": "127.0.0.1:1", "static": "127.0.0.1:2",
        "registered": "127.0.0.1:3"}
    # use_registry=False pins the hub to its explicit targets only.
    hermetic = MetricsHub(targets={"static": "127.0.0.1:2"},
                          use_registry=False)
    assert hermetic._known_targets() == {"static": "127.0.0.1:2"}


# ---------------------------------------------------------------------------
# SLO specs: parsing + burn math
# ---------------------------------------------------------------------------

def test_slo_parse_inline_file_and_single_object(tmp_path):
    inline = ('[{"name": "p99", "metric": "serving.latency", '
              '"stat": "p99", "max": 0.25, "severity": "page", '
              '"labels": {"tenant": "B"}}]')
    (spec,) = parse_slo_specs(inline)
    assert (spec.name, spec.stat, spec.max, spec.severity) == (
        "p99", "p99", 0.25, "page")
    assert spec.labels == {"tenant": "B"}
    # A single object (no list) and a file path both parse.
    assert parse_slo_specs('{"name": "x", "metric": "m", "min": 1}')[0].min == 1
    path = tmp_path / "slo.json"
    path.write_text(inline)
    assert parse_slo_specs(str(path))[0].name == "p99"
    # Default source is DKTPU_HEALTH_SLO; empty -> no specs.
    assert parse_slo_specs() == []


def test_slo_parse_rejections(tmp_path):
    with pytest.raises(ValueError, match="exactly one of max/min"):
        parse_slo_specs('{"name": "x", "metric": "m", "max": 1, "min": 1}')
    with pytest.raises(ValueError, match="exactly one of max/min"):
        parse_slo_specs('{"name": "x", "metric": "m"}')
    with pytest.raises(ValueError, match="severity"):
        parse_slo_specs(
            '{"name": "x", "metric": "m", "max": 1, "severity": "loud"}')
    with pytest.raises(ValueError, match="fast_s"):
        parse_slo_specs(
            '{"name": "x", "metric": "m", "max": 1, "fast_s": 60, '
            '"slow_s": 30}')
    with pytest.raises(ValueError, match="unknown keys"):
        parse_slo_specs('{"name": "x", "metric": "m", "max": 1, "oops": 2}')
    with pytest.raises(ValueError, match="name\\+metric"):
        parse_slo_specs('{"metric": "m", "max": 1}')
    with pytest.raises(ValueError, match="not found"):
        parse_slo_specs(str(tmp_path / "missing.json"))


def test_burn_rate_math_and_zero_guards():
    cap = SloSpec(name="c", metric="m", max=2.0)
    assert cap.burn(None) is None  # no data is not a breach
    assert cap.burn(1.0) == pytest.approx(0.5)
    assert cap.burn(4.0) == pytest.approx(2.0)
    degenerate = SloSpec(name="d", metric="m", max=0.0)
    assert degenerate.burn(0.0) == 0.0
    assert degenerate.burn(0.1) == float("inf")
    floor = SloSpec(name="f", metric="m", min=10.0)
    assert floor.burn(20.0) == pytest.approx(0.5)
    assert floor.burn(5.0) == pytest.approx(2.0)
    assert floor.burn(0.0) == float("inf")


# ---------------------------------------------------------------------------
# Doctored-ring hub math: windows, spans, rates
# ---------------------------------------------------------------------------

def _bare_hub(**kw):
    kw.setdefault("targets", {})
    kw.setdefault("use_registry", False)
    return MetricsHub(**kw)


def _inject(hub, name="t0", role=None):
    t = TargetState(name=name, endpoint="127.0.0.1:1", role=role,
                    ever_up=True)
    hub._targets[name] = t
    return t


def test_multiwindow_rule_fast_breach_needs_slow_confirmation():
    hub = _bare_hub()
    t = _inject(hub)
    now = time.time()
    ring = t.gauges["stale"] = deque(maxlen=64)
    for i in range(10):  # established normal, outside the fast window
        ring.append((now - 250 + i * 20, 0.2))
    for dt in (10.0, 5.0):  # a fresh spike
        ring.append((now - dt, 5.0))
    spec = SloSpec(name="stale", metric="stale", stat="mean",
                   max=1.0, fast_s=30.0, slow_s=300.0)
    engine = SloEngine([spec], alerts=AlertManager())
    out = engine.evaluate(hub)["stale"]
    # Fast window burns hot but the slow window vetoes the blip.
    assert out["burn_fast"] > 1.0 and out["burn_slow"] <= 1.0
    assert not out["breaching"]
    assert not engine.alerts.active()
    for i in range(10):  # the spike persists -> slow window confirms
        ring.append((now - 2 - i * 0.1, 5.0))
    out = engine.evaluate(hub)["stale"]
    assert out["burn_fast"] > 1.0 and out["burn_slow"] > 1.0
    assert out["breaching"] and engine.alerts.is_active("slo:stale")
    # Attainment counted evaluations-with-data; both breached fast.
    assert engine.attainment()["stale"] == 0.0


def test_measure_stats_globs_roles_and_absence():
    hub = _bare_hub()
    a = _inject(hub, "serveA", role="serving")
    b = _inject(hub, "serveB", role="serving")
    now = time.time()
    for t, v in ((a, 2.0), (b, 4.0)):
        t.gauges["serving.queue_depth"] = deque([(now - 1, v)])
    assert hub.measure("serving.queue_depth", stat="mean") == pytest.approx(3.0)
    assert hub.measure("serving.queue_depth", stat="max") == pytest.approx(4.0)
    assert hub.measure("serving.*", stat="value",
                       target="serveB") == pytest.approx(4.0)
    assert hub.measure("serving.*", stat="value",
                       target="serving") == pytest.approx(3.0)  # role glob
    assert hub.measure("serving.queue_depth", stat="value",
                       target="nomatch") is None
    assert hub.measure("absent.metric") is None
    names = hub.metric_names()
    assert "serving.queue_depth" in names["gauges"]


def test_span_window_quantile_is_windowed_not_since_boot():
    hub = _bare_hub()
    t = _inject(hub)
    now = time.time()
    lo_i, hi_i = 2, 10
    base = [0] * (len(BUCKET_BOUNDS) + 1)
    base[lo_i] = 100
    head = list(base)
    head[hi_i] = 10
    t.spans["serving.latency"] = deque([
        (now - 100, 100, 10.0, tuple(base)),   # before the fast window
        (now - 5, 110, 12.0, tuple(head)),     # inside it
    ])
    # Fast window diff = 10 slow requests only -> p99 lands in the high
    # bucket; the since-boot view (no base inside) is dominated by the
    # 100 fast ones.
    assert hub.measure("serving.latency", stat="p99",
                       window_s=30) == pytest.approx(BUCKET_BOUNDS[hi_i])
    assert hub.measure("serving.latency", stat="p50",
                       window_s=300) == pytest.approx(BUCKET_BOUNDS[lo_i])
    assert hub.measure("serving.latency", stat="span_mean",
                       window_s=30) == pytest.approx(0.2)


def test_rate_points_are_reset_safe():
    hub = _bare_hub()
    t = _inject(hub)
    t0 = time.time()
    hub._rate_point(t, "c", t0, 10.0)
    hub._rate_point(t, "c", t0 + 1.0, 20.0)
    hub._rate_point(t, "c", t0 + 2.0, 5.0)   # process restart: reset
    hub._rate_point(t, "c", t0 + 3.0, 8.0)   # re-based, not negative
    rates = [v for _, v in t.rates["c"]]
    assert rates == [pytest.approx(10.0), pytest.approx(3.0)]
    assert all(r >= 0 for r in rates)


# ---------------------------------------------------------------------------
# AlertManager: hysteresis, events, page -> flight dump
# ---------------------------------------------------------------------------

def test_alert_fire_and_clear_hysteresis():
    am = AlertManager(clear_after=2)
    assert am.update("k", True, message="hot",
                     labels={"tenant": "A"}) == "fired"
    assert am.update("k", True) is None  # still breaching: no re-fire
    assert am.is_active("k")
    assert am.update("k", False) is None  # first calm eval: held
    assert am.is_active("k")
    assert am.update("k", False) == "cleared"  # second calm eval: cleared
    assert not am.is_active("k")
    assert am.update("k", False) is None  # clearing a clear is a no-op
    assert (am.fired_total, am.cleared_total) == (1, 1)
    (fired,) = _events("health_alert")
    assert fired["alert"] == "k" and fired["tenant"] == "A"
    (cleared,) = _events("health_clear")
    assert cleared["alert"] == "k"
    snap = _counters()
    assert snap["health.alerts_fired"] == 1
    assert snap["health.alerts_cleared"] == 1
    # A breach mid-calm-streak resets the hysteresis counter.
    am.update("j", True)
    am.update("j", False)
    am.update("j", True)
    assert am.update("j", False) is None, "calm streak must restart"
    assert am.is_active("j")


def test_page_alert_drops_a_flight_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("DKTPU_TRACE", "1")
    monkeypatch.setenv("DKTPU_TRACE_DIR", str(tmp_path))
    recorder._reset()
    am = AlertManager()
    am.update("tick", True, severity="ticket")
    assert list(tmp_path.glob("flight-*")) == [], "tickets never dump"
    am.update("slo:p99", True, severity="page")
    (dump,) = list(tmp_path.glob("flight-*"))
    recs = [json.loads(line) for line in open(dump)]
    assert any(r.get("reason") == "health:slo:p99" for r in recs)
    # The alert's own event made it into the dumped ring.
    assert any(r.get("kind") == "health_alert" for r in recs)


# ---------------------------------------------------------------------------
# Sentinels
# ---------------------------------------------------------------------------

def test_drift_sentinel_fires_on_staleness_creep():
    hub = _bare_hub()
    t = _inject(hub)
    now = time.time()
    ring = t.gauges["netps.staleness_mean"] = deque(maxlen=64)
    for i in range(10):
        ring.append((now - 280 + i * 25, 1.5))  # steady, above the floor
    sn = Sentinels(alerts=AlertManager(clear_after=1))
    sn.evaluate(hub)
    assert not sn.alerts.is_active("staleness_creep"), "flat is healthy"
    for i in range(5):
        ring.append((now - 10 + i * 2, 9.0))  # recent >> established
    sn.evaluate(hub)
    assert sn.alerts.is_active("staleness_creep")


def test_shed_spike_fires_against_a_calm_baseline():
    hub = _bare_hub()
    t = _inject(hub)
    now = time.time()
    ring = t.rates["serving.shed"] = deque(maxlen=64)
    for i in range(6):
        ring.append((now - 280 + i * 40, 0.0))  # calm: no sheds
    sn = Sentinels(alerts=AlertManager(clear_after=1))
    sn.evaluate(hub)
    assert not sn.alerts.is_active("shed_spike")
    ring.append((now - 1, 2.0))  # sheds out of nowhere
    sn.evaluate(hub)
    assert sn.alerts.is_active("shed_spike")


def test_sentinels_read_no_file_from_cwd(tmp_path, monkeypatch):
    """The health plane judges the fleet by what the hub scraped, never
    by a benchmark record that happens to lie in the working directory."""
    (tmp_path / "BENCH_SUMMARY.json").write_text(json.dumps({"configs": [
        {"metric": "tok_per_sec", "value": 70.0, "pin": 100.0,
         "within_band": False, "vs_baseline": 0.7}]}))
    (tmp_path / "BENCH_PIN.json").write_text(json.dumps(
        {"weather_band_pct": 10, "configs": {"tp": {"pin": 100.0}}}))
    monkeypatch.chdir(tmp_path)
    hub = _bare_hub()
    t = _inject(hub)
    t.gauges["bench.tp"] = deque([(time.time() - 1, 80.0)])
    sn = Sentinels(alerts=AlertManager(clear_after=1))
    sn.evaluate(hub)
    assert not [key for key in sn.alerts.active()
                if key.startswith("bench_regression")]


# ---------------------------------------------------------------------------
# Live integration: hub vs a real PS, target_down fire + clear
# ---------------------------------------------------------------------------

def _ps(**kw):
    from distkeras_tpu.netps.server import PSServer

    kw.setdefault("discipline", "adag")
    kw.setdefault("host", "127.0.0.1")
    kw.setdefault("port", 0)
    return PSServer(**kw).start()


def test_hub_scrapes_ps_gauges_rates_and_clock():
    from distkeras_tpu.netps.client import PSClient

    srv = _ps()
    hub = _bare_hub(targets={"ps": srv.endpoint}, interval=30)
    client = PSClient(srv.endpoint, worker_id=0)
    sweeps = []
    hub.on_sweep(lambda h: sweeps.append(h.sweeps))
    try:
        tmpl = [np.zeros((2,), np.float32)]
        client.join(init=tmpl)
        assert hub.scrape_once() == 1
        for i in range(3):
            client.commit([np.ones_like(a) for a in tmpl], i)
        time.sleep(0.05)
        assert hub.scrape_once() == 1
        client.leave()
    finally:
        srv.close()
        hub.close()
    assert sweeps == [1, 2]
    t = hub.target("ps")
    assert t.status() == "UP" and t.ready is True and t.ever_up
    assert t.clock_offset_s is not None and abs(t.clock_offset_s) < 5.0
    assert hub.measure("stats.commits_total", stat="value") == 3.0
    # The commits landed between the two sweeps -> a positive rate.
    assert hub.measure("stats.commits_total", stat="rate",
                       window_s=60) > 0.0
    assert not hub.is_down("ps")


def test_target_down_fires_for_silent_ps_and_clears_on_return():
    srv = _ps()
    hub = _bare_hub(targets={"ps": srv.endpoint}, down_after=2,
                    timeout=0.5, interval=30)
    sn = Sentinels(alerts=AlertManager(clear_after=1))
    try:
        hub.scrape_once()
        sn.evaluate(hub)
        assert not sn.alerts.active()
        srv.close()
        hub.scrape_once()
        sn.evaluate(hub)
        assert not hub.is_down("ps"), "one miss is not an outage"
        hub.scrape_once()
        sn.evaluate(hub)
        assert hub.is_down("ps") and hub.is_down(srv.endpoint)
        assert hub.target("ps").status() == "DOWN"
        alert = sn.alerts.active()["target_down:ps"]
        assert alert.severity == "page" and alert.labels == {"target": "ps"}
        # The babysitter restarts the PS (new port); re-pointing the
        # target and answering one scrape clears the page.
        srv = _ps()
        hub.add_target(srv.endpoint, "ps")
        hub.scrape_once()
        sn.evaluate(hub)
        assert not sn.alerts.active()
        assert not hub.is_down("ps")
        (cleared,) = _events("health_clear")
        assert cleared["alert"] == "target_down:ps"
    finally:
        srv.close()
        hub.close()


def test_never_reached_target_is_pending_not_down():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    hub = _bare_hub(targets={"ghost": f"127.0.0.1:{port}"}, down_after=1)
    hub.scrape_once()
    t = hub.target("ghost")
    assert t.down and not t.ever_up
    assert t.status() == "DOWN" or t.status() == "PENDING"
    # is_down (the supervisor trigger) must stay False: never-up targets
    # are still binding, and shooting them would be a restart loop.
    assert not hub.is_down("ghost")
    assert hub.down_targets() == []


def test_standby_is_scraped_as_not_ready(tmp_path):
    from distkeras_tpu.netps.client import PSClient
    from distkeras_tpu.netps.standby import StandbyServer

    srv = _ps(state_dir=str(tmp_path / "state"))
    stb = StandbyServer(srv.endpoint, promote_after=30.0, host="127.0.0.1",
                        port=0, state_dir=str(tmp_path / "sb")).start()
    hub = _bare_hub(targets={"primary": srv.endpoint,
                             "standby": stb.endpoint}, interval=30)
    client = PSClient(srv.endpoint, worker_id=0)
    try:
        client.join(init=[np.zeros((2,), np.float32)])
        assert hub.scrape_once() == 2
        assert hub.target("primary").ready is True
        assert hub.target("standby").ready is False
        assert hub.target("standby").status() == "NOT-READY"
        assert not hub.is_down("standby"), "not-ready is not down"
    finally:
        stb.close()
        srv.close()
        hub.close()


# ---------------------------------------------------------------------------
# Readiness over the stats op + the readiness-aware ServeClient walk
# ---------------------------------------------------------------------------

def test_serving_readiness_and_prefer_ready_walk():
    from flax import linen as nn

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.netps.endpoints import EndpointWalker
    from distkeras_tpu.serving import (ModelRegistry, ServeClient,
                                       ServingFrontend)

    class TinyMLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(3)(nn.relu(nn.Dense(8)(x)))

    model = Model.build(TinyMLP(), np.zeros((2, 4), np.float32))
    reg_a = ModelRegistry(model, (1, 4))
    reg_b = ModelRegistry(model, (1, 4))
    a = ServingFrontend(reg_a, max_wait_s=0.002).start()
    b = ServingFrontend(reg_b, max_wait_s=0.002).start()
    client = ServeClient(f"{a.endpoint},{b.endpoint}",
                         timeout=2.0, retries=3, backoff=0.01)
    try:
        assert a.ready and b.ready
        # Replica a starts a hot swap: mid-warmup it reports not-ready
        # over the stats op, and the health-aware walk sinks it.
        reg_a.warming = True
        assert not a.ready
        hub = _bare_hub(targets={"a": a.endpoint, "b": b.endpoint})
        hub.scrape_once()
        assert hub.target("a").ready is False
        assert hub.target("a").status() == "NOT-READY"
        assert hub.target("b").ready is True
        order = client.prefer_ready(probe_timeout=0.5)
        assert order[0] == client._walker.endpoints[0]
        assert f"{order[0][0]}:{order[0][1]}" == b.endpoint
        assert f"{order[1][0]}:{order[1][1]}" == a.endpoint
        out, _ = client.infer(np.zeros((1, 4), np.float32))
        assert out.shape == (1, 3)
        # Swap done: both ready again. prefer_ready preserves relative
        # order WITHIN each class, so the walker stays on [b, a] — a
        # probe pass never shuffles healthy replicas for fun.
        reg_a.warming = False
        order = client.prefer_ready(probe_timeout=0.5)
        assert [f"{h}:{p}" for h, p in order] == [b.endpoint, a.endpoint]
        # reorder() is permutation-only: dropping an endpoint must raise.
        walker = EndpointWalker("h:1,h:2,h:3")
        walker.reorder(list(reversed(walker.endpoints)))
        assert walker.current() == ("h", 3)
        with pytest.raises(ValueError, match="permutation"):
            walker.reorder(walker.endpoints[:2])
    finally:
        client.close()
        a.close()
        b.close()
        reg_a.close()
        reg_b.close()


def test_serving_replica_set_registers_targets():
    from flax import linen as nn

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.serving.replica import ServingReplicaSet

    class TinyMLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(3)(nn.relu(nn.Dense(8)(x)))

    model = Model.build(TinyMLP(), np.zeros((2, 4), np.float32))
    rs = ServingReplicaSet(model, n=2, buckets=(1, 4), max_wait_s=0.002)
    try:
        rs.start()
        regs = registered_targets()
        assert "serve0" in regs and "serve1" in regs
        # A deliberate stop unregisters (must not page); a crash would
        # keep the registration so target_down can catch it.
        rs.stop_replica(0)
        assert "serve0" not in registered_targets()
        assert "serve1" in registered_targets()
    finally:
        rs.close()


# ---------------------------------------------------------------------------
# CLIs: health / top / scrape / report --trace
# ---------------------------------------------------------------------------

def test_health_cli_one_shot_text_json_and_exit_codes(
        tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)  # hermetic vs repo BENCH_* files
    srv = _ps()
    try:
        rc = report_main(["health", "--targets", f"ps={srv.endpoint}",
                          "--samples", "2", "--gap", "0.05"])
        text = capsys.readouterr().out
        assert rc == 0, "healthy fleet -> exit 0"
        assert "fleet health: 1/1 targets up" in text
        assert "ps" in text and "yes" in text
        # --json: same structure, machine-readable.
        rc = report_main(["health", "--targets", f"ps={srv.endpoint}",
                          "--samples", "1", "--json"])
        snap = json.loads(capsys.readouterr().out)
        assert rc == 0
        (target,) = snap["targets"]
        assert target["name"] == "ps" and target["status"] == "UP"
        assert target["ready"] is True
        # An impossible floor SLO breaches in both windows -> exit 1,
        # and the alert carries its labels into the summary.
        slo = json.dumps({"name": "commits", "metric": "stats.commits_total",
                          "stat": "value", "min": 1e9,
                          "labels": {"tenant": "acme"}})
        rc = report_main(["health", "--targets", f"ps={srv.endpoint}",
                          "--samples", "2", "--gap", "0.05",
                          "--slo", slo, "--json"])
        snap = json.loads(capsys.readouterr().out)
        assert rc == 1, "active alerts -> exit 1"
        (alert,) = snap["alerts"]
        assert alert["key"] == "slo:commits" and alert["tenant"] == "acme"
        assert snap["slos"]["commits"]["attainment"] == 0.0
    finally:
        srv.close()


def test_top_cli_bounded_iterations(tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    srv = _ps()
    try:
        rc = report_main(["top", "--targets", f"ps={srv.endpoint}",
                          "--interval", "0.05", "--iterations", "2",
                          "--no-clear"])
    finally:
        srv.close()
    out = capsys.readouterr().out
    assert rc == 0
    assert out.count("fleet health:") == 2, "one frame per iteration"
    assert "\x1b[2J" not in out, "--no-clear must not emit ANSI clears"


def test_scrape_cli_json_is_one_line(capsys):
    srv = _ps()
    try:
        assert report_main(["scrape", srv.endpoint, "--json"]) == 0
    finally:
        srv.close()
    out = capsys.readouterr().out
    assert out.count("\n") == 1, "--json is a single compact line"
    assert json.loads(out)["ok"] is True


def test_scrape_cli_typed_connection_refused(capsys):
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    rc = report_main(["scrape", f"127.0.0.1:{port}"])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert captured.err.count("\n") == 1, "one line, not a traceback"
    assert captured.err.startswith(
        f"scrape error: connection_refused: 127.0.0.1:{port}")


def test_scrape_cli_typed_timeout(capsys):
    silent = socket.socket()
    silent.bind(("127.0.0.1", 0))
    silent.listen(1)  # accepts the connect, never answers
    port = silent.getsockname()[1]
    try:
        rc = report_main(["scrape", f"127.0.0.1:{port}",
                          "--timeout", "0.2"])
    finally:
        silent.close()
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.err.startswith(
        f"scrape error: timeout: 127.0.0.1:{port}")


def test_report_trace_exit_contract_on_missing_and_empty(
        tmp_path, capsys):
    # Nonexistent path: operator error -> one stderr line, exit 2.
    missing = tmp_path / "never-made"
    assert report_main(["report", str(missing), "--trace"]) == 2
    captured = capsys.readouterr()
    assert captured.err.strip() == (
        f"trace report: no such file or directory: {missing}")
    # An existing dir with no records is a valid, boring answer: exit 0.
    empty = tmp_path / "empty"
    empty.mkdir()
    assert report_main(["report", str(empty), "--trace"]) == 0
    assert report_main(["report", str(empty), "--trace", "--json"]) == 0
    rep = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rep["commits"] == 0


def test_report_trace_discovers_rotated_only_streams(tmp_path, capsys):
    from distkeras_tpu.telemetry.tracing import TelemetryCollector

    # A stream whose live file was rotated away before the process died
    # exists only as `<base>.jsonl.N` — discovery must still find it.
    rotated = tmp_path / "rot"
    rotated.mkdir()
    (rotated / "ps.jsonl.1").write_text(
        json.dumps({"kind": "note", "ts": 1.0}) + "\n")
    (rotated / "ps.jsonl.2").write_text(
        json.dumps({"kind": "note", "ts": 2.0}) + "\n")
    recs = TelemetryCollector.from_dir(str(rotated)).records()
    assert [r["ts"] for r in recs] == [1.0, 2.0], "generations in order"
    assert all(r["stream"] == "ps.jsonl" for r in recs)
    assert report_main(["report", str(rotated), "--trace"]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# Process vitals
# ---------------------------------------------------------------------------

def test_vitals_sample_and_lifecycle(monkeypatch):
    from distkeras_tpu.telemetry import vitals

    out = vitals.sample_vitals()
    assert out["runtime.rss_mb"] > 1.0
    assert out["runtime.open_fds"] >= 3
    gauges = telemetry.get().snapshot()["gauges"]
    assert gauges["runtime.rss_mb"]["value"] == out["runtime.rss_mb"]
    assert gauges["runtime.open_fds"]["value"] == out["runtime.open_fds"]
    # Zero interval (the default) and the telemetry kill-switch are no-ops.
    assert vitals.start_vitals(0) is False
    assert vitals.start_vitals() is False, "DKTPU_VITALS_S defaults to off"
    monkeypatch.setattr(telemetry, "enabled", lambda: False)
    assert vitals.start_vitals(0.01) is False
    monkeypatch.undo()
    try:
        assert vitals.start_vitals(0.01) is True
        assert vitals.start_vitals(0.01) is True, "idempotent"
    finally:
        vitals.stop_vitals()
    vitals.stop_vitals()  # double-stop is fine


# ---------------------------------------------------------------------------
# Supervisor hooks: Job PS-plane mapping + FleetScheduler requeue
# ---------------------------------------------------------------------------

def test_job_maps_ps_roles_to_scrape_endpoints():
    from distkeras_tpu.job_deployment import Job, Punchcard

    pc = Punchcard(job_name="hp", script="t.py", hosts=["localhost"],
                   tenant="acme",
                   ps={"host": "127.0.0.1", "port": 7611,
                       "standby_host": "127.0.0.1", "standby_port": 7612})
    job = Job(pc)
    assert job._ps_endpoint_for_role("primary") == "127.0.0.1:7611"
    assert job._ps_endpoint_for_role("standby") == "127.0.0.1:7612"
    assert job._ps_endpoint_for_role("shard-0") is None
    # Nothing launched yet -> nothing registered.
    assert job.register_health_targets() == {}

    sharded = Job(Punchcard(
        job_name="hp2", script="t.py", hosts=["localhost"],
        ps={"host": "127.0.0.1", "shards": 2,
            "shard_ports": [7621, 7622]}))
    assert sharded._ps_endpoint_for_role("shard-0") == "127.0.0.1:7621"
    assert sharded._ps_endpoint_for_role("shard-1") == "127.0.0.1:7622"
    assert sharded._ps_endpoint_for_role("shard-0-standby") is None
    assert sharded._ps_endpoint_for_role("shard-9") is None
    assert sharded._ps_endpoint_for_role("primary") is None

    assert Job(Punchcard(job_name="nops", script="t.py",
                         hosts=["localhost"]))._ps_endpoint_for_role(
        "primary") is None


class _FakeProc:
    def __init__(self):
        self.killed = False

    def poll(self):
        return None if not self.killed else -9

    def kill(self):
        self.killed = True


class _Hook:
    """Duck-typed stand-in for MetricsHub.is_down."""

    def __init__(self):
        self.down = set()

    def is_down(self, endpoint):
        return endpoint in self.down


def test_job_liveness_kill_shoots_only_the_down_ps():
    from distkeras_tpu.job_deployment import Job, Punchcard

    pc = Punchcard(job_name="lk", script="t.py", hosts=["localhost"],
                   tenant="acme",
                   ps={"host": "127.0.0.1", "port": 7631,
                       "standby_host": "127.0.0.1", "standby_port": 7632})
    job = Job(pc)
    job._ps_proc = _FakeProc()
    job._standby_proc = _FakeProc()
    hook = _Hook()
    job._liveness_kill(hook)
    assert not job._ps_proc.killed and not job._standby_proc.killed
    hook.down.add("127.0.0.1:7631")
    job._liveness_kill(hook)
    assert job._ps_proc.killed, "the wedged primary gets SIGKILLed"
    assert not job._standby_proc.killed, "the healthy standby is spared"
    assert _counters()["resilience.liveness_kills"] == 1
    (ev,) = _events("liveness_kill")
    assert ev["role"] == "primary" and ev["endpoint"] == "127.0.0.1:7631"
    assert ev["tenant"] == "acme"
    # Registration names are tenant-prefixed <job>.<role>.
    regs = job.register_health_targets()
    assert regs == {"acme.lk.primary": "127.0.0.1:7631",
                    "acme.lk.standby": "127.0.0.1:7632"}
    assert registered_targets()["acme.lk.primary"] == "127.0.0.1:7631"


def test_fleet_scheduler_health_hook_requeues_once_per_outage():
    from distkeras_tpu.fleet import FleetJob, FleetScheduler
    from distkeras_tpu.fleet.job import RUNNING

    class EndpointRuntime:
        endpoint = "127.0.0.1:7641"

        def __init__(self):
            self.n = 0
            self.closed = False

        def ensure_started(self):
            pass

        def worker_main(self, wid, should_run):
            while should_run() and self.n < 100000:
                self.n += 1
                time.sleep(0.002)

        def progress(self):
            return self.n

        def done(self):
            return self.n >= 100000

        def revoke(self, wid):
            pass

        def close(self):
            self.closed = True

    def drive(sched, until, timeout=20.0):
        deadline = time.monotonic() + timeout
        while not until():
            assert time.monotonic() < deadline, "scenario timed out"
            sched.tick()
            time.sleep(0.002)

    hook = _Hook()
    sched = FleetScheduler(capacity=2, tick_s=0.01, health_hook=hook)
    rt = EndpointRuntime()
    job = sched.submit(FleetJob("svc", "acme", rt, min_gang=1,
                                max_workers=1))
    try:
        drive(sched, lambda: job.state == RUNNING)
        sched.tick()
        # A RUNNING job's endpoint is kept registered for scraping.
        assert registered_targets()["fleet.acme.svc"] == rt.endpoint
        hook.down.add(rt.endpoint)
        drive(sched, lambda: _counters().get(
            "fleet.liveness_requeues") == 1.0)
        (ev,) = _events("fleet_liveness_requeue")
        assert ev["tenant"] == "acme" and ev["endpoint"] == rt.endpoint
        # Still down across later ticks: one requeue per outage, not per
        # tick (the job re-places and keeps running meanwhile).
        for _ in range(8):
            sched.tick()
            time.sleep(0.002)
        assert _counters()["fleet.liveness_requeues"] == 1.0
        # Recovery then a SECOND outage earns its own requeue.
        hook.down.clear()
        drive(sched, lambda: job.state == RUNNING)
        sched.tick()
        hook.down.add(rt.endpoint)
        drive(sched, lambda: _counters().get(
            "fleet.liveness_requeues") == 2.0)
        hook.down.clear()
        drive(sched, lambda: job.state == RUNNING)
        assert job.requeues >= 2
    finally:
        sched.close()
    assert sched.floor_violations == 0

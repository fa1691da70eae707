"""CI health-chaos smoke (not a pytest module — run directly).

The fleet health plane watching a small fleet while chaos happens to it,
then the same fleet fault-free as a false-positive control:

**Faulted leg** — 2 in-process serving replicas under ``serve_slow``
(three 0.35 s reply holds among ~40 requests) and 1 parameter-server
subprocess carrying ``ps_crash@8`` in its own fault plan, all scraped by
one :class:`MetricsHub` with a page-severity p99 SLO:

* the **p99 SLO alert fires within one fast window** of the holds (the
  windowed span-diff quantile sees them; both burn windows confirm);
* the page alert **drops a flight-recorder dump** whose reason names it;
* the SIGKILLed PS flips to ``target_down`` within one fast window of
  the crash, and the alert **CLEARS** after a babysitter relaunches the
  server on the same port (clear hysteresis: two calm sweeps);
* ``telemetry health --json`` against the recovered fleet exits 0.

**Control leg** — the identical fleet, SLOs, and load with zero faults:
the run must end with **zero alerts fired** (a sentinel that cries wolf
is worse than none).

    python tests/smoke_health_chaos.py

All seeds and fault indices are pinned, so reruns schedule the same
chaos.
"""

import os
import sys

# Runs from a checkout without installation: sys.path[0] is tests/, so the
# repo root must be appended (an installed distkeras_tpu still wins).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(_REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("DKTPU_NET_TIMEOUT", "1.0")
os.environ.setdefault("DKTPU_NET_RETRIES", "3")
os.environ.setdefault("DKTPU_NET_BACKOFF", "0.02")
# Trace on: the page alert must prove it dumped the flight ring.
os.environ.setdefault("DKTPU_TRACE", "1")

import glob  # noqa: E402
import json  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

#: serving requests per leg, and the (pinned) global request indices the
#: frontend holds for HOLD_S — 3/40 > 1%, so the windowed p99 sees them.
REQUESTS = 40
SLOW_AT = (10, 14, 18)
HOLD_S = 0.35

#: the PS subprocess's own plan: SIGKILL just before folding commit 8.
PS_CRASH_AT = 8
PS_COMMITS = 16

SLO_SPECS = [
    {"name": "serve-p99", "metric": "serving.latency", "stat": "p99",
     "max": 0.08, "fast_s": 2.0, "slow_s": 4.0, "severity": "page",
     "target": "serve*", "labels": {"tenant": "acme", "job": "serve"}},
]

HUB_INTERVAL = 0.2
DOWN_AFTER = 2


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_ps(port: int, state_dir: str, faults: str = ""):
    """One PS subprocess with ITS OWN fault plan (never the smoke's)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("DKTPU_NET_FAULTS", "DKTPU_FAULTS_STATE")}
    env["JAX_PLATFORMS"] = "cpu"
    # The smoke chdirs to a scratch dir; the child must still import the
    # checkout.
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if faults:
        env["DKTPU_NET_FAULTS"] = faults
    return subprocess.Popen(
        [sys.executable, "-m", "distkeras_tpu.netps", "--host", "127.0.0.1",
         "--port", str(port), "--discipline", "adag", "--lease", "2.0",
         "--state-dir", state_dir], env=env)


def _wait(predicate, timeout: float, what: str) -> float:
    t0 = time.monotonic()
    while not predicate():
        elapsed = time.monotonic() - t0
        assert elapsed < timeout, f"timed out after {timeout}s: {what}"
        time.sleep(0.05)
    return time.monotonic() - t0


def _build_fleet(trace_dir: str, ps_faults: str):
    """(replica set, ps proc, ps endpoint, hub, engine, alerts)."""
    from flax import linen as nn

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.serving import ServingReplicaSet
    from distkeras_tpu.telemetry.health import (AlertManager, MetricsHub,
                                                Sentinels, SloEngine,
                                                parse_slo_specs,
                                                register_target)

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(3)(nn.relu(nn.Dense(8)(x)))

    model = Model.build(MLP(), np.zeros((2, 4), np.float32), seed=0)
    rs = ServingReplicaSet(model, n=2, buckets=(1, 4),
                           max_wait_s=0.003).start()  # registers serve0/1
    port = _free_port()
    state_dir = tempfile.mkdtemp(prefix="dktpu-health-ps-")
    proc = _launch_ps(port, state_dir, faults=ps_faults)
    endpoint = f"127.0.0.1:{port}"
    register_target(endpoint, "ps")

    alerts = AlertManager(clear_after=2)
    engine = SloEngine(parse_slo_specs(json.dumps(SLO_SPECS)),
                       alerts=alerts)
    sentinels = Sentinels(alerts=alerts)
    hub = MetricsHub(interval=HUB_INTERVAL, down_after=DOWN_AFTER,
                     timeout=0.5)
    hub.on_sweep(engine.evaluate)
    hub.on_sweep(sentinels.evaluate)
    hub.start()
    return rs, proc, endpoint, hub, engine, alerts


def _drive_load(rs, endpoint: str) -> tuple:
    """The two tenants' load: serving inference + PS training commits.
    Returns (answered, commits_before_crash_or_done)."""
    from distkeras_tpu.netps.client import PSClient
    from distkeras_tpu.serving import ServeClient

    client = ServeClient(rs.endpoints(), timeout=3.0, retries=3,
                         backoff=0.02)
    rng = np.random.default_rng(11)
    answered = 0
    for _ in range(REQUESTS):
        rows = int(rng.integers(1, 5))
        out, _v = client.infer(
            rng.standard_normal((rows, 4)).astype(np.float32))
        assert out.shape == (rows, 3)
        answered += 1
    client.close()

    ps = PSClient(endpoint, worker_id=0)
    tmpl = [np.zeros((4,), np.float32)]
    commits = 0
    try:
        ps.join(init=tmpl)
        for i in range(PS_COMMITS):
            ps.commit([np.ones_like(a) for a in tmpl], i)
            commits += 1
            time.sleep(0.02)
        ps.leave()
    except Exception:
        pass  # ps_crash mid-commit: the crash is the point
    finally:
        try:
            ps.close()
        except Exception:
            pass
    return answered, commits


def _teardown(rs, proc, hub) -> None:
    hub.close()
    rs.close()
    if proc.poll() is None:
        proc.terminate()
        proc.wait(timeout=10.0)


def faulted_leg(trace_dir: str) -> None:
    from distkeras_tpu import telemetry
    from distkeras_tpu.serving.frontend import reset_request_index
    from distkeras_tpu.telemetry.report import main as report_main

    telemetry.reset()
    reset_request_index()
    rs, proc, endpoint, hub, engine, alerts = _build_fleet(
        trace_dir, ps_faults=f"ps_crash@{PS_CRASH_AT};seed=1")
    print(f"[smoke] faulted leg: replicas={rs.endpoints()} ps={endpoint} "
          f"faults={os.environ['DKTPU_NET_FAULTS']} + "
          f"ps_crash@{PS_CRASH_AT}")
    try:
        _wait(lambda: not hub.is_down(endpoint) and hub.target("ps")
              and hub.target("ps").ever_up, 15.0, "PS never came up")
        answered, commits = _drive_load(rs, endpoint)
        assert answered == REQUESTS, (answered, REQUESTS)
        assert commits >= PS_CRASH_AT - 1, (
            f"PS died too early: {commits} commits")

        # (1) The slow holds must page the p99 SLO within one fast window.
        lat = _wait(lambda: alerts.is_active("slo:serve-p99"),
                    SLO_SPECS[0]["fast_s"] + 3.0,
                    "p99 SLO alert never fired")
        print(f"[smoke] p99 page alert fired {lat:.2f}s after load "
              f"(fast window {SLO_SPECS[0]['fast_s']}s)")
        alert = alerts.active()["slo:serve-p99"]
        assert alert.severity == "page"
        assert alert.labels == {"tenant": "acme", "job": "serve"}

        # (2) The page alert dropped a flight dump naming itself.
        def page_dump():
            for path in glob.glob(os.path.join(trace_dir, "flight-*")):
                with open(path, encoding="utf-8") as f:
                    for line in f:
                        try:
                            rec = json.loads(line)
                        except ValueError:
                            continue
                        if rec.get("reason") == "health:slo:serve-p99":
                            return path
            return None

        _wait(lambda: page_dump() is not None, 10.0,
              "page alert left no flight dump")
        print(f"[smoke] flight dump for the page: {page_dump()}")

        # (3) The crashed PS flips to target_down within one fast window.
        _wait(lambda: proc.poll() is not None, 30.0,
              "ps_crash never killed the PS subprocess")
        t_crash = time.monotonic()
        _wait(lambda: alerts.is_active("target_down:ps"),
              DOWN_AFTER * HUB_INTERVAL + SLO_SPECS[0]["fast_s"] + 3.0,
              "target_down:ps never fired")
        det = time.monotonic() - t_crash
        assert hub.is_down("ps") and hub.is_down(endpoint)
        down = alerts.active()["target_down:ps"]
        assert down.severity == "page"
        print(f"[smoke] target_down:ps fired {det:.2f}s after the SIGKILL")

        # (4) The babysitter restarts the PS on the SAME port; the alert
        # clears after two calm sweeps, never by hand.
        port = int(endpoint.rsplit(":", 1)[1])
        state_dir = tempfile.mkdtemp(prefix="dktpu-health-ps2-")
        proc = _launch_ps(port, state_dir)
        _wait(lambda: not alerts.is_active("target_down:ps"), 30.0,
              "target_down:ps never cleared after the restart")
        assert not hub.is_down("ps")
        cleared = [e for e in telemetry.get().events()
                   if e.get("kind") == "health_clear"
                   and e.get("alert") == "target_down:ps"]
        assert cleared, "no health_clear event for the recovery"
        print("[smoke] target_down:ps CLEARED after babysitter restart")

        # (5) The operator CLI agrees with the in-process plane.
        hub.close()  # one reader at a time on the sockets
        rc = report_main(["health", "--targets",
                          f"ps={endpoint};{rs.endpoints()}",
                          "--samples", "2", "--gap", "0.3", "--json"])
        assert rc == 0, "recovered fleet must scrape healthy (exit 0)"
        fired = alerts.fired_total
        assert fired >= 2, f"expected p99 + target_down fires, saw {fired}"
    finally:
        _teardown(rs, proc, hub)


def control_leg(trace_dir: str) -> None:
    from distkeras_tpu import telemetry
    from distkeras_tpu.resilience import faults
    from distkeras_tpu.serving.frontend import reset_request_index

    os.environ.pop("DKTPU_NET_FAULTS", None)
    faults.set_net_plan(None)
    telemetry.reset()
    reset_request_index()
    rs, proc, endpoint, hub, engine, alerts = _build_fleet(trace_dir,
                                                           ps_faults="")
    print(f"[smoke] control leg: replicas={rs.endpoints()} ps={endpoint} "
          f"(no faults)")
    try:
        _wait(lambda: hub.target("ps") and hub.target("ps").ever_up,
              15.0, "PS never came up")
        answered, commits = _drive_load(rs, endpoint)
        assert answered == REQUESTS and commits == PS_COMMITS
        # Let both burn windows close over the healthy data.
        time.sleep(SLO_SPECS[0]["slow_s"] + 2 * HUB_INTERVAL)
        assert alerts.fired_total == 0, (
            f"fault-free control fired {alerts.fired_total} alert(s): "
            f"{[h for h in alerts.history if h['event'] == 'fired']}")
        assert not alerts.active()
        print(f"[smoke] control: {answered} requests, {commits} commits, "
              f"0 alerts")
    finally:
        _teardown(rs, proc, hub)


def main() -> int:
    trace_dir = tempfile.mkdtemp(prefix="dktpu-health-smoke-")
    # Scratch cwd: the CLI's sentinels read BENCH_* files relative to
    # cwd, and a checkout's real bench results must not leak in.
    os.chdir(trace_dir)
    os.environ.setdefault("DKTPU_TRACE_DIR", trace_dir)
    os.environ.setdefault(
        "DKTPU_NET_FAULTS",
        ";".join(f"serve_slow@{i}:{HOLD_S}" for i in SLOW_AT) + ";seed=7")
    faulted_leg(os.environ["DKTPU_TRACE_DIR"])
    control_leg(os.environ["DKTPU_TRACE_DIR"])
    print("[smoke] OK: p99 page within the fast window + flight dump, "
          "target_down fired and cleared across the PS restart, "
          "control leg fired zero alerts")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Telemetry subsystem tests (ISSUE 1): span nesting/timing, JSONL +
Prometheus round-trips, straggler flagging, staleness gauges vs the
disciplines' deterministic rotation, MetricsLogger context-manager behavior,
and the acceptance path — a report rendered from JSONLs produced by REAL
SynchronousDistributedTrainer and ADAG runs."""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.telemetry.core import Telemetry
from distkeras_tpu.telemetry.exporters import (
    parse_prometheus,
    prometheus_text,
    read_jsonl,
    write_jsonl,
)
from distkeras_tpu.telemetry.report import build_report, render_report
from distkeras_tpu.telemetry.training import (
    DisciplineMonitor,
    dynsgd_scales,
    flag_stragglers,
    staleness_schedule,
)


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


# -- core primitives --------------------------------------------------------
def test_span_records_duration():
    t = Telemetry()
    with t.span("work"):
        time.sleep(0.01)
    h = t.histogram("work")
    assert h.count == 1
    assert 0.005 < h.total < 1.0


def test_span_nesting_paths_and_containment():
    t = Telemetry()
    with t.span("outer"):
        with t.span("inner"):
            time.sleep(0.005)
        with t.span("inner"):
            pass
    snap = t.snapshot()["spans"]
    assert set(snap) == {"outer", "outer/inner"}
    assert snap["outer/inner"]["count"] == 2
    # Parent wall time contains the children's.
    assert snap["outer"]["total"] >= snap["outer/inner"]["total"]


def test_span_nesting_is_per_thread():
    import threading

    t = Telemetry()
    done = threading.Event()

    def worker():
        with t.span("bg"):
            done.wait(1.0)

    th = threading.Thread(target=worker)
    with t.span("fg"):
        th.start()
        time.sleep(0.01)
    done.set()
    th.join()
    # The background span must NOT nest under the foreground one.
    assert "bg" in t.snapshot()["spans"]
    assert "fg/bg" not in t.snapshot()["spans"]


def test_counter_gauge_histogram_aggregates():
    t = Telemetry()
    t.counter("c").add(2)
    t.counter("c").add(3)
    for v in (1.0, 2.0, 3.0):
        t.gauge("g").set(v)
    for v in (0.001, 0.01, 0.1):
        t.histogram("h").observe(v)
    snap = t.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == {
        "value": 3.0, "count": 3, "mean": 2.0, "min": 1.0, "max": 3.0}
    assert snap["spans"]["h"]["count"] == 3
    assert abs(snap["spans"]["h"]["total"] - 0.111) < 1e-9


def test_disabled_registry_is_noop():
    t = Telemetry(enabled=False)
    with t.span("x"):
        pass
    t.counter("c").add(1)
    t.gauge("g").set(1)
    t.histogram("h").observe(1)
    t.event("e", {"a": 1})
    snap = t.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "spans": {}}
    assert t.events() == []


def test_span_overhead_is_small():
    """The instrumentation-cost bound underlying the <=2% overhead budget:
    a span costs a few µs; hot paths (fold rounds, native gathers) are
    hundreds of µs to ms. Generous bound so CI boxes can't flake."""
    t = Telemetry()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with t.span("hot"):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 100e-6, f"span cost {per_span * 1e6:.1f}us"


# -- exporters --------------------------------------------------------------
def test_jsonl_round_trip(tmp_path):
    t = Telemetry()
    t.counter("rounds").add(4)
    t.event("custom", {"round": -1, "tag": "x"})
    with t.span("phase"):
        pass
    path = str(tmp_path / "t.jsonl")
    write_jsonl(t, path, extra={"run": "r1"})
    recs = read_jsonl(path)
    summary = [r for r in recs if r.get("kind") == "telemetry_summary"]
    assert len(summary) == 1
    assert summary[0]["counters"]["rounds"] == 4
    assert summary[0]["spans"]["phase"]["count"] == 1
    assert summary[0]["run"] == "r1"
    assert any(r.get("kind") == "custom" for r in recs)
    # Append-only: a second dump adds a second summary, clobbers nothing.
    write_jsonl(t, path)
    assert len([r for r in read_jsonl(path)
                if r.get("kind") == "telemetry_summary"]) == 2


def test_prometheus_round_trip():
    t = Telemetry()
    t.counter("native.gather_calls").add(7)
    t.gauge("feeder.queue_depth").set(2)
    for v in (0.001, 0.25, 0.25):
        t.histogram("dispatch[blocked]").observe(v)
    text = prometheus_text(t)
    parsed = parse_prometheus(text)
    assert parsed["dktpu_counter_total"][
        (("name", "native_gather_calls"),)] == 7
    assert parsed["dktpu_gauge"][(("name", "feeder_queue_depth"),)] == 2
    label = ("span", "dispatch_blocked_")
    assert parsed["dktpu_span_seconds_count"][(label,)] == 3
    assert abs(parsed["dktpu_span_seconds_sum"][(label,)] - 0.501) < 1e-9
    # Cumulative buckets: the +Inf bucket equals the count.
    inf = parsed["dktpu_span_seconds_bucket"][(label, ("le", "+Inf"))]
    assert inf == 3
    # A mid bucket holds the 0.001 observation but not the 0.25 pair.
    le_01 = [v for k, v in parsed["dktpu_span_seconds_bucket"].items()
             if k[0] == label and k[1][1] not in ("+Inf",)
             and float(k[1][1]) >= 0.001 and float(k[1][1]) < 0.25]
    assert le_01 and all(v >= 1 for v in le_01)


# -- straggler heuristic ----------------------------------------------------
def test_flag_stragglers_synthetic():
    times = [1.0, 1.1, 0.9, 1.0, 5.0, 1.05, 2.3]
    assert flag_stragglers(times, k=2.0) == [4, 6]
    assert flag_stragglers(times, k=4.0) == [4]
    assert flag_stragglers([1.0, 9.0]) == []  # too few samples to anchor
    assert flag_stragglers([0.0, 0.0, 0.0]) == []  # degenerate median


# -- staleness vs disciplines.py -------------------------------------------
def test_staleness_schedule_matches_dynsgd_commit_scale():
    """The host-side schedule must reproduce DynSGDFold.commit's scale
    1/(((worker_id + fold_state) % W) + 1) exactly, for every (round, worker).
    """
    from distkeras_tpu.parallel.disciplines import DynSGDFold

    W = 5
    disc = DynSGDFold()
    center = {"w": jnp.zeros(3)}
    local = {"w": jnp.ones(3)}
    for r in range(2 * W):
        stale = staleness_schedule(disc, r, W)
        scales = dynsgd_scales(stale)
        for i in range(W):
            commit, _ = disc.commit(
                center, local, jnp.asarray(r, jnp.int32),
                worker_id=jnp.asarray(i, jnp.int32), window=4, num_workers=W)
            # delta == 1, so the commit value IS the fold scale.
            np.testing.assert_allclose(
                np.asarray(commit["w"][0]), scales[i], rtol=1e-6)
            assert stale[i] == (i + r) % W


def test_staleness_schedule_non_communicating_is_none():
    from distkeras_tpu.parallel.disciplines import EnsembleFold

    assert staleness_schedule(EnsembleFold(), 0, 4) is None
    assert staleness_schedule(None, 0, 4) is None


def test_discipline_monitor_fields_and_gauges():
    from distkeras_tpu.parallel.disciplines import DynSGDFold

    t = Telemetry()
    mon = DisciplineMonitor(DynSGDFold(), num_workers=4, telemetry=t)
    loss = np.array([1.0, 2.0, 3.0, 4.0])
    fields = mon.round_fields(1, loss, round_seconds=0.1)
    assert fields["staleness"] == [1, 2, 3, 0]
    np.testing.assert_allclose(
        fields["dynsgd_scale"], [1 / 2, 1 / 3, 1 / 4, 1 / 1], atol=1e-6)
    np.testing.assert_allclose(
        fields["loss_divergence"], [-1.5, -0.5, 0.5, 1.5])
    assert t.gauge("discipline.staleness_mean").value == 1.5
    assert t.gauge("discipline.loss_divergence_max").value == 1.5


def test_discipline_monitor_flags_live_stragglers():
    t = Telemetry()
    mon = DisciplineMonitor(None, num_workers=1, telemetry=t)
    loss = np.float32(1.0)
    for r, dt in enumerate([0.1, 0.1, 0.1, 0.1]):
        assert "straggler" not in mon.round_fields(r, loss, round_seconds=dt)
    assert mon.round_fields(4, loss, round_seconds=0.5)["straggler"] is True
    assert t.counter("discipline.straggler_rounds").value == 1


def test_discipline_monitor_ignores_burst_tails():
    """Blocked/auto execution delivers burst-tail callbacks; callers pass
    round_seconds=None for them (MetricsLogger derives the signal from the
    engine's state contract) — tails must not poison the straggler median
    or be flagged, while genuinely slow blocks still flag."""
    t = Telemetry()
    mon = DisciplineMonitor(None, num_workers=1, telemetry=t)
    loss = np.float32(1.0)
    # 4 blocks of R=4: one real timing boundary + 3 burst tails per block.
    for block in range(4):
        fields = mon.round_fields(block * 4, loss, round_seconds=0.2)
        assert "straggler" not in fields, f"block {block} flagged"
        for j in (1, 2, 3):
            fields = mon.round_fields(block * 4 + j, loss,
                                      round_seconds=None)
            assert "straggler" not in fields
    assert t.counter("discipline.straggler_rounds").value == 0
    # A genuinely slow block still flags against the block-time median.
    assert mon.round_fields(16, loss, round_seconds=1.0)["straggler"] is True


# -- MetricsLogger ----------------------------------------------------------
def test_metrics_logger_context_manager_and_idempotent_close(tmp_path):
    from distkeras_tpu.metrics import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path, samples_per_round=8) as logger:
        logger(0, np.float32(1.0))
        logger(1, np.float32(0.5))
        assert logger._file is not None
    assert logger._file is None  # __exit__ closed it
    logger.close()  # idempotent: second close is a no-op
    logger.close()
    recs = read_jsonl(path)
    rounds = [r for r in recs if "round" in r and "kind" not in r]
    assert [r["round"] for r in rounds] == [0, 1]
    # close() appended the registry summary — one file serves the report.
    assert any(r.get("kind") == "telemetry_summary" for r in recs)


def test_metrics_logger_feeds_telemetry(tmp_path):
    from distkeras_tpu.metrics import MetricsLogger

    t = Telemetry()
    with MetricsLogger(str(tmp_path / "m.jsonl"), telemetry=t) as logger:
        logger(0, np.float32(2.0))
    snap = t.snapshot()
    assert snap["counters"]["rounds"] == 1
    assert snap["gauges"]["loss"]["value"] == 2.0
    assert snap["spans"]["round_seconds"]["count"] == 1


def test_metrics_logger_burst_attribution_blocked_contract(tmp_path,
                                                           monkeypatch):
    """The wired path: run_blocked fires callback bursts where the FIRST
    call of a block absorbs the whole block's wall time in dt but only the
    LAST call carries a state. The logger must mark boundaries as
    first-after-a-state-bearing-call — NOT the state-bearing calls
    themselves — or the straggler median anchors on JSONL-write jitter and
    a genuinely slow block never flags."""
    import types

    from distkeras_tpu import metrics
    from distkeras_tpu.telemetry.training import DisciplineMonitor

    # The logger reads a clock the test owns: a block's wall is what the
    # test says it is, whatever else the box is doing.
    now = [100.0]
    monkeypatch.setattr(metrics, "time", types.SimpleNamespace(
        perf_counter=lambda: now[0], time=time.time))
    t = Telemetry()
    mon = DisciplineMonitor(None, num_workers=1, telemetry=t)
    with metrics.MetricsLogger(str(tmp_path / "b.jsonl"), telemetry=t,
                               monitor=mon) as logger:
        state = object()
        R = 4
        for block in range(5):
            # A block's wall lands on j=0's dt: 10 ms, and 1 s for the slow
            # one; the burst's other callbacks follow 2 us apart.
            now[0] += 1.0 if block == 4 else 0.010
            for j in range(R):
                logger(block * R + j, np.float32(1.0),
                       state if j == R - 1 else None)
                now[0] += 2e-6
    recs = logger.records
    # Block-first records are boundaries; everything else is a tail —
    # including the state-bearing block-final records. The marker is
    # explicit on EVERY record (False on boundaries), so readers never fall
    # back to the dt threshold for new-format files.
    for i, r in enumerate(recs):
        assert r.get("burst_tail") is (i % R != 0), f"record {i} mismarked"
    # The slow block flags on its FIRST record (where its wall time lives).
    assert recs[16].get("straggler") is True
    assert not any(r.get("straggler") for r in recs[:16])


# -- report CLI -------------------------------------------------------------
def _write_rounds(path, rows):
    with open(path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


def test_report_straggler_table_and_segments(tmp_path):
    path = str(tmp_path / "r.jsonl")
    rows = [
        {"round": r, "loss": 1.0, "round_seconds": 0.1,
         "samples_per_sec": 100.0}
        for r in range(6)
    ]
    rows[3]["round_seconds"] = 0.9  # the planted straggler
    _write_rounds(path, rows)
    rep = build_report(path)
    assert rep["rounds"] == 6
    assert [s["round"] for s in rep["stragglers"]] == [3]
    assert rep["stragglers"][0]["x_median"] == 9.0
    text = render_report(rep)
    assert "Stragglers" in text and "Throughput segments" in text


def test_report_stragglers_exclude_burst_tails(tmp_path):
    """Offline twin of the live-monitor rule: blocked-run JSONLs (µs
    burst-tail rounds) must not flag every block-final round."""
    path = str(tmp_path / "blocked.jsonl")
    rows = []
    for block in range(5):
        rows.append({"round": block * 4, "loss": 1.0, "round_seconds": 0.2})
        rows += [{"round": block * 4 + j, "loss": 1.0, "round_seconds": 2e-6}
                 for j in (1, 2, 3)]
    rows.append({"round": 20, "loss": 1.0, "round_seconds": 0.9})  # real one
    _write_rounds(path, rows)
    rep = build_report(path)
    assert [s["round"] for s in rep["stragglers"]] == [20]


def test_telemetry_mark_delta_windows_runs():
    """Sequential runs share the process registry; a mark window must
    report only the second run's activity (counters/spans subtract, events
    slice)."""
    t = Telemetry()
    t.counter("rounds").add(5)
    with t.span("dispatch"):
        pass
    t.event("bench_config", {"run": 1})
    m = t.mark()
    t.counter("rounds").add(3)
    with t.span("dispatch"):
        pass
    with t.span("dispatch"):
        pass
    t.event("bench_config", {"run": 2})
    summary, events = t.delta(m)
    assert summary["counters"] == {"rounds": 3.0}
    assert summary["spans"]["dispatch"]["count"] == 2
    assert [e["run"] for e in events] == [2]
    # An untouched metric does not appear in the window at all.
    assert "loss" not in summary["gauges"]


def test_metrics_logger_summary_is_per_run(tmp_path):
    """Two back-to-back MetricsLogger runs on the shared registry: run 2's
    JSONL summary must not re-attribute run 1's rounds."""
    from distkeras_tpu.metrics import MetricsLogger

    t = Telemetry()
    p1, p2 = str(tmp_path / "r1.jsonl"), str(tmp_path / "r2.jsonl")
    with MetricsLogger(p1, telemetry=t) as l1:
        for r in range(4):
            l1(r, np.float32(1.0))
    with MetricsLogger(p2, telemetry=t) as l2:
        l2(0, np.float32(1.0))
    s2 = [r for r in read_jsonl(p2) if r.get("kind") == "telemetry_summary"]
    assert s2[0]["counters"]["rounds"] == 1  # not 5
    assert s2[0]["spans"]["round_seconds"]["count"] == 1


def test_report_burst_grouping(tmp_path):
    # Blocked execution: one real timing boundary + burst tail of ~0s rounds.
    path = str(tmp_path / "b.jsonl")
    rows = [{"round": 0, "loss": 1.0, "round_seconds": 0.4,
             "samples_per_sec": 10.0}]
    rows += [{"round": r, "loss": 1.0, "round_seconds": 1e-6,
              "samples_per_sec": 4e6} for r in (1, 2, 3)]
    _write_rounds(path, rows)
    rep = build_report(path)
    assert len(rep["segments"]) == 1
    assert rep["segments"][0]["rounds"] == 4


def test_report_cli_main(tmp_path, capsys):
    from distkeras_tpu.telemetry.report import main

    path = str(tmp_path / "cli.jsonl")
    _write_rounds(path, [{"round": 0, "loss": 2.0, "round_seconds": 0.1}])
    assert main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "Telemetry report" in out
    assert main(["report", path, "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["rounds"] == 1


# -- acceptance: real trainer runs -> report --------------------------------
def _toy_df(n=256, d=12, classes=3, seed=0):
    from distkeras_tpu.data.dataframe import DataFrame

    rng = np.random.default_rng(seed)
    return DataFrame({
        "features": rng.random((n, d), dtype=np.float32),
        "label": rng.integers(0, classes, n).astype(np.int32),
    })


def _toy_model(d=12, classes=3):
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.models.base import Model

    return Model.build(MLP(hidden=(16,), num_outputs=classes),
                       jnp.zeros((1, d), jnp.float32))


def test_report_from_real_sync_and_adag_runs(tmp_path):
    """Acceptance: ``telemetry report`` renders phase breakdown +
    staleness/straggler sections from JSONLs written by a real
    SynchronousDistributedTrainer run and a real ADAG run."""
    from distkeras_tpu.trainers import ADAG, SynchronousDistributedTrainer

    df = _toy_df()
    sync_path = str(tmp_path / "sync.jsonl")
    t1 = SynchronousDistributedTrainer(
        _toy_model(), loss="sparse_categorical_crossentropy",
        num_workers=4, batch_size=4, num_epoch=1, metrics_path=sync_path)
    t1.train(df)

    telemetry.reset()  # per-run aggregates for the ADAG report
    adag_path = str(tmp_path / "adag.jsonl")
    t2 = ADAG(_toy_model(), loss="sparse_categorical_crossentropy",
              num_workers=4, batch_size=4, communication_window=2,
              num_epoch=1, metrics_path=adag_path)
    t2.train(df)

    sync_rep = build_report(sync_path)
    assert sync_rep["rounds"] > 0
    spans = {p["span"] for p in sync_rep["phases"]}
    assert any("dispatch" in s for s in spans)
    assert "engine_run" in spans

    adag_rep = build_report(adag_path)
    assert adag_rep["rounds"] > 0
    # Discipline-aware sections: ADAG communicates -> staleness present.
    assert adag_rep["staleness"] is not None
    assert adag_rep["staleness"]["num_workers"] == 4
    assert adag_rep["staleness"]["per_worker_mean"] == [1.5, 1.5, 1.5, 1.5]
    assert "loss_divergence_rms" in adag_rep["staleness"]
    text = render_report(adag_rep)
    for section in ("Phase breakdown", "Throughput segments", "Staleness",
                    "Stragglers"):
        assert section in text
    # Input-stall accounting reached the registry via the run loop.
    assert "input_stall_seconds" in adag_rep["counters"]


def test_trainer_closes_metrics_file_on_failure(tmp_path):
    """The satellite leak fix: a run that raises mid-training must still
    close the metrics JSONL (close runs in the trainer's finally)."""
    from distkeras_tpu.trainers import SynchronousDistributedTrainer

    path = str(tmp_path / "fail.jsonl")
    boom = RuntimeError("boom")

    def exploding_on_round(r, loss):
        raise boom

    t = SynchronousDistributedTrainer(
        _toy_model(), loss="sparse_categorical_crossentropy",
        num_workers=4, batch_size=4, num_epoch=1, metrics_path=path,
        on_round=exploding_on_round)
    with pytest.raises(RuntimeError, match="boom"):
        t.train(_toy_df())
    # The logger was closed despite the failure: its summary record (written
    # by close()) is present in the file.
    assert any(r.get("kind") == "telemetry_summary"
               for r in read_jsonl(path))


def test_pipeline_engine_on_step_observation():
    """The pipeline engine's own observation point (satellite: it previously
    had none): on_step fires per step and the dispatch span records."""
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import TransformerLM
    from distkeras_tpu.parallel.pipeline_engine import PipelineEngine
    from distkeras_tpu.runtime.mesh import hybrid_mesh

    model = Model.build(
        TransformerLM(vocab_size=32, num_layers=2, d_model=16, num_heads=2,
                      d_ff=32, max_seq_len=8),
        jnp.zeros((1, 8), jnp.int32))
    mesh = hybrid_mesh({"data": 2, "pipe": 2})
    seen = []
    eng = PipelineEngine(model, "sgd", "sparse_categorical_crossentropy",
                         mesh, num_microbatches=2,
                         on_step=lambda i, loss: seen.append(i))
    state = eng.init_state()
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 32, (4, 8)), jnp.int32)
    tgts = jnp.asarray(np.roll(np.asarray(toks), -1, 1), jnp.int32)
    for _ in range(2):
        state, loss = eng.step(state, toks, tgts)
    assert seen == [0, 1]
    snap = telemetry.get().snapshot()["spans"]
    assert snap["pipeline.dispatch"]["count"] == 2

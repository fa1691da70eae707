"""Benchmark: the five BASELINE.md configs + the flagship transformer, with
achieved TFLOPS / MFU.

Runs on whatever accelerator jax exposes (the driver runs it on one real TPU
chip). Prints ONE JSON line whose headline is the north-star config (BASELINE
config #3: CIFAR-10 CNN under AEASGD, samples/s/chip) and whose ``configs``
list carries all six measured configs:

    #1 MNIST MLP / SingleTrainer      #2 MNIST CNN / ADAG
    #3 CIFAR-10 CNN / AEASGD          #4 IMDB LSTM / DynSGD
    #5 ResNet-50 / synchronous DP     #6 TransformerLM L=2048 / flash attn
                                         (tokens/s/chip — beyond reference)

Each entry reports samples/s/chip, achieved TFLOPS (from XLA's compiled cost
analysis of the actual round executable — fwd+bwd+optimizer+collectives) and %
of the chip's bf16 peak (MFU). ``vs_baseline`` compares against the committed
protocol-matched pin (``BENCH_PIN.json``, when present), with ``within_band``
flagging whether the delta is inside the pin's allowed band and
``vs_ceiling`` the fraction of the config's roofline-derived bound (metrics
without a pin fall back to the most recent ``BENCH_r*.json``). The reference
itself publishes no throughput numbers (BASELINE.json ``published: {}``).
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))

# bf16 peak FLOPS by TPU generation (per chip). Only consulted on TPU (CPU
# runs report no TFLOPS/MFU — there is no meaningful "peak" there).
_PEAK_BF16 = (
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v6", 918e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)


def _chip_peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for tag, peak in _PEAK_BF16:
        if tag in kind:
            return peak
    raise ValueError(
        f"no bf16 peak known for device_kind {device.device_kind!r}: add it "
        "to _PEAK_BF16 with its source — an MFU is never silently left out")


# Analytic training FLOPs per sample (fwd x3 for fwd+bwd), per config.
# XLA's compiled cost_analysis is NOT usable here: it counts a lax.scan body
# once, not x trip-count, so windowed rounds and the LSTM recurrence are
# undercounted by large factors (verified: it reported 0.01 TFLOPS for the
# LSTM config). Derivations (dense/conv = 2*M*N*K; conv = 2*H*W*Cout*Cin*k^2):
#   mnist_mlp   784-500-500-10 dense stack           = 1.294 MFLOP fwd
#   mnist_cnn   3x3 convs 1->32 (28^2), 32->64 (14^2), dense 3136->128->10
#               = 0.452 + 7.225 + 0.803 + 0.003      = 8.48 MFLOP fwd
#   cifar10_cnn 3x3 convs 3->64 (32^2), 64->128 (16^2), 128->256 (8^2),
#               dense 4096->256->10 = 3.54 + 37.75 + 37.75 + 2.10 + 0.005
#                                                    = 81.1 MFLOP fwd
#   imdb_lstm   seq 200 x LSTM cell 2*(E+H)*4H (E=64, H=128) + head
#               = 200 * 0.787 MFLOP                  = 39.3 MFLOP fwd
#   resnet50    canonical 224x224 bottleneck stack   = 4.1 GFLOP fwd
_TRAIN_FLOPS_PER_SAMPLE = {
    "mnist_mlp_single": 3 * 1.294e6,
    "mnist_cnn_adag": 3 * 8.48e6,
    "cifar10_cnn_aeasgd": 3 * 81.1e6,
    "imdb_lstm_dynsgd": 3 * 39.3e6,
    "resnet50_sync": 3 * 4.1e9,
}


def _pin_config() -> tuple[dict, float]:
    """(per-metric pin entries, weather band fraction) from BENCH_PIN.json.

    The committed, protocol-matched baseline pin (VERDICT r4 weak #1):
    ``vs_baseline`` is computed against these pins — NOT against the
    previous round's artifact, which r4 showed machine-reads as a
    regression across any protocol change — and ``within_band`` flags
    whether the delta is inside the pin's allowed band."""
    try:
        with open(os.path.join(_REPO, "BENCH_PIN.json")) as f:
            pin = json.load(f)
        return (pin.get("configs", {}),
                float(pin.get("weather_band_pct", 15)) / 100.0)
    except (OSError, ValueError):
        return {}, 0.15


def _prior_values() -> dict[str, float]:
    """metric -> value from the most recent prior round's BENCH_r*.json."""
    paths = sorted(
        glob.glob(os.path.join(_REPO, "BENCH_r*.json")),
        key=lambda p: int(re.search(r"BENCH_r(\d+)", p).group(1)),
    )
    for path in reversed(paths):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        # Driver-written records wrap the bench JSON line under "parsed" —
        # which is null when that round's bench crashed before printing its
        # line; skip to the next-most-recent record instead of dying here.
        rec = rec.get("parsed", rec)
        if not isinstance(rec, dict):
            continue
        vals: dict[str, float] = {}
        if rec.get("metric") and rec.get("value"):
            vals[rec["metric"]] = float(rec["value"])
        for c in rec.get("configs", []):
            if c.get("metric") and c.get("value"):
                vals[c["metric"]] = float(c["value"])
        if vals:
            return vals
    return {}


def _health_summary(tele, results: list) -> dict:
    """The BENCH_SUMMARY ``health_summary`` block: typed health-plane
    alert traffic observed during the run plus any config that left its
    pinned band, so the regression sentinels (and a human reading the
    perf trajectory) see drift without re-deriving it."""
    alerts = []
    raised = cleared = 0
    try:
        for e in tele.events():
            if e.get("kind") == "health_alert":
                raised += 1
                alerts.append({k: e.get(k) for k in
                               ("alert", "severity", "message", "value",
                                "tenant", "job") if e.get(k) is not None})
            elif e.get("kind") == "health_clear":
                cleared += 1
    except Exception:  # diagnostics never fail the bench
        pass
    return {
        "alerts_raised": raised,
        "alerts_cleared": cleared,
        "alerts": alerts,
        "bench_regressions": [
            {"metric": r.get("metric"), "value": r.get("value"),
             "vs_baseline": r.get("vs_baseline")}
            for r in results if r.get("within_band") is False],
    }


def _emit_summary(out: dict) -> None:
    """Emit the bench summary both ways the driver can consume it: as the
    process's FINAL stdout line (flushed, nothing printed after it — the
    BENCH_r05 record showed a truncated tail machine-reads as
    ``"parsed": null``) and as ``BENCH_SUMMARY.json`` beside the repo's
    other bench artifacts, so a clipped stdout stream still leaves a
    parseable record on disk."""
    import sys

    summary = json.dumps(out)
    try:
        with open(os.path.join(_REPO, "BENCH_SUMMARY.json"), "w") as f:
            f.write(summary + "\n")
    except OSError as e:  # the printed line is still the record of truth
        print(f"[bench] BENCH_SUMMARY.json write failed: {e}",
              file=sys.stderr)
    sys.stderr.flush()
    print(summary, flush=True)


def _time_steps(step_once, warmup: int, timed: int, reps: int = None):
    """Shared timing protocol: warmup, then ``reps`` independent repetitions
    of the ``timed``-call loop, each fenced by fetching a value with
    device_get. Returns the per-rep elapsed seconds list. Round-4 protocol
    change: the old best-of-2 could not tell a regression from run-to-run
    wander (±20-30% measured on the setup of that round) — callers now take
    a TRIMMED MEDIAN over >=5 reps and record the dispersion."""
    import jax

    for i in range(warmup):
        fence = step_once(i)
    jax.device_get(fence)
    if reps is None:
        reps = 5 if jax.default_backend() == "tpu" else 1
    times = []
    for _rep in range(reps):
        t0 = time.perf_counter()
        for i in range(timed):
            fence = step_once(i)
        jax.device_get(fence)
        times.append(time.perf_counter() - t0)
    return times


def _throughput_stats(times, units_per_rep: float) -> dict:
    """Trimmed-median throughput + dispersion from per-rep elapsed seconds.

    ``value`` is the median of the reps with the single best and worst
    dropped (n >= 5) — robust to one latency outlier in either
    direction; p10/p90 are over ALL reps so the record keeps the full
    spread the median is defending against."""
    tput = sorted(units_per_rep / t for t in times)
    trimmed = tput[1:-1] if len(tput) >= 5 else tput
    return {
        "value": float(np.median(trimmed)),
        "p50": round(float(np.median(tput)), 1),
        "p10": round(float(np.percentile(tput, 10)), 1),
        "p90": round(float(np.percentile(tput, 90)), 1),
        "reps": len(tput),
    }


def _bench_engine(engine, plan, warmup: int, timed: int, rounds_per_program=1,
                  reps: int = None):
    """Time `timed` fold rounds of an Async/Sync engine; returns the per-rep
    elapsed-seconds list (each normalized to ``timed`` rounds).

    ``rounds_per_program`` dispatches blocks of rounds as one XLA program
    (``engine.multi_round_fn``) — semantics-preserving, and it keeps host
    dispatch from bounding the small-model configs (rounds 3-5 measured
    mnist_mlp at 6.7ms/round, >60% dispatch, on an earlier setup; not
    re-measured on the current one). ``"auto"`` probes the steady-state per-round time and
    sizes R with the same constants as ``run_auto`` in parallel/engine.py.
    (The bench probe re-dispatches one resident batch, so it measures compute
    only; a real run's probe includes staging and can size R smaller for
    input-bound configs — bench numbers are an upper bound on that path.)
    """
    import jax
    import numpy as _np
    from jax.sharding import NamedSharding, PartitionSpec as _P

    state = engine.init_state()
    if rounds_per_program == "auto":
        # Stage through the engine's own path (put_global handles
        # multi-process shardings; a raw device_put would not).
        xs0, ys0 = engine._put_batch(*plan.round(0))
        for _ in range(2):  # compile + warm-up
            state, loss = engine._round_fn(state, xs0, ys0)
            jax.device_get(loss)
        # Steady-state probe: any single-round fence adds a fixed sync/fetch
        # round-trip, so run a batch of unfenced rounds and fence once, then
        # size R exactly the way the trainers do (same constants as run_auto
        # in parallel/engine.py, so the bench measures the R a real run
        # would pick).
        from distkeras_tpu.parallel.engine import _auto_size_r, probe_steady

        carry0 = {"s": state}

        def _probe_one():
            carry0["s"], loss = engine._round_fn(carry0["s"], xs0, ys0)
            return loss

        steady = probe_steady(_probe_one)
        state = carry0["s"]
        # _auto_size_r also handles the multi-process R agreement.
        rounds_per_program = _auto_size_r(steady, xs0.nbytes + ys0.nbytes)
    R = max(1, min(rounds_per_program, timed))
    # Pre-stage a few distinct blocks on device and cycle them: host input
    # transfer isn't what's being benchmarked (training overlaps it via the
    # RoundFeeder prefetcher), and staging dozens of unique rounds can cost
    # more wall-clock than the measurement itself.
    shard = NamedSharding(engine.mesh, _P(None, "data"))
    n_blocks = max(1, min(plan.num_rounds // R, 2))

    def stage(i):
        from distkeras_tpu.runtime.mesh import put_global

        rs = range(i * R, i * R + R)
        xs = _np.stack([plan.round(r % plan.num_rounds)[0] for r in rs])
        ys = _np.stack([plan.round(r % plan.num_rounds)[1] for r in rs])
        # put_global: multi-process shardings need the callback path.
        return put_global(xs, shard), put_global(ys, shard)

    staged = [stage(i) for i in range(n_blocks)]
    fn = engine.multi_round_fn(R) if R > 1 else None
    carry = {"state": state}

    def one(i):
        block = staged[i % len(staged)]
        if fn is not None:
            carry["state"], loss = fn(carry["state"], *block)
        else:
            xs, ys = block
            carry["state"], loss = engine._round_fn(carry["state"], xs[0], ys[0])
        return loss

    n_timed = max(1, timed // R)
    times = _time_steps(one, max(1, warmup // R), n_timed, reps=reps)
    # Normalize each rep to ``timed`` rounds so callers see per-rep elapsed
    # for the same notional work regardless of the blocked-program sizing.
    return [t / (n_timed * R) * timed for t in times]


def _measure_input_stall(engine, plan) -> float | None:
    """Input-stall fraction of a short REAL-path run (RoundFeeder staging,
    one dispatch per round): steady-state feeder wait seconds / wall.

    The timed bench pre-stages batches on device, so it measures pure
    compute; this companion number is what separates compute from data time
    when comparing bench rounds (ISSUE 1 satellite). Round 0's wait is
    excluded from numerator AND denominator — the feeder has nothing to
    overlap yet, so its wait is the full stage time even when staging is
    perfectly hidden in steady state (the docs/PERFORMANCE.md feed-overlap
    convention: "near-zero past round 0 = staging fully hidden"). Callers
    pass a several-round plan so the steady-state numerator has multiple
    wait samples. The denominator is the dispatch-loop wall between the
    first and last round callbacks — NOT the whole run(), whose trailing
    D2H retire fence would otherwise be charged to a small config's few
    rounds and deflate the fraction."""
    import time as _t

    try:
        ticks: list[float] = []

        def cb(r, loss, st):
            ticks.append(_t.perf_counter())

        engine.run(plan, rounds_per_program=1, on_round=cb)
        waits = getattr(engine, "feed_waits", [])
        if len(ticks) < 2 or len(waits) < 2:
            return None
        loop_wall = ticks[-1] - ticks[0]
        if loop_wall <= 0:
            return None
        return round(min(sum(waits[1:]) / loop_wall, 1.0), 4)
    except Exception:
        return None  # diagnostics must never fail the config


def _measure(name, model_fn, discipline, batch_size, window, sample_shape,
             num_classes, timed=30, warmup=3, int_inputs=False, vocab=None,
             optimizer="sgd", rounds_per_program=1, num_workers=None, reps=None,
             measure_stall=True):
    """Build engine+plan for one config and measure it."""
    import jax

    # Parameter init is eager op-by-op flax code: run it on CPU (no per-op
    # TPU compiles); the engines device_put params where they belong anyway.
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = model_fn()

    from distkeras_tpu.data import DataFrame
    from distkeras_tpu.data.batching import make_batches
    from distkeras_tpu.parallel.disciplines import get_discipline
    from distkeras_tpu.parallel.engine import AsyncEngine
    from distkeras_tpu.parallel.sync import SyncEngine
    from distkeras_tpu.runtime.mesh import data_mesh

    if jax.default_backend() != "tpu":
        # CPU smoke mode: the numbers are meaningless off-TPU; just exercise
        # the path cheaply on the 2-core CI box.
        rounds_per_program = 1
        window = min(window, 2)
        batch_size = min(batch_size, 16)
        timed = min(timed, 2)
        warmup = 1
    num_chips = jax.device_count()
    rng = np.random.default_rng(0)
    # Two rounds of unique data are enough: throughput only needs the shapes.
    n = 2 * num_chips * window * batch_size
    if int_inputs:
        x = rng.integers(0, vocab, size=(n,) + sample_shape).astype(np.int32)
    else:
        x = rng.random(size=(n,) + sample_shape, dtype=np.float32)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    df = DataFrame({"features": x, "label": y})
    mesh = data_mesh(num_workers=1 if discipline == "single" else num_workers)
    workers = mesh.shape["data"]
    plan = make_batches(df, "features", "label", batch_size,
                        num_workers=workers, window=window, num_epoch=1)
    if discipline in ("single", "sync"):
        engine = SyncEngine(model, optimizer, "sparse_categorical_crossentropy",
                            mesh, learning_rate=0.01, compute_dtype="bfloat16")
    else:
        fold = get_discipline(discipline) if discipline != "aeasgd" else (
            get_discipline("aeasgd", alpha=0.05))
        engine = AsyncEngine(model, optimizer, "sparse_categorical_crossentropy",
                             fold, mesh, window=window, learning_rate=0.01,
                             compute_dtype="bfloat16")
    times = _bench_engine(engine, plan, warmup, timed,
                          rounds_per_program=rounds_per_program, reps=reps)
    stall_frac = None
    if measure_stall:
        # Longer real-path plan (same two rounds of data, more epochs): one
        # warmup wait to discard + five steady-state samples, instead of the
        # single noisy sample a 2-round plan would give. Runs AFTER the
        # timed bench so the per-round program is already compiled (a
        # compile inside the stall run would inflate the wall denominator).
        stall_plan = make_batches(df, "features", "label", batch_size,
                                  num_workers=workers, window=window,
                                  num_epoch=3)
        stall_frac = _measure_input_stall(engine, stall_plan)
    samples = timed * workers * window * batch_size
    # per chip IN USE (== all visible chips for the standard configs; the
    # scaling sweep pins smaller worker counts)
    stats = _throughput_stats(times, samples / workers)
    sps_chip = stats["value"]
    tflops = None
    mfu = None
    # Off-TPU the models may be swapped for tiny stand-ins (see resnet50_sync)
    # and the analytic FLOPs don't apply; report raw samples/s only.
    per_sample = _TRAIN_FLOPS_PER_SAMPLE.get(name) if jax.default_backend() == "tpu" else None
    if per_sample:
        achieved = per_sample * sps_chip
        tflops = achieved / 1e12
        mfu = achieved / _chip_peak_flops(jax.devices()[0])
    rec = {
        "metric": f"{name}_samples_per_sec_per_chip",
        "value": round(sps_chip, 1),
        "unit": "samples/s/chip",
        "p50": stats["p50"], "p10": stats["p10"], "p90": stats["p90"],
        "reps": stats["reps"],
        "achieved_tflops_per_chip": round(tflops, 2) if tflops else None,
        "mfu_vs_bf16_peak": round(mfu, 4) if mfu else None,
    }
    if measure_stall:
        rec["input_stall_fraction"] = stall_frac
    return rec


def _measure_async_transformer(name, *, num_layers, d_model, num_heads, d_ff,
                               vocab, seq_len, batch, window=8, timed=4,
                               reps=5):
    """Config #7: the flagship flash transformer trained as ONE AEASGD
    worker — the async-disciplines x flash composition's single-chip cost
    (window-``window`` lax.scan of steps + the elastic fold per round,
    remat'd blocks). The number to compare against config #6's bare SPMD
    step; docs/PERFORMANCE.md 'Flash under the async disciplines'."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.data.batching import make_batches
    from distkeras_tpu.data.dataframe import DataFrame
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import TransformerLM
    from distkeras_tpu.parallel.disciplines import get_discipline
    from distkeras_tpu.parallel.engine import AsyncEngine, stage_round
    from distkeras_tpu.runtime.mesh import data_mesh

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:  # CPU smoke
        num_layers, d_model, num_heads, d_ff = 2, 64, 2, 128
        vocab, seq_len, batch, window, timed, reps = 256, 128, 2, 2, 2, 1

    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = Model.build(
            TransformerLM(vocab_size=vocab, num_layers=num_layers,
                          d_model=d_model, num_heads=num_heads, d_ff=d_ff,
                          max_seq_len=seq_len,
                          attn_impl="flash" if on_tpu else "dense",
                          remat=on_tpu),
            jnp.zeros((1, 1), jnp.int32))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, size=(batch * window * 2, seq_len))
    df = DataFrame({"features": toks.astype(np.int32),
                    "label": np.roll(toks, -1, 1).astype(np.int32)})
    plan = make_batches(df, "features", "label", batch_size=batch,
                        num_workers=1, window=window, num_epoch=1)
    engine = AsyncEngine(
        model, "adam", "sparse_categorical_crossentropy",
        get_discipline("aeasgd", alpha=0.05), data_mesh(num_workers=1),
        window=window, learning_rate=1e-4,
        compute_dtype="bfloat16" if on_tpu else None)
    xs, ys = stage_round(engine, plan, 0)
    carry = {"s": engine.init_state()}

    def one(_i):
        carry["s"], loss = engine._round_fn(carry["s"], xs, ys)
        return loss

    times = _time_steps(one, 1, timed, reps=reps)
    stats = _throughput_stats(times, timed * window * batch * seq_len)
    return {"metric": f"{name}_tokens_per_sec_per_chip",
            "value": round(stats["value"], 1), "unit": "tokens/s/chip",
            "p50": stats["p50"], "p10": stats["p10"], "p90": stats["p90"],
            "reps": stats["reps"]}


def _measure_netps_transformer(name, *, num_layers, d_model, num_heads, d_ff,
                               vocab, seq_len, batch, window=4, rounds=8,
                               reps=3):
    """Config #8: an AEASGD transformer trained THROUGH the networked
    parameter server over loopback — the RPC overhead as a pinned number.

    Three measurements on the SAME model and jitted window executable:

    * ``inprocess``  — the AsyncEngine elastic fold (no RPC at all): the
      ceiling the netps path chases;
    * ``pr4``        — netps with the PR 4 data-plane knobs (serial loop,
      f32 deltas, one connection; the zero-copy framing is unconditional);
    * ``optimized``  — netps with the PR 5 data plane: compute/comms
      overlap (`DKTPU_NET_INFLIGHT=2`), int8 deltas with error feedback,
      and 2-way sharded striping (loopback TCP);
    * ``shm``        — the PR 5 knobs over the same-host shared-memory
      ring (`DKTPU_NET_TRANSPORT=shm`): payloads via mmap, doorbell on a
      UDS — the PR 6 fast path. ``shm_vs_tcp_optimized`` is the headline
      A/B (acceptance: >= 1.5x);
    * ``mesh``       — the device-resident center
      (`DKTPU_NET_TRANSPORT=mesh`): same-process workers fold through the
      in-process dispatch into donated device buffers, zero wire bytes.
      ``mesh_vs_inprocess`` is its acceptance ratio (>= 1.0: the dialect
      must meet the in-process engine fold, the ceiling every wire
      dialect chases).

    The headline value is the shm path (the dialect a colocated deployment
    negotiates); ``data_plane_ab`` records all four plus the recovered
    gap fractions. ``hier_curve`` adds the fold-throughput-vs-worker-count
    curve for the flat vs hierarchical (`DKTPU_NET_HIER=1`) topologies:
    same shm dialect, per-point root-commit and worker-commit rates, so
    the root-ingress cut is a measured number."""
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.data.batching import make_batches
    from distkeras_tpu.data.dataframe import DataFrame
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import TransformerLM
    from distkeras_tpu.netps.remote import run_remote
    from distkeras_tpu.netps.server import PSServer
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.disciplines import get_discipline
    from distkeras_tpu.parallel.engine import AsyncEngine, stage_round
    from distkeras_tpu.runtime.mesh import data_mesh
    from distkeras_tpu.workers import make_local_loop

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:  # CPU smoke: keep the comms-visible SHAPE, shrink sizes
        num_layers, d_model, num_heads, d_ff = 2, 384, 4, 1536
        vocab, seq_len, batch, window = 4096, 64, 2, 1
        rounds, reps = 12, 2

    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = Model.build(
            TransformerLM(vocab_size=vocab, num_layers=num_layers,
                          d_model=d_model, num_heads=num_heads, d_ff=d_ff,
                          max_seq_len=seq_len,
                          attn_impl="flash" if on_tpu else "dense",
                          remat=on_tpu),
            jnp.zeros((1, 1), jnp.int32))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, size=(batch * window * rounds, seq_len))
    df = DataFrame({"features": toks.astype(np.int32),
                    "label": np.roll(toks, -1, 1).astype(np.int32)})
    plan = make_batches(df, "features", "label", batch_size=batch,
                        num_workers=1, window=window, num_epoch=1)
    alpha = 0.05
    dtype = "bfloat16" if on_tpu else None
    lr = 1e-4
    tx = optax.adam(lr)
    loss_fn = get_loss("sparse_categorical_crossentropy")
    tokens = plan.num_rounds * window * batch * seq_len

    # -- in-process ceiling: the AsyncEngine elastic fold, same plan size --
    engine = AsyncEngine(
        model, "adam", "sparse_categorical_crossentropy",
        get_discipline("aeasgd", alpha=alpha), data_mesh(num_workers=1),
        window=window, learning_rate=lr, compute_dtype=dtype)
    xs, ys = stage_round(engine, plan, 0)
    carry = {"s": engine.init_state()}

    def one(_i):
        carry["s"], loss = engine._round_fn(carry["s"], xs, ys)
        return loss

    times = _time_steps(one, 1, plan.num_rounds, reps=reps)
    inproc = _throughput_stats(times, tokens)["value"]

    # -- the two netps loopback variants, one shared jitted window ---------
    loop_fn = jax.jit(make_local_loop(
        model.module, loss_fn, tx,
        compute_dtype=jnp.bfloat16 if on_tpu else None))

    def run_variant(transport="tcp", state_dir=None, **knobs):
        elapsed = []
        for rep in range(reps + 1):  # rep 0 = warmup (jit compile, sockets)
            srv = PSServer(discipline="aeasgd", transport=transport,
                           state_dir=state_dir).start()
            try:
                t0 = time.perf_counter()
                run_remote(endpoint=srv.endpoint, model=model, tx=tx,
                           loss_fn=loss_fn, plan=plan,
                           discipline="aeasgd", window=window, alpha=alpha,
                           compute_dtype=jnp.bfloat16 if on_tpu else None,
                           transport=transport,
                           loop_fn=loop_fn, **knobs)
                if rep:
                    elapsed.append(time.perf_counter() - t0)
            finally:
                srv.close()
        return _throughput_stats(elapsed, tokens)

    pr4 = run_variant(inflight=1, shards=1, compress="none")
    opt = run_variant(inflight=2, shards=2, compress="int8")
    # Durability A/B (write-ahead journal + snapshots, PR 7) on the
    # OPTIMIZED loopback plane (int8 + overlap + striping — the config a
    # loopback deployment actually ships): the journal records deltas in
    # their WIRE dtype, so compressing the wire compresses the journal
    # 4x, and the overlap lane keeps the (already-async) journal writer
    # entirely off the compute path — that combination is what holds the
    # <= 5 % steady-state budget (f32/serial journaling on a CPU dev box
    # is memory-bandwidth-bound and measures 20-35 %; the knob note in
    # PERFORMANCE.md). Measured as INTERLEAVED baseline/durable pairs
    # (back to back, per-pair ratio, median): run-to-run noise between
    # two minutes-apart measurements here is far larger than the 5 %
    # being measured, pairing cancels it. A fresh state dir per pair at
    # the production snapshot cadence; the server is ctor-seeded so the
    # one-off base snapshot lands before the timed window (steady-state
    # write path, not a recovery replay or the seed).
    init_leaves = [np.asarray(a, np.float32)
                   for a in jax.tree.leaves(model.params)]

    def one_durability_pair(durable_first):
        import shutil

        out, state = {}, tempfile.mkdtemp(prefix="dkbench-ps-")
        order = (state, None) if durable_first else (None, state)
        try:
            for state_dir in order:
                srv = PSServer(center=init_leaves if state_dir else None,
                               discipline="aeasgd",
                               state_dir=state_dir).start()
                try:
                    t0 = time.perf_counter()
                    run_remote(endpoint=srv.endpoint, model=model, tx=tx,
                               loss_fn=loss_fn, plan=plan,
                               discipline="aeasgd", window=window,
                               alpha=alpha,
                               compute_dtype=(jnp.bfloat16 if on_tpu
                                              else None),
                               inflight=2, shards=2, compress="int8",
                               loop_fn=loop_fn)
                    out[state_dir is not None] = time.perf_counter() - t0
                finally:
                    srv.close()
        finally:
            # Unlinking drops the pair's dirty pages with it: on this box
            # letting state dirs accumulate makes LATER pairs pay earlier
            # pairs' writeback — an artifact of back-to-back bench runs,
            # not of the 20 MB/s a real int8 journal sustains.
            shutil.rmtree(state, ignore_errors=True)
        return out[True] / out[False]

    # ABBA: alternate which leg runs first so slow monotonic box drift
    # (thermal, cache state) cancels instead of biasing the second leg;
    # geomean over the pairs because the residual noise is symmetric and
    # multiplicative (an even-N median would arbitrarily pick a side of
    # a wide gap).
    ratios = sorted(one_durability_pair(durable_first=bool(i % 2))
                    for i in range(max(reps + 2, 10)))
    durable_ratio = float(np.exp(np.mean(np.log(ratios))))
    # The ring's best knobs differ from TCP's: with payload copies at
    # memcpy speed, the int8 quantize/dequantize passes (and a second
    # ring's doorbell) cost more than the bytes they save — f32 over ONE
    # ring wins (measured; the codec stays a TCP/cross-host lever).
    shm_v = run_variant(transport="shm", inflight=2, shards=1,
                        compress="none")
    # -- the mesh arm: the device-resident center (PR 20) ------------------
    # Same-process workers fold through the in-process dispatch into
    # donated device buffers — zero wire bytes, zero payload copies. The
    # ring's knob rule applies a fortiori (f32, one lane); the headline
    # ratio is against the IN-PROCESS engine fold, the ceiling every wire
    # dialect chases (acceptance: >= 1.0 — the dialect must close the RPC
    # gap outright, not just narrow it).
    mesh_v = run_variant(transport="mesh", inflight=2, shards=1,
                         compress="none")
    # -- the auto arm: the self-tuning controller from a COLD start --------
    # No data-plane knobs at all: join-time probes + the online control
    # loop pick codec/inflight/striping (the acceptance bar is matching
    # the best hand-tuned variant above within the run-to-run band). The
    # chosen knobs are read back from the controller's own run summary
    # event — the bench reports what the controller DID, not what it was
    # expected to do.
    from distkeras_tpu import telemetry as _telemetry
    from distkeras_tpu.netps.tuner import recommended_topology

    auto_v = run_variant(transport="shm", autotune=True)
    auto_knobs = None
    for ev in _telemetry.get().events():
        if ev.get("kind") == "tuner_run_summary":
            auto_knobs = {k: ev.get(k) for k in
                          ("inflight", "codec", "shards", "transport")}

    # -- fold-throughput vs worker count: flat vs hierarchical topology ----
    # One timed run per point (the executable and sockets are warm from the
    # variants above): root-commit rate is the ingress the root actually
    # absorbs; worker-commit rate is the system-wide fold demand — their
    # ratio is the measured fan-in cut. Deliberately NOT run_variant: each
    # point needs the server's commit_log and a single unwarmed shot, not
    # the warmup+reps throughput protocol.
    curve_rounds = max(4, rounds // 2)
    hier_curve = []
    for W in (1, 2, 4):
        toks_w = rng.integers(0, vocab,
                              size=(W * batch * window * curve_rounds,
                                    seq_len))
        df_w = DataFrame({"features": toks_w.astype(np.int32),
                          "label": np.roll(toks_w, -1, 1).astype(np.int32)})
        plan_w = make_batches(df_w, "features", "label", batch_size=batch,
                              num_workers=W, window=window, num_epoch=1)
        tokens_w = plan_w.num_rounds * W * window * batch * seq_len
        for topo in ("flat", "hier"):
            srv = PSServer(discipline="aeasgd", transport="shm").start()
            try:
                t0 = time.perf_counter()
                run_remote(endpoint=srv.endpoint, model=model, tx=tx,
                           loss_fn=loss_fn, plan=plan_w,
                           discipline="aeasgd", window=window, alpha=alpha,
                           compute_dtype=jnp.bfloat16 if on_tpu else None,
                           transport="shm", hier=(topo == "hier"),
                           hier_flush=0.5, inflight=1, shards=1,
                           compress="none", loop_fn=loop_fn)
                dt = time.perf_counter() - t0
                hier_curve.append({
                    "workers": W, "topology": topo,
                    # What the self-tuning controller WOULD pick at this
                    # fan-in (the measured crossover rule) — lined up
                    # against both measured topologies per point.
                    "controller_topology": recommended_topology(W),
                    "tokens_per_sec": round(tokens_w / dt, 1),
                    "root_commits": len(srv.commit_log),
                    "root_commits_per_sec": round(
                        len(srv.commit_log) / dt, 2),
                    "worker_commits_per_sec": round(
                        W * plan_w.num_rounds / dt, 2),
                })
            finally:
                srv.close()

    gap = inproc - pr4["value"]
    rec = {
        "metric": f"{name}_tokens_per_sec_per_chip",
        "value": round(shm_v["value"], 1), "unit": "tokens/s/chip",
        "p50": shm_v["p50"], "p10": shm_v["p10"], "p90": shm_v["p90"],
        "reps": shm_v["reps"],
        "data_plane_ab": {
            "inprocess_tokens_per_sec": round(inproc, 1),
            "pr4_tokens_per_sec": round(pr4["value"], 1),
            "optimized_tokens_per_sec": round(opt["value"], 1),
            "shm_tokens_per_sec": round(shm_v["value"], 1),
            "mesh_tokens_per_sec": round(mesh_v["value"], 1),
            "optimized_vs_pr4": round(opt["value"] / pr4["value"], 3),
            "shm_vs_tcp_optimized": round(shm_v["value"] / opt["value"], 3),
            "mesh_vs_inprocess": (round(mesh_v["value"] / inproc, 3)
                                  if inproc > 0 else None),
            "mesh_vs_shm": round(mesh_v["value"] / shm_v["value"], 3),
            "durable_tokens_per_sec": round(
                opt["value"] / durable_ratio, 1),
            "durable_overhead_vs_optimized": round(durable_ratio - 1.0, 3),
            "durable_pair_ratios": [round(r, 3) for r in ratios],
            "rpc_gap_recovered": (
                round((shm_v["value"] - pr4["value"]) / gap, 3)
                if gap > 0 else None),
            "knobs": {"inflight": 2, "compress": "none", "shards": 1,
                      "transport": "shm"},
            "auto_tokens_per_sec": round(auto_v["value"], 1),
            "auto_vs_best_hand_tuned": round(
                auto_v["value"] / shm_v["value"], 3),
            "auto_knobs": auto_knobs,
        },
        "hier_curve": hier_curve,
    }

    # -- sim drift: calibrate the simulator on THIS run's own trace stream
    # and replay the deployment (distkeras_tpu.sim.calibrate). The
    # predicted/measured throughput ratio ships in the summary so the
    # bench-regression sentinel watches calibration rot like any other
    # out-of-band config. One traced shot of the PR-4 flat plane (tracing
    # adds wire bytes, so it gets its own run, not the timed variants).
    import shutil as _shutil

    from distkeras_tpu.sim.calibrate import sim_drift as _sim_drift
    from distkeras_tpu.telemetry.tracing import context as _trace_ctx
    from distkeras_tpu.telemetry.tracing.collector import TelemetryCollector

    trace_dir = tempfile.mkdtemp(prefix="dkbench-trace-")
    saved_env = {k: os.environ.get(k)
                 for k in ("DKTPU_TRACE", "DKTPU_TRACE_DIR")}
    os.environ["DKTPU_TRACE"] = "1"
    os.environ["DKTPU_TRACE_DIR"] = trace_dir
    _trace_ctx._reset_stream()
    try:
        srv = PSServer(discipline="aeasgd").start()
        try:
            t0 = time.perf_counter()
            run_remote(endpoint=srv.endpoint, model=model, tx=tx,
                       loss_fn=loss_fn, plan=plan, discipline="aeasgd",
                       window=window, alpha=alpha,
                       compute_dtype=jnp.bfloat16 if on_tpu else None,
                       inflight=1, shards=1, compress="none",
                       loop_fn=loop_fn)
            traced_dt = time.perf_counter() - t0
        finally:
            srv.close()
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _trace_ctx._reset_stream()
    try:
        records = TelemetryCollector.from_dir(trace_dir).records()
        rec["sim_drift"] = _sim_drift(
            records, tokens / traced_dt,
            tokens_per_round=window * batch * seq_len)
    finally:
        _shutil.rmtree(trace_dir, ignore_errors=True)
    return rec


def _measure_spmd_transformer(name, *, num_layers, d_model, num_heads, d_ff,
                              vocab, seq_len, batch, timed=12, warmup=2,
                              reps=None):
    """Flagship config: TransformerLM with the Pallas flash-attention kernel,
    single-chip slice (the multi-chip dp x sp x tp path is exercised by
    __graft_entry__.dryrun_multichip with ring attention; the Mosaic flash
    kernel itself runs per-chip and is not GSPMD-partitionable, so this
    measures the per-chip training step a pod config would replicate)."""
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import TransformerLM
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.ops.precision import cast_floats

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:  # CPU smoke: shrink to toy size
        num_layers, d_model, num_heads, d_ff = 2, 64, 2, 128
        vocab, seq_len, batch, timed, warmup = 256, 128, 2, 2, 1

    arch = dict(vocab_size=vocab, num_layers=num_layers, d_model=d_model,
                num_heads=num_heads, d_ff=d_ff, max_seq_len=seq_len)
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        # (1, 1) dummy: param shapes don't depend on input length (pos_embed
        # is sized by max_seq_len) and a full-length concrete init would run
        # dense 2048^2 attention on the CPU just to derive shapes.
        model = Model.build(
            TransformerLM(**arch), jnp.zeros((1, 1), jnp.int32))
    module = TransformerLM(**arch, attn_impl="flash" if on_tpu else "dense")
    loss_fn = get_loss("sparse_categorical_crossentropy")
    tx = optax.adam(1e-4)
    dtype = jnp.bfloat16 if on_tpu else None

    def loss_of(params, x, y):
        p = cast_floats(params, dtype)
        logits = module.apply({"params": p}, x, train=True,
                              rngs={"dropout": jax.random.key(0)})
        return loss_fn(logits.astype(jnp.float32), y)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_of)(params, x, y)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = jax.device_put(model.params)
    opt_state = jax.jit(tx.init)(params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, size=(batch, seq_len))
    x = jnp.asarray(toks, jnp.int32)
    y = jnp.asarray(np.roll(toks, -1, 1), jnp.int32)
    carry = {"p": params, "o": opt_state}

    def one(_i):
        carry["p"], carry["o"], loss = step(carry["p"], carry["o"], x, y)
        return loss

    times = _time_steps(one, warmup, timed, reps=reps)
    stats = _throughput_stats(times, timed * batch * seq_len)
    tokens_per_s = stats["value"]
    rec = {"metric": f"{name}_tokens_per_sec_per_chip",
           "value": round(tokens_per_s, 1), "unit": "tokens/s/chip",
           "p50": stats["p50"], "p10": stats["p10"], "p90": stats["p90"],
           "reps": stats["reps"]}
    if on_tpu:
        # analytic train FLOPs/token: 6 x matmul params (fwd 2P + bwd 4P;
        # embedding lookups aren't matmuls) + causal attention scores/values
        # (12 x (L/2)*d per layer fwd+bwd)
        p_embed = vocab * d_model + model.module.max_seq_len * d_model
        p_mm = sum(int(a.size) for a in jax.tree.leaves(model.params)) - p_embed
        per_token = 6 * p_mm + 6 * seq_len * d_model * num_layers
        achieved = per_token * tokens_per_s
        rec["achieved_tflops_per_chip"] = round(achieved / 1e12, 2)
        rec["mfu_vs_bf16_peak"] = round(
            achieved / _chip_peak_flops(jax.devices()[0]), 4)
    return rec


def _measure_sharded_center(name, *, tensors=16, rows=256, cols=512,
                            workers=4, commits=6, shard_counts=(1, 2, 4)):
    """Config #10 — the sharded center plane's fold-throughput curve: the
    SAME synthetic center (``tensors`` x ``rows`` x ``cols`` f32) committed
    to by ``workers`` concurrent clients, measured against a single
    :class:`PSServer` (shards=1, the baseline every point normalizes to)
    and against :class:`ShardSet` gangs of 2 and 4 — each point the full
    join/commit/pull protocol, sharded points through
    :class:`ShardedPSClient`'s plan-scattered fan-out. ``speedup_vs_1`` at
    4 shards is the acceptance number (the per-shard fold lock is the
    single-PS bottleneck being split; docs/SHARDING.md)."""
    import threading

    from distkeras_tpu.netps.server import PSServer
    from distkeras_tpu.netps.shards import ShardSet, make_ps_client

    rng = np.random.default_rng(0)
    center = [rng.standard_normal((rows, cols)).astype(np.float32)
              for _ in range(tensors)]
    center_bytes = sum(a.nbytes for a in center)
    curve = []
    for n in shard_counts:
        if n == 1:
            srv = PSServer(center=[a.copy() for a in center],
                           discipline="adag").start()
            endpoint, plan, closer = srv.endpoint, None, srv.close
        else:
            ss = ShardSet(n, center=[a.copy() for a in center],
                          discipline="adag").start()
            endpoint, plan, closer = ss.endpoint, ss.plan, ss.close
        try:
            barrier = threading.Barrier(workers + 1)
            errors: list = []

            def work(w, endpoint=endpoint, plan=plan, barrier=barrier,
                     errors=errors):
                client = make_ps_client(endpoint, plan=plan)
                try:
                    _c, counter = client.join(init=center)
                    delta = [np.full_like(a, 1e-3) for a in center]
                    barrier.wait()
                    for _ in range(commits):
                        client.commit(delta, counter)
                        _c, counter = client.pull()
                    client.leave()
                except Exception as e:  # surfaced below, never swallowed
                    errors.append(e)
                finally:
                    client.close()

            threads = [threading.Thread(target=work, args=(w,))
                       for w in range(workers)]
            for t in threads:
                t.start()
            barrier.wait()  # joins (compile/plan adoption) stay untimed
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
        finally:
            closer()
        if errors:
            raise errors[0]
        folds = workers * commits
        curve.append({
            "shards": n,
            "folds_per_sec": round(folds / dt, 2),
            "bytes_per_sec": round(folds * center_bytes / dt, 1),
        })
    base = curve[0]["folds_per_sec"]
    for pt in curve:
        pt["speedup_vs_1"] = (round(pt["folds_per_sec"] / base, 3)
                              if base > 0 else None)
    best = curve[-1]
    return {
        "metric": f"{name}_folds_per_sec",
        "value": best["folds_per_sec"], "unit": "folds/s",
        "center_bytes": int(center_bytes),
        "workers": workers,
        "speedup_vs_single_ps": best["speedup_vs_1"],
        "shard_curve": curve,
    }


def _measure_serving(name, *, feature_dim=64, hidden=256, num_classes=10,
                     qps_levels=(50, 200, 800), duration_s=2.0,
                     max_wait_ms=2.0, buckets="1,4,16,64",
                     load_threads=8):
    """Config #9 — the serving plane's latency/throughput frontier: a
    loopback :class:`ServingFrontend` over a small MLP, open-loop offered
    load swept across ``qps_levels``, client-observed p50/p99 per level.
    The headline value is the best achieved QPS; the ``latency_curve``
    list is the real deliverable — it shows where micro-batching holds
    p99 flat and where admission control starts shedding instead of
    letting the queue eat the tail."""
    import threading

    import numpy as np
    from flax import linen as nn

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.serving import (
        ModelRegistry,
        ServeClient,
        ServingError,
        ServingFrontend,
        parse_buckets,
    )

    class _MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(num_classes)(nn.relu(nn.Dense(hidden)(x)))

    model = Model.build(_MLP(), np.zeros((2, feature_dim), np.float32))
    registry = ModelRegistry(model, parse_buckets(buckets))
    frontend = ServingFrontend(registry,
                               max_wait_s=max_wait_ms / 1e3).start()
    curve = []
    try:
        for offered in qps_levels:
            lat: list[float] = []
            shed = [0]
            errs = [0]
            lock = threading.Lock()
            stop = time.perf_counter() + duration_s
            interval = load_threads / float(offered)

            def _load(k, interval=interval, stop=stop, lat=lat,
                      shed=shed, errs=errs):
                client = ServeClient(frontend.endpoint, timeout=5.0,
                                     retries=2, backoff=0.01)
                x = np.random.default_rng(k).standard_normal(
                    (1, feature_dim)).astype(np.float32)
                nxt = time.perf_counter() + (k / load_threads) * interval
                while True:
                    now = time.perf_counter()
                    if now >= stop:
                        break
                    if now < nxt:
                        time.sleep(min(nxt - now, 0.005))
                        continue
                    nxt += interval
                    t0 = time.perf_counter()
                    try:
                        client.infer(x)
                        with lock:
                            lat.append(time.perf_counter() - t0)
                    except ServingError:
                        with lock:
                            shed[0] += 1
                    except Exception:
                        with lock:
                            errs[0] += 1
                client.close()

            threads = [threading.Thread(target=_load, args=(k,))
                       for k in range(load_threads)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            lat.sort()
            n = len(lat)
            curve.append({
                "offered_qps": offered,
                "achieved_qps": round(n / dt, 1) if dt > 0 else 0.0,
                "p50_ms": round(lat[n // 2] * 1e3, 3) if n else None,
                "p99_ms": (round(lat[min(n - 1, int(n * 0.99))] * 1e3, 3)
                           if n else None),
                "answered": n, "shed": shed[0], "errors": errs[0],
            })
    finally:
        frontend.close()
        registry.close()
    best = max((c["achieved_qps"] for c in curve), default=0.0)
    return {
        "metric": f"{name}_requests_per_sec",
        "value": round(best, 1), "unit": "requests/s",
        "latency_curve": curve,
        "compiles": registry.compiles(),
    }


def _measure_streaming(name, *, total=90, drift_at=30, num_workers=2,
                       k=2, batch=16, feature_dim=4, num_classes=3,
                       checkpoint_every=8):
    """Config #11 — the streaming continual-training loop, measured as a
    fleet tenant under chaos: a :class:`StreamingTraining` job on a
    :class:`FleetScheduler` pool ingests a throttled socket feed whose
    labels drift at record ``drift_at`` and whose connection is severed
    mid-run, while a :class:`ModelRegistry` hot-swaps its checkpoints
    through the drift watch's regression gate. The headline value is
    committed items/s; the deliverables next to it are the loop-closure
    numbers — event-to-served-weight freshness (p50/p99 across swaps)
    and time-to-recover after the injected drift (page -> clear)."""
    import os as _os
    import tempfile
    import threading

    import numpy as np

    from distkeras_tpu import telemetry
    from distkeras_tpu.fleet import DONE, FleetJob, FleetScheduler
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.mlp import MLP
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.ops.optimizers import get_optimizer
    from distkeras_tpu.resilience import faults
    from distkeras_tpu.resilience.faults import FaultPlan
    from distkeras_tpu.serving import ModelRegistry
    from distkeras_tpu.streaming import (
        DriftWatch,
        SocketSource,
        StreamingTraining,
        StreamProducer,
        WindowedEval,
    )

    def build():
        return Model.build(MLP(hidden=(16,), num_outputs=num_classes),
                           np.zeros((1, feature_dim), np.float32), seed=0)

    rng = np.random.default_rng(7)
    centers = rng.normal(scale=4.0, size=(num_classes, feature_dim))

    def blob(prng, kk, bb):
        y = prng.integers(0, num_classes, size=(kk, bb))
        x = (centers[y] + prng.normal(scale=0.5, size=(kk, bb, feature_dim))
             ).astype(np.float32)
        return x, y.astype(np.int32)

    xh, yh = blob(rng, 1, 64)
    xh, yh_drift = xh[0], ((yh[0] + 1) % num_classes).astype(np.int32)

    base = tempfile.mkdtemp(prefix="dktpu-bench-stream-")
    ckpt_dir = _os.path.join(base, "ckpt")
    faults.set_plan(FaultPlan.parse(
        f"feed_gap@8:0.2;drift@{drift_at};seed=3"))
    prod = StreamProducer()
    watch = DriftWatch(window=WindowedEval(fast=8, slow=40))
    rt = StreamingTraining(
        model=build(), tx=get_optimizer("sgd", 0.1),
        loss_fn=get_loss("sparse_categorical_crossentropy"),
        source=SocketSource(prod.endpoint, drift_classes=num_classes),
        num_workers=num_workers, discipline="adag", seed=0,
        journal=_os.path.join(base, "offsets.json"),
        checkpoint_dir=ckpt_dir, checkpoint_every=checkpoint_every,
        drift_watch=watch, max_pending=8)

    def produce():
        prng = np.random.default_rng(11)
        t0 = time.monotonic()
        for i in range(total):
            while (i - rt.progress() > 24
                   and time.monotonic() - t0 < 240):
                time.sleep(0.02)
            xs, ys = blob(prng, k, batch)
            prod.feed(xs, ys)
            if i == total // 2:
                # Sever the live feed mid-run: reconnect-and-resume is
                # part of the measured steady state, not a free pass.
                prod.kill_connections()
        prod.end()

    def held_out_loss(cand):
        logits = np.asarray(cand.infer((xh,)), np.float64)
        logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        return float(-logp[np.arange(len(yh_drift)), yh_drift].mean())

    registry = ModelRegistry(
        build(), (64,), directory=ckpt_dir, poll_s=0.1,
        quality_gate=watch.regression_gate(held_out_loss,
                                           regress_floor=0.5))
    registry.start()
    sched = FleetScheduler(capacity=num_workers, tick_s=0.02)
    job = sched.submit(FleetJob("stream", "bench", rt, priority=0,
                                min_gang=1, max_workers=num_workers))
    threading.Thread(target=produce, daemon=True).start()
    t0 = time.perf_counter()
    sched.start()
    try:
        ok = sched.wait(timeout=420)
        dt = time.perf_counter() - t0
    finally:
        sched.close()
        registry.close()
        prod.close()
        faults.reset()
    if not ok or job.state != DONE or rt.errors:
        raise RuntimeError(
            f"streaming bench did not drain: state={job.state} "
            f"errors={rt.errors[:2]}")
    registry.poll_once()
    bm, version = registry.current()
    acc = float((np.asarray(bm.infer((xh,))).argmax(-1)
                 == yh_drift).mean())
    fresh = sorted(e["seconds"] for e in telemetry.get().events()
                   if e["kind"] == "serve_freshness")
    n = len(fresh)
    return {
        "metric": f"{name}_items_per_sec",
        "value": round(total / dt, 2) if dt > 0 else None,
        "unit": "items/s",
        "items": total,
        "drift_recovery_s": (round(watch.last_recovery_s, 3)
                             if watch.last_recovery_s is not None else None),
        "drift_events": watch.drift_events,
        "freshness_p50_s": round(fresh[n // 2], 3) if n else None,
        "freshness_p99_s": (round(fresh[min(n - 1, int(n * 0.99))], 3)
                            if n else None),
        "swaps": n,
        "served_step": version,
        "served_acc_drifted": round(acc, 4),
        "source_reconnects":
            int(telemetry.get().counter("stream.source_reconnects").value),
    }


def scaling_sweep():
    """The north-star gate's measurement machinery (BASELINE.md #3): CIFAR-10
    CNN under AEASGD at num_workers = 1, 2, 4, ..., N over the visible devices,
    reporting total samples/s and scaling efficiency vs the 1-worker run
    (``metrics.scaling_efficiency``). On a pod this sweeps real chips; run
    with ``BENCH_SCALING=1``. Prints its own single JSON line and exits."""
    import jax

    from distkeras_tpu.metrics import scaling_efficiency
    from distkeras_tpu.models.cnn import cifar10_cnn

    on_tpu = jax.default_backend() == "tpu"
    n = jax.device_count()
    ws, w = [], 1
    while w <= n:
        ws.append(w)
        w *= 2
    if ws[-1] != n:
        ws.append(n)  # always measure the full visible device count
    # One config for both the sweep and the analytic basis below — they must
    # agree or round_seconds would be computed for the wrong sample count.
    window, batch = 8, 1024 if on_tpu else 16
    points = []
    base_per_chip = None
    for w in ws:
        rec = _measure("cifar10_cnn_aeasgd", cifar10_cnn, "aeasgd",
                       batch_size=batch, window=window,
                       sample_shape=(32, 32, 3), num_classes=10,
                       timed=8 if on_tpu else 2,
                       rounds_per_program=2 if on_tpu else 1, num_workers=w,
                       measure_stall=False)
        per_chip = rec["value"]
        total = per_chip * w
        if base_per_chip is None:
            base_per_chip = per_chip
        points.append({
            "num_workers": w,
            "samples_per_sec_total": round(total, 1),
            "scaling_efficiency": round(
                scaling_efficiency(total, base_per_chip, w), 4),
        })
    out = {
        # Headline = the north-star gate's analytic bound when computable
        # (the r3 verdict flagged the old measured-at-N=1 headline as a
        # tautology dressed as a measurement); the measured single/virtual-
        # mesh points stay, honestly labeled. ``kind`` declares the
        # headline's provenance so downstream tooling cannot mistake an
        # analytic bound for a measurement (VERDICT r4 weak #4): on a
        # one-chip host the sweep measures nothing beyond N=1, and the
        # gate ratio lives under ``analytic_v5e``, not the top level.
        "metric": "cifar10_cnn_aeasgd_scaling_efficiency",
        "value": points[-1]["scaling_efficiency"],
        "unit": "ratio (throughput(N) / (N x throughput(1)))",
        "kind": "measured",
        "vs_baseline": round(points[-1]["scaling_efficiency"] / 0.90, 3),
        "measured_points": points,
    }
    if on_tpu:
        # Analytic v5e extrapolation for the north-star gate: measured
        # single-chip round time + ring-all-reduce ICI cost (roofline.py;
        # tests/test_scaling_model.py pins the >=90%@64 bound). TPU-only:
        # a CPU round time is not a v5e round time, and labeling it one
        # would overstate the bound.
        from distkeras_tpu.roofline import FoldScalingModel

        sps1 = base_per_chip
        model_bytes = cifar10_cnn().num_params * 4
        analytic = FoldScalingModel(
            round_seconds=(window * batch) / sps1, model_bytes=model_bytes)
        out["metric"] = "cifar10_cnn_aeasgd_predicted_scaling_efficiency_at_64"
        out["value"] = round(analytic.efficiency(64), 4)
        out["unit"] = ("ratio (analytic bound from measured single-chip "
                       "round; one ring direction, zero overlap)")
        out["kind"] = "analytic-bound"
        # The gate ratio is model-output / 0.90 — it belongs with the model,
        # not in measurement clothing at the top level.
        del out["vs_baseline"]
        out["analytic_v5e"] = {
            "vs_gate_0p90": round(analytic.efficiency(64) / 0.90, 3),
            "basis": {
                "measured_samples_per_s_per_chip": round(sps1, 1),
                "round_seconds": round((window * batch) / sps1, 6),
                "model_bytes": int(model_bytes),
                "ici_link_bytes_per_s": 45e9,
                "assumptions": "one ring direction, zero compute/comm overlap",
            },
            "curve": analytic.curve(),
            "predicted_efficiency_at_64": analytic.efficiency(64),
        }
    out["resnet50_sync_v5e"] = resnet_sync_scaling_section()
    _emit_summary(out)


def resnet_sync_scaling_section() -> dict:
    """BASELINE #5's actual gate: ResNet-50 *synchronous* DP — a per-STEP
    ~100 MB f32 grad all-reduce with no window amortization — modeled to 256
    chips over ICI and across a v5e multislice DCN hop, from the measured
    single-chip step time in the most recent committed bench record
    (``roofline.SyncStepScalingModel``; pinned by tests/test_scaling_model).
    Includes the levers (bf16 grad all-reduce, grad_accum) at 256 chips."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.resnet import ResNet
    from distkeras_tpu.roofline import SyncStepScalingModel

    batch = 128  # the bench config's per-chip batch
    sps = _prior_values().get("resnet50_sync_samples_per_sec_per_chip",
                              1980.4)  # BENCH_r03 floor
    step_s = batch / sps
    # Param bytes without a concrete init: eval_shape traces shapes only.
    module = ResNet(stage_sizes=(3, 4, 6, 3), num_outputs=1000)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0),
                            jnp.zeros((1, 224, 224, 3), jnp.float32),
                            train=False))
    grad_bytes = 4 * sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["params"]))

    base = SyncStepScalingModel(step_seconds=step_s, grad_bytes=grad_bytes)
    multi = SyncStepScalingModel(step_seconds=step_s, grad_bytes=grad_bytes,
                                 chips_per_slice=128)
    bf16 = SyncStepScalingModel(step_seconds=step_s, grad_bytes=grad_bytes / 2)
    accum2 = SyncStepScalingModel(step_seconds=step_s, grad_bytes=grad_bytes,
                                  grad_accum=2)
    return {
        "basis": {
            "measured_samples_per_s_per_chip": round(float(sps), 1),
            "per_chip_batch": batch,
            "step_seconds": round(step_s, 6),
            "grad_bytes": int(grad_bytes),
            "ici_link_bytes_per_s": 45e9,
            "dcn_bytes_per_s_per_host": 25e9,
            "assumptions": ("per-step f32 grad all-reduce, one ring "
                            "direction, zero compute/comm overlap; "
                            "multislice = intra-slice reduce-scatter + "
                            "cross-slice DCN exchange per host NIC + "
                            "intra-slice all-gather"),
        },
        "curve_single_slice_ici": base.curve(),
        "predicted_efficiency_at_64": round(base.efficiency(64), 4),
        "predicted_efficiency_at_256": round(base.efficiency(256), 4),
        "multislice_2x128": {
            "comm_ms_at_256": round(multi.comm_seconds(256) * 1e3, 4),
            "predicted_efficiency_at_256": round(multi.efficiency(256), 4),
        },
        "levers_at_256": {
            "bf16_grad_allreduce": round(bf16.efficiency(256), 4),
            "grad_accum_2": round(accum2.efficiency(256), 4),
        },
    }


def main():
    import jax

    # BENCH_PLATFORM=cpu pins the platform for the virtual-mesh sweep (the
    # forced host-device count only exists on the cpu backend).
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

    from distkeras_tpu.runtime.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    if os.environ.get("BENCH_SCALING") not in (None, "", "0"):
        scaling_sweep()
        return

    from distkeras_tpu.models.cnn import cifar10_cnn, mnist_cnn
    from distkeras_tpu.models.lstm import imdb_lstm
    from distkeras_tpu.models.mlp import mnist_mlp
    from distkeras_tpu.models.resnet import resnet50, tiny_resnet

    on_tpu = jax.default_backend() == "tpu"
    # CPU CI smoke: shrink work so the script stays fast; TPU gets real sizes.
    scale = 1.0 if on_tpu else 0.1

    def rounds(n):
        return max(2, int(n * scale))

    configs = [
        # 1 — correctness/throughput floor: MNIST MLP, single process
        ("mnist_mlp_single", mnist_mlp, "single",
         dict(batch_size=1024 if on_tpu else 64, window=8, sample_shape=(784,),
              num_classes=10, timed=rounds(64), optimizer="adam",
              rounds_per_program="auto")),
        # 2 — MNIST CNN under ADAG (async adaptive gradients). B=2048: the
        # r4 on-chip B-sweep (1024/2048/4096 -> 31.6/35.0/24.6 TF raw step)
        # puts the knee at 2048; see docs/PERFORMANCE.md.
        ("mnist_cnn_adag", mnist_cnn, "adag",
         dict(batch_size=2048 if on_tpu else 32, window=8,
              sample_shape=(28, 28, 1), num_classes=10, timed=rounds(32),
              rounds_per_program="auto")),
        # 3 — NORTH STAR: CIFAR-10 CNN under AEASGD (elastic averaging).
        # B=2048: r5 same-process sweep 1024 -> 240.5k, 2048 -> 247.8k
        # samples/s/chip (higher arithmetic intensity past the B=1024
        # byte profile the r4 ceiling was derived at).
        ("cifar10_cnn_aeasgd", cifar10_cnn, "aeasgd",
         dict(batch_size=2048 if on_tpu else 16, window=8,
              sample_shape=(32, 32, 3), num_classes=10, timed=rounds(16),
              rounds_per_program="auto")),
        # 4 — IMDB LSTM under DynSGD (staleness-aware)
        # cell_impl="pallas": the whole recurrence as one Pallas program
        # (weights resident in VMEM across timesteps) — 1.9x over the XLA
        # scan lowering on this chip (ops/pallas/lstm.py).
        # B=2048 amortizes the recurrence's serial per-step latency (r4
        # B-sweep: 512/1024/2048/4096 -> 22.4/27.4/34.1/32.6 TF; the kernel's
        # VMEM cap was raised to admit B>2048 — docs/PERFORMANCE.md).
        ("imdb_lstm_dynsgd",
         lambda: imdb_lstm(vocab_size=20000, embed_dim=64, hidden_size=128,
                           seq_len=200, cell_impl="pallas" if on_tpu else "xla"),
         "dynsgd",
         dict(batch_size=2048 if on_tpu else 8, window=4, sample_shape=(200,),
              num_classes=2, timed=rounds(24), int_inputs=True, vocab=20000,
              rounds_per_program="auto")),
        # 5 — ResNet-50 sync DP (BASELINE's pod config, single-chip slice here)
        # CPU smoke swaps in the CIFAR-shaped tiny ResNet: compiling the full
        # 224x224 ResNet-50 fwd+bwd takes minutes on the 2-core box and the
        # off-TPU number is meaningless anyway.
        ("resnet50_sync", resnet50 if on_tpu else tiny_resnet, "sync",
         dict(batch_size=128 if on_tpu else 4, window=2,
              sample_shape=(224, 224, 3) if on_tpu else (32, 32, 3),
              num_classes=1000 if on_tpu else 10,
              timed=rounds(8), warmup=2)),
    ]

    # 6 - beyond-reference flagship: TransformerLM + flash attention.
    # model_fn=None + discipline="transformer" routes to the dedicated
    # measure function (tokens/s unit).
    configs.append(("transformer_lm_flash", None, "transformer",
                    dict(num_layers=8, d_model=1024, num_heads=16, d_ff=4096,
                         vocab=32768, seq_len=2048, batch=8, timed=16)))

    # 7 - the composition: the same flagship trained as an AEASGD worker
    # (async discipline engine: window scan + elastic fold, remat). Expect
    # ~80% of config #6's step rate (PERFORMANCE.md).
    configs.append(("transformer_aeasgd_flash", None, "async_transformer",
                    dict(num_layers=8, d_model=1024, num_heads=16, d_ff=4096,
                         vocab=32768, seq_len=2048, batch=8)))

    # 8 - the netps data plane: an AEASGD transformer trained THROUGH the
    # networked PS over loopback, A/B'd against the PR 4 data plane and the
    # in-process fold on the same model + executable, so the RPC overhead
    # (and what overlap/compression/striping recover of it) is a pinned
    # number. The shape is deliberately comms-visible — a ~17M-param tree
    # (68 MB f32 per pull/commit direction) with few tokens per round — so
    # the A/B measures the WIRE, not the matmuls around it; that is also
    # the regime where the netps gap to the in-process fold lives.
    configs.append(("netps_loopback_aeasgd", None, "netps_transformer",
                    dict(num_layers=4, d_model=512, num_heads=8, d_ff=2048,
                         vocab=8192, seq_len=128, batch=4, window=2,
                         rounds=12)))

    # 9 - the serving plane: p50/p99 latency vs offered QPS over a loopback
    # micro-batching frontend (distkeras_tpu/serving/). Open-loop load at
    # each level; the curve shows where bucketed batching holds p99 flat
    # and where admission control sheds instead of letting the queue eat
    # the tail.
    configs.append(("serving_latency", None, "serving",
                    dict(feature_dim=64, hidden=256, num_classes=10,
                         qps_levels=(50, 200, 800), duration_s=2.0)))

    # 10 - the sharded center plane: fold throughput vs shard count over
    # the SAME synthetic center (1 = plain PSServer baseline, 2/4 =
    # ShardSet gangs dialed through ShardedPSClient). The curve pins how
    # much of the single-PS fold-lock bottleneck the partition plan
    # actually splits (acceptance: >= 1.6x at 4 shards on real hardware).
    configs.append(("sharded_center", None, "sharded_center",
                    dict(tensors=16, rows=256,
                         cols=512 if on_tpu else 256,
                         workers=4, commits=6 if on_tpu else 4)))

    # 11 - the streaming continual-training loop as a fleet tenant under
    # chaos (feed gap + injected concept drift + severed feed): committed
    # items/s headline, with the loop-closure numbers next to it —
    # event-to-served-weight freshness p50/p99 at hot-swap and
    # time-to-recover after drift@R (page -> clear). Host/IO bound by
    # design; the same size runs on CPU CI and on-chip.
    configs.append(("streaming_loop", None, "streaming",
                    dict(total=90, drift_at=30, num_workers=2)))

    # Optional subset for debugging: BENCH_CONFIGS=cifar10,resnet python bench.py
    only = [s for s in os.environ.get("BENCH_CONFIGS", "").split(",") if s]
    if only:
        configs = [c for c in configs if any(tag in c[0] for tag in only)]

    from distkeras_tpu import telemetry

    tele = telemetry.get()
    prior = _prior_values()
    pins, band = _pin_config()
    results = []
    for name, model_fn, discipline, kw in configs:
        t_cfg = time.perf_counter()
        try:
            with tele.span(f"bench[{name}]"):
                if discipline == "transformer":
                    rec = _measure_spmd_transformer(name, **kw)
                elif discipline == "async_transformer":
                    rec = _measure_async_transformer(name, **kw)
                elif discipline == "netps_transformer":
                    rec = _measure_netps_transformer(name, **kw)
                elif discipline == "serving":
                    rec = _measure_serving(name, **kw)
                elif discipline == "sharded_center":
                    rec = _measure_sharded_center(name, **kw)
                elif discipline == "streaming":
                    rec = _measure_streaming(name, **kw)
                else:
                    rec = _measure(name, model_fn, discipline, **kw)
        except Exception as e:  # a config must never take down the whole bench
            kind = ("tokens" if "transformer" in str(discipline)
                    else "samples")
            rec = {"metric": f"{name}_{kind}_per_sec_per_chip",
                   "value": None, "unit": f"{kind}/s/chip",
                   "error": f"{type(e).__name__}: {e}"}
        # Every config record carries its config NAME alongside the derived
        # metric string, so summary consumers (the regression sentinel, ad
        # hoc jq) select configs without re-parsing metric suffixes.
        rec.setdefault("name", name)
        tele.event("bench_config", {k: rec.get(k) for k in
                                    ("name", "metric", "value", "unit",
                                     "input_stall_fraction", "error")
                                    if rec.get(k) is not None})
        entry = pins.get(rec["metric"]) if rec.get("value") else None
        if entry and entry.get("pin"):
            rec["vs_baseline"] = round(rec["value"] / entry["pin"], 3)
            cfg_band = (float(entry["band_pct"]) / 100.0
                        if entry.get("band_pct") is not None else band)
            rec["within_band"] = bool(
                abs(rec["value"] / entry["pin"] - 1.0) <= cfg_band)
            if entry.get("ceiling_samples_per_sec"):
                rec["vs_ceiling"] = round(
                    rec["value"] / entry["ceiling_samples_per_sec"], 3)
        elif rec.get("value") and rec["metric"] in prior:
            # Unpinned config (new this round): previous artifact, as before.
            rec["vs_baseline"] = round(rec["value"] / prior[rec["metric"]], 3)
        results.append(rec)
        print(f"[bench] {name}: {rec.get('value')} {rec.get('unit')} "
              f"(tflops={rec.get('achieved_tflops_per_chip')}, "
              f"{time.perf_counter() - t_cfg:.0f}s)", file=__import__('sys').stderr)

    headline = next(
        (r for r in results if r["metric"].startswith("cifar10")), results[0]
    )
    out = {
        "metric": headline["metric"],
        "value": headline["value"],
        "unit": headline["unit"],
        "vs_baseline": headline.get("vs_baseline", 1.0),
        "within_band": headline.get("within_band"),
        "achieved_tflops_per_chip": headline.get("achieved_tflops_per_chip"),
        "mfu_vs_bf16_peak": headline.get("mfu_vs_bf16_peak"),
        # Compute-vs-data split (real staged path, not the pre-staged timed
        # loop): future bench rounds can tell an input-bound regression from
        # a compute one.
        "input_stall_fraction": headline.get("input_stall_fraction"),
        "configs": results,
        # Health-plane rollup: alerts the run raised/cleared (counters +
        # typed events from the telemetry registry) and configs that left
        # their pinned band — the regression sentinel reads this block,
        # so perf drift is visible in the same trajectory as perf itself.
        "health_summary": _health_summary(tele, results),
    }
    # Telemetry JSONL beside the bench record (driver captures stdout into
    # BENCH_r*.json; the spans/counters/per-config events land here).
    tele_path = os.environ.get("BENCH_TELEMETRY_PATH",
                               os.path.join(_REPO, "BENCH_TELEMETRY.jsonl"))
    try:
        from distkeras_tpu.telemetry.exporters import write_jsonl

        write_jsonl(tele, tele_path, extra={"source": "bench.py"})
    except Exception as e:  # diagnostics never fail the bench
        print(f"[bench] telemetry dump failed: {e}",
              file=__import__("sys").stderr)
    _emit_summary(out)


if __name__ == "__main__":
    main()

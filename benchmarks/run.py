"""The benchmark's command: one cell, one seed, one measured window, one line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process trains the cell's configuration through the normal
``dk.<Trainer>(...).train(dataframe)`` on the chips JAX finds, for a warm-up
and then ``--seconds`` of measured rounds (``harness/window.py``), checks what
came out, and prints as the last line of its standard output the JSON object
the driver reads (``harness/result_line.py`` checks it first). Everything else
goes on earlier lines, each starting ``[bench``.

Nothing here names a cell, a configuration or a metric. ``--workload X`` is
``workloads/X.json``; that names ``configs/<config>.json``; that names
``families/<family>.py``; the metrics X reports are BENCHMARK.json's, each with
a file ``end_to_end/<metric>.json`` or ``layer_metrics/<metric>.json`` naming a
reader in ``readers/``. A later PR adds files and entries and edits none.

Without a TPU, or with another number of chips than the cell asks for, the
command prints no result line and exits 2. ``--rehearse`` (tests and local use
only) swaps in the family's tiny preset and accepts a CPU; the line then says
``"platform": "cpu"`` and none of its numbers is a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can say

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)  # `benchmarks` and `distkeras_tpu`, from any cwd


class RunFailed(Exception):
    """The run cannot print an honest line; the message says why."""


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_START:7.2f}s] {msg}", flush=True)


def _load(*parts) -> dict:
    path = os.path.join(HERE, *parts)
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise RunFailed(f"no file {os.path.relpath(path, ROOT)}") from None


def read_metrics(run, declared: dict, traced: bool) -> dict:
    """Each declared metric through its file's reader. A reader that finds
    nothing returns ``None``; the metric is then left out, and the line's own
    check refuses the line, naming it."""
    values = {}
    for name in declared:
        spec = _load("layer_metrics" if traced else "end_to_end",
                     f"{name}.json")
        reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        value = reader.read(run, **spec.get("arguments", {}))
        if value is None:
            log(f"metric {name}: its reader ({spec['reader']}) found nothing")
        else:
            values[name] = float(value)
    return values


def device_block(rehearse: bool) -> dict:
    """The device as JAX reports it, and the peak on the fullest chip. The
    CPU backend keeps no memory statistics, so a rehearsal (only) puts the
    process's peak resident size there for the same code to carry."""
    import jax

    dev = jax.devices()[0]
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        # What arrays held at their peak, and what the runtime set aside for
        # the programs' own scratch (a TPU reports that apart, as reserved;
        # arrays cannot use it): both are the chip's memory, taken.
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
        log(f"memory of {d}: " + ", ".join(
            f"{k} {v / 1e9:.3f} GB" for k, v in sorted(stats.items())
            if "bytes" in k))
    if rehearse and not any(peaks):
        import resource

        peaks = [resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def round_program_text(engine, steps: int, batch: int, shapes) -> str:
    """The optimized HLO of the engine's round program, compiled for the
    argument shapes and shardings ``train()`` ran it with (a copy of
    ``chip_smoke.round_program_text``). The compile hits the cache."""
    import numpy as np

    x_shape, x_dtype, y_shape, y_dtype = shapes
    lead = (engine.num_workers, steps, batch)
    xs, ys = engine._put_batch(np.zeros(lead + tuple(x_shape), x_dtype),
                               np.zeros(lead + tuple(y_shape), y_dtype))
    return engine._round_fn.lower(
        engine.init_state(), xs, ys).compile().as_text()


def count_all_reduce(hlo: str) -> int:
    return len(re.findall(r"all-reduce(?:-start)?\(", hlo))


class CompileLog:
    """When the backend compiled, by JAX's own monitoring events, so that a
    compilation inside the measured segment is seen and fails the run."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.ends: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.ends.append((time.perf_counter(), duration))

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.ends if t0 < t <= t1)


def make_window(args, trace_dir, **hooks):
    """The run's clock (``harness/window.py``) as ``main`` builds it. On the
    chip the trace spans about ``TRACE_MIN_S``; a rehearsal's closes at the
    fewest rounds the bracket's rule takes, since it measures nothing."""
    from benchmarks.harness.window import TRACE_MIN_S, Window

    return Window(args.seconds, t_start=T_START, trace_dir=trace_dir,
                  trace_min_s=0.0 if args.rehearse else TRACE_MIN_S, **hooks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset, any platform: control flow only")
    args = ap.parse_args(argv)
    traced = bool(args.trace)

    from benchmarks.harness import peaks, result_line, trace_reduce
    from benchmarks.harness.window import WindowClosed

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    cell = args.workload
    workload = _load("workloads", f"{cell}.json")
    config = _load("configs", f"{workload['config']}.json")
    chips = int(workload["chips"])
    declared = result_line.declared_metrics(manifest, cell, traced)
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    if args.rehearse:
        config = {**config, **family.TINY,
                  "module": {**config["module"], **family.TINY["module"]}}

    try:
        import jax

        import distkeras_tpu as dk
        from distkeras_tpu import telemetry
        from distkeras_tpu.runtime.compile_cache import ensure_compile_cache
    except ImportError as e:
        raise RunFailed(f"the program is not importable from {ROOT}: {e}") \
            from None

    # -- device: a TPU with the cell's chips, or nothing ----------------------
    dev = jax.devices()[0]
    platform = "cpu" if args.rehearse else "tpu"
    log(f"{cell}: {len(jax.devices())} x {dev.device_kind!r} ({dev.platform}), "
        f"seed {args.seed}, {args.seconds:g} s, trace {args.trace}"
        + (", REHEARSAL: no number below is a measurement"
           if args.rehearse else ""))
    if dev.platform != platform:
        raise RunFailed(
            f"found platform {dev.platform!r}, not {platform!r}: this command "
            "measures on a TPU and falls back to nothing"
            + ("" if args.rehearse else "; --rehearse walks it on a CPU"))
    if len(jax.devices()) != chips:
        raise RunFailed(f"the cell asks for {chips} chip(s) and JAX finds "
                        f"{len(jax.devices())}")
    peak = peaks.lookup(dev.device_kind, rehearse=args.rehearse)
    if not args.rehearse:  # a rehearsal measures nothing and caches nothing
        # Every program into the persistent cache, the sub-second ones of
        # Model.build's eager init too: each run is a new process and pays
        # them.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"compile cache {ensure_compile_cache()}")
    compiles = CompileLog(jax)

    # -- set-up: model, reference check, data, trainer -------------------------
    # The program takes seeds as int32 in places; the data takes all of it.
    seed = args.seed % (2 ** 31 - 1)
    kwargs = dict(workload["trainer"]["kwargs"])
    model = family.build_model(config, seed)
    log(f"model built: {model.num_params:,} parameters")
    check = family.reference_check(model, config, args.seed,
                                   kwargs.get("compute_dtype"))
    log(f"reference check: {check}")
    if not check["ok"]:
        raise RunFailed(
            f"the built model disagrees with references/{config['family']}.py "
            f"on the logits: relative L2 {check['rel_l2']:.3g} above "
            f"{check['tolerance']:g}")
    # The feed's other keys are the traffic's parameters for the generator.
    feed = {k: v for k, v in workload["feed"].items()
            if k not in ("rows", "num_epoch")}
    df = family.make_dataframe(config, workload["feed"]["rows"], args.seed,
                               **feed)
    steps, batch = kwargs[workload["trainer"]["steps_kwarg"]], kwargs["batch_size"]
    units_per_round = (kwargs["num_workers"] * steps * batch
                       * family.units_per_sample(config))

    tele = telemetry.get()
    marks = {}
    trace_dir = tempfile.mkdtemp(prefix="dkbench-trace-") if traced else None
    window = make_window(
        args, trace_dir, on_open=lambda: marks.update(open=tele.mark()),
        on_close=lambda: marks.update(spans=tele.delta(
            marks["open"])[0]["spans"]))
    trainer = getattr(dk, workload["trainer"]["class"])(
        model, **kwargs, num_epoch=workload["feed"]["num_epoch"], seed=seed,
        on_round=window)
    window.t_train = time.perf_counter()
    try:
        trainer.train(df)
        raise RunFailed(
            f"the plan of {len(window.ticks)} rounds ended inside the window: "
            "give the workload's feed more rows or epochs")
    except WindowClosed:
        pass
    finally:
        window.abort_trace()
    i0, i1 = window.segment
    log(f"set-up {window.ticks[0] - T_START:.2f} s (build "
        f"{window.t_train - T_START:.2f} s); segment: {window.segment_rounds} "
        f"rounds in {window.segment_s:.3f} s, {units_per_round} units a round; "
        f"loss {window.losses[0]:.4f} -> {window.losses[-1]:.4f}")
    device = device_block(args.rehearse)  # before the HLO check allocates a state

    # -- the trace ------------------------------------------------------------
    trace = None
    if traced:
        try:
            planes, annotation = trace_reduce.load(trace_dir, dev.platform, log)
            trace = trace_reduce.reduce_planes(planes, annotation,
                                               window.trace_rounds)
        except trace_reduce.TraceUnreadable as e:
            raise RunFailed(f"trace: {e}") from None
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        log(f"trace: {trace['rounds']} rounds in a bracket of "
            f"{trace['window_s']:.4f} s, busy {trace['busy_s']:.4f} s (mean of "
            f"{trace['planes']} plane(s): {trace['busy_s_per_plane']}); "
            f"longest gaps on device 0 {trace['longest_gaps_s']} s; "
            f"profiler start/stop took {window.trace_cost_s}")

    # -- correct? --------------------------------------------------------------
    why_not = []
    losses = window.losses
    if not all(map(math.isfinite, losses)):
        why_not.append("a fetched loss is not finite")
    tail = window.segment_losses()
    tail = tail[-max(len(tail) // 4, 1):]
    if not sum(tail) / len(tail) < losses[0]:
        why_not.append(f"the loss did not fall: first round {losses[0]:.4f}, "
                       f"mean of the segment's last quarter "
                       f"{sum(tail) / len(tail):.4f}")
    n_compiles = compiles.inside(window.ticks[i0], window.ticks[i1])
    if n_compiles:
        why_not.append(f"{n_compiles} compilation(s) inside the measured "
                       "segment")
    interpreted = int(tele.counter("pallas.interpreted_calls").value)
    del df
    gc.collect()  # the trained state, which the caught exception's frames held
    t0 = time.perf_counter()
    hlo = round_program_text(trainer.engine, steps, batch,
                             family.sample_shapes(config))
    mosaic, all_reduces = hlo.count("tpu_custom_call"), count_all_reduce(hlo)
    log(f"round program: {mosaic} Mosaic calls, {all_reduces} all-reduces "
        f"(text in {time.perf_counter() - t0:.1f} s); interpreted Pallas "
        f"calls {interpreted}; compilations in the segment {n_compiles}")
    if trace:
        # The readers of single ops rest on this: a trace event carries the
        # name of the program's instruction.
        seen = {name for _, _, name in trace["ops0"]}
        folds = trace_reduce.instruction_names(hlo, "all-reduce")
        log(f"trace: {len(seen & trace_reduce.instruction_names(hlo))} of "
            f"{len(seen)} distinct op names are instructions of the round "
            f"program; its {len(folds)} all-reduce instructions have "
            f"{sum(1 for e in trace['ops0'] if e[2] in folds)} events in "
            f"the op line and "
            f"{sum(1 for e in trace['async0'] if e[2] in folds)} in the "
            "async line")
    if dev.platform == "tpu":
        if interpreted:
            why_not.append(f"{interpreted} Pallas calls ran interpreted")
        if family.expects_mosaic(config) and not mosaic:
            why_not.append("the round program holds no Mosaic call")
    if kwargs["num_workers"] > 1 and not all_reduces:
        why_not.append("the round program of several workers holds no "
                       "all-reduce")
    for reason in why_not:
        log(f"NOT CORRECT: {reason}")

    # -- the line ----------------------------------------------------------------
    # What the run recorded, as the readers take it.
    run = types.SimpleNamespace(
        window=window, chips=chips, units_per_round=units_per_round,
        feed_waits=list(getattr(trainer.engine, "feed_waits", [])),
        spans=marks["spans"], trace=trace, peak=peak, hlo=hlo,
        flops_per_unit=family.train_flops_per_unit(config))
    line = result_line.build(
        correct=not why_not, attempted=window.segment_rounds,
        failed=window.failed_rounds(),
        values=read_metrics(run, declared, traced), declared=declared,
        device=device, breakdown=trace["breakdown"] if trace else None)
    try:
        result_line.validate(line, declared, chips, traced, platform)
    except result_line.LineRefused as e:
        raise RunFailed(f"the result line was refused before printing: {e}") \
            from None
    sys.stderr.flush()
    print(result_line.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except RunFailed as e:
        print(f"[bench] FAILED, no result: {e}", file=sys.stderr, flush=True)
        sys.exit(2)

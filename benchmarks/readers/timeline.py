"""Set-up time by the program's own spans, from its telemetry timeline.

``telemetry.get().timeline()`` lists the ended spans of the process with
their start on ``time.perf_counter_ns`` — the clock of the harness's ticks.
Set-up ends with the first tick (``window.ticks[0]``, the first fetched
loss), so a set-up metric is the time that the named spans which ended by
then cover (:func:`covered_seconds`); ``run.py`` lowers the round program
once more after the run, and that must not count. A path matches whether the span was opened at
the top (``model_build``) or under another (``a/model_build``). The
``compile.*`` spans are flat, so those of the programs ``Model.build`` runs
lie inside ``model_build`` in time: a metric's ``outside`` leaves them to it,
and the set-up metrics add up without counting a second twice.

What a reader returns where there is nothing to read: ``None`` (which fails
the traced run, by name) when the program keeps a timeline and its registry
declares no such span, because a refactor dropped it; ``0.0`` when the span
is declared and none ended before the first tick (no cache load in a cold
run); and ``0.0``, said on a ``[bench`` line, when the program's telemetry
has no ``timeline`` at all: such a program predates it (the parent commit of
the PR that added it), and ``harness/result_line.py`` cannot print a line
that leaves a declared metric out.
"""

from __future__ import annotations


def _say(msg: str) -> None:
    print(f"[bench] timeline: {msg}", flush=True)


def spans():
    """The program's timeline entries, or ``None`` where it keeps none."""
    from distkeras_tpu import telemetry

    timeline = getattr(telemetry.get(), "timeline", None)
    return None if timeline is None else timeline()


def covered_seconds(entries, paths, first_tick_ns: int, outside=()) -> float:
    """Seconds that the entries named in ``paths`` cover, thread by thread,
    up to ``first_tick_ns``: the union of their intervals and not the sum of
    their durations, because JAX reports the tracing of a function and of
    every jitted function it calls, one inside the other. Entries that lie
    inside a span named in ``outside`` on the same thread are left to it
    (``Model.build``'s own small programs compile inside ``model_build``)."""
    wanted, around = set(paths), set(outside)
    done = [e for e in entries if e["t0_ns"] + e["dur_ns"] <= first_tick_ns]
    holes = [(e["thread"], e["t0_ns"], e["t0_ns"] + e["dur_ns"]) for e in done
             if e["path"].rsplit("/", 1)[-1] in around]
    spans_of: dict = {}
    for e in done:
        lo, hi = e["t0_ns"], e["t0_ns"] + e["dur_ns"]
        if e["path"].rsplit("/", 1)[-1] in wanted and not any(
                thread == e["thread"] and a <= lo and hi <= b
                for thread, a, b in holes):
            spans_of.setdefault(e["thread"], []).append((lo, hi))
    total = 0
    for intervals in spans_of.values():
        end = None
        for lo, hi in sorted(intervals):
            if end is None or lo > end:
                total, end = total + hi - lo, hi
            elif hi > end:
                total, end = total + hi - end, hi
    return total / 1e9


def _say_setup(run, entries, first_tick_ns: int) -> None:
    """Once a run: what each path's spans cover up to the first tick,
    longest first (the next reader of a slow set-up starts here)."""
    if hasattr(run, "timeline_said"):
        return
    run.timeline_said = True
    by_path: dict = {}
    for e in entries:
        if e["t0_ns"] + e["dur_ns"] <= first_tick_ns:
            by_path.setdefault(e["path"], []).append(e)
    covered = {p: (covered_seconds(es, [p.rsplit("/", 1)[-1]], first_tick_ns),
                   len(es)) for p, es in by_path.items()}
    _say("covered by the first tick, s (spans): " + ", ".join(
        f"{path} {t:.3f} ({n})" for path, (t, n) in
        sorted(covered.items(), key=lambda kv: -kv[1][0])[:16] if t))


def read(run, paths, outside=()):
    entries = spans()
    if entries is None:
        _say(f"this program's telemetry keeps no timeline (it predates it): "
             f"{'+'.join(paths)} reads 0")
        return 0.0
    from distkeras_tpu.telemetry import core, registry

    if not all(registry.declared("span", p) for p in [*paths, *outside]):
        return None
    if len(entries) >= core.TIMELINE_CAPACITY:
        _say("the timeline's ring is full: set-up's spans may have left it")
        return None
    first_tick_ns = int(run.window.ticks[0] * 1e9)
    _say_setup(run, entries, first_tick_ns)
    return covered_seconds(entries, paths, first_tick_ns, outside)

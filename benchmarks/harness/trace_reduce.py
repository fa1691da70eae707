"""From a profiler trace to device busy time, collective time and a breakdown.

Plain functions over ``(start_ns, dur_ns, name)`` lists, so the CPU tests feed
them hand-made intervals; only :func:`load` touches the profiler's file.

What a trace of this system on a TPU v5e holds (looked at by hand, the
``.trace.json.gz`` of a ResNet-50 run through ``SynchronousDistributedTrainer``
under jax 0.9 / libtpu 0.0.34): one plane per chip, ``/device:TPU:<n>``, with
three lines. ``XLA Ops`` has one event per executed HLO op (about 7,000 a
ResNet round, 55,000 a GPT-2 medium round), named by the whole text of its
instruction, nested where a ``while`` or a fusion encloses others. ``XLA
Modules`` has one event per executed program (``jit_round_fn(<hash>)``), back
to back when the host keeps up. ``Steps`` repeats the modules. The host plane
``/host:CPU`` holds the threads. So on a TPU:

* the plane's busy time comes from **one** line, ``XLA Ops``: overlapping
  intervals are merged (nesting would count twice), clipped to the bracket,
  and summed. ``XLA Modules`` is not added to it: a module's event covers the
  waits inside the program too;
* the bracket is on the device's own clock: from the start of the second
  module event in the trace to the start of the last. The first may have been
  cut by the start of the trace and the last by its end; between those two
  starts lie whole rounds and every idle gap between them. The window is the
  bracket, not first-op-to-last-op, or idle at the edges would be lost;
* with four planes the busy time is their **mean**, never their sum;
* ``Async XLA Ops`` holds the flight of each asynchronous pair, from its
  ``-start`` to its ``-done`` (copies, slices, collectives). It is kept apart
  (``async0``) for the readers of collectives and never counted as busy;
* the host tracer is **off** on a TPU (``host_tracer_level`` 0). With it on at
  any level the runtime's transfer threads (``pjrt-tpu-tasks``) write an event
  per chunk of every upload: 7.8 million in a 4.6 s trace of the ResNet cell's
  38.5 MB rounds, which stretched those rounds from 114 ms to 512 ms and
  ``stop_trace`` to 40 s (my chip run, PR 24). A trace that slows what it
  measures reads idle time that is not the system's.

The CPU backend has no device plane. Its ops run on host threads named
``tf_XLAPjRtCpuClient/<n>`` and carry the stats ``hlo_op`` and
``device_ordinal``; a rehearsal groups them by ordinal into stand-in planes and
takes the bracket from the harness's own ``TraceAnnotation``. Those selectors
are the only difference between the platforms, and they are data (below).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

#: The ``TraceAnnotation`` the harness holds open around the traced rounds.
BRACKET_ANNOTATION = "dkbench.bracket"

#: Where each platform's trace keeps what. ``bracket.line`` names the line of
#: whole-program events on the device clock; ``bracket.annotation`` names a
#: host ``TraceAnnotation`` (then the caller says how many rounds it held).
SELECTORS = {
    "tpu": {"plane": r"^/device:TPU:(\d+)$", "ops_line": r"^XLA Ops$",
            "async_line": r"^Async XLA Ops$",
            "bracket": {"line": r"^XLA Modules$"}, "host_tracer_level": 0},
    "cpu": {"plane": r"^/host:CPU$", "ops_line": r"^tf_XLAPjRtCpuClient",
            "op_stat": "hlo_op", "device_stat": "device_ordinal",
            "bracket": {"annotation": BRACKET_ANNOTATION},
            "host_tracer_level": 1},
}


class TraceUnreadable(ValueError):
    """The trace does not hold what the reduction needs; the run fails."""


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def merge(events, lo=None, hi=None):
    """Union of the events' intervals, clipped to ``[lo, hi]``: a sorted list
    of ``(start, end, name)`` where ``name`` is the event that ended last in
    the merged run (what the device did before the gap that follows)."""
    spans = []
    for start, dur, name in events:
        s, e = start, start + dur
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e > s:
            spans.append((s, e, name))
    spans.sort(key=lambda t: (t[0], t[1]))
    out = []
    for s, e, name in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e, name)
        else:
            out.append((s, e, name))
    return out


def busy_ns(events, lo, hi) -> float:
    return sum(e - s for s, e, _ in merge(events, lo, hi))


def self_ns_by_name(events, lo, hi) -> dict:
    """Each name's own time inside ``[lo, hi]``: an event's duration less the
    part its direct children cover, so a ``while`` does not swallow its body."""
    clipped = []
    for start, dur, name in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            clipped.append((s, e, name))
    clipped.sort(key=lambda t: (t[0], -t[1]))
    totals: dict = {}
    stack = []  # [end, name, self]
    def close(item):
        totals[item[1]] = totals.get(item[1], 0.0) + max(item[2], 0.0)
    for s, e, name in clipped:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close(stack.pop())
    return totals


def idle_gaps(merged, lo, hi, boundaries=()) -> dict:
    """Idle seconds inside ``[lo, hi]`` by what surrounds them: a gap that
    holds the start of a program is the host's (the run loop had not yet
    dispatched the next round: feeder pop, dispatch, the ``on_round`` hook);
    any other lies inside a program, after the op named."""
    out: dict = {}
    edges = [lo] + [x for s, e, _ in merged for x in (s, e)] + [hi]
    names = ["window start"] + [n for _, _, n in merged]
    boundaries = sorted(boundaries)
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        at = bisect.bisect_left(boundaries, s)
        if at < len(boundaries) and boundaries[at] <= e:
            label = "between round programs: host run loop"
        elif boundaries:
            label = f"inside a program, after {kind(names[i // 2])}"
        else:
            label = f"after {kind(names[i // 2])}"
        out[label] = out.get(label, 0.0) + (e - s)
    return out


def module_bracket(modules):
    """``(lo, hi, rounds, starts)`` from one plane's whole-program events:
    second start to last start. The round program is the name with the most
    device time (nothing else should run, but a stray program must not shift
    the bracket)."""
    by_name: dict = {}
    for start, dur, name in modules:
        by_name.setdefault(name, []).append((start, dur))
    if not by_name:
        raise TraceUnreadable("no whole-program events in the trace")
    rounds = max(by_name.values(), key=lambda evs: sum(d for _, d in evs))
    starts = sorted(s for s, _ in rounds)
    if len(starts) < 3:
        raise TraceUnreadable(
            f"the trace holds {len(starts)} round programs; a bracket of "
            "whole rounds needs three")
    return starts[1], starts[-1], len(starts) - 2, starts


def kind(name: str) -> str:
    """``fusion.9199`` -> ``fusion``: a round program unrolls its steps and
    layers into tens of thousands of instructions, each a percent of the time
    at most, so the breakdown adds up the instructions of one stem. A Pallas
    kernel's stem is the flax module that called it (``attn``)."""
    return re.sub(r"[.\d]+$", "", name) or name


def _top(d: dict, n: int = 10) -> list:
    return [[k, v / 1e9] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def _by_kind(d: dict) -> dict:
    out: dict = {}
    for name, ns in d.items():
        out[kind(name)] = out.get(kind(name), 0.0) + ns
    return out


def reduce_planes(planes, annotation=None, rounds_in_annotation=None) -> dict:
    """Reduce ``planes`` (each ``{"ops": [...], "modules": [...]}``, device 0
    first) to the numbers the readers and the result line take.

    ``busy_s`` and ``window_s`` are means over the planes, each plane within
    its own bracket. ``ops0``, ``async0``, ``lo`` and ``hi`` are device 0's
    events and bracket, for readers of single ops. The breakdown is device 0's
    too: four chips run the same program."""
    if not planes:
        raise TraceUnreadable("no device plane in the trace")
    per_plane = []
    for plane in planes:
        if plane.get("modules"):
            lo, hi, rounds, starts = module_bracket(plane["modules"])
        elif annotation is not None and rounds_in_annotation:
            lo, hi = annotation
            rounds, starts = rounds_in_annotation, ()
        else:
            raise TraceUnreadable("neither program events nor the bracket "
                                  "annotation were found in the trace")
        per_plane.append({"lo": lo, "hi": hi, "rounds": rounds,
                          "starts": starts,
                          "busy_ns": busy_ns(plane["ops"], lo, hi)})
    first, ops0 = per_plane[0], planes[0]["ops"]
    lo, hi = first["lo"], first["hi"]
    merged0 = merge(ops0, lo, hi)
    n = len(per_plane)
    return {
        "busy_s": sum(p["busy_ns"] for p in per_plane) / n / 1e9,
        "window_s": sum(p["hi"] - p["lo"] for p in per_plane) / n / 1e9,
        "rounds": first["rounds"],
        "planes": n,
        "busy_s_per_plane": [p["busy_ns"] / 1e9 for p in per_plane],
        "ops0": ops0, "async0": planes[0].get("async", []),
        "lo": lo, "hi": hi,
        "longest_gaps_s": sorted(
            ((b[0] - a[1]) / 1e9 for a, b in zip(merged0, merged0[1:])),
            reverse=True)[:3],
        "breakdown": {
            "device_ops": _top(_by_kind(self_ns_by_name(ops0, lo, hi))),
            "idle_gaps": _top(idle_gaps(merged0, lo, hi, first["starts"])),
        },
    }


# ---------------------------------------------------------------------------
# the profiler's file
# ---------------------------------------------------------------------------

def instruction_names(hlo: str, opcode: str = r"[\w\-]+") -> set:
    """Names of the instructions in a compiled program's text whose opcode
    matches ``opcode`` (with ``-start``/``-done`` for an asynchronous pair);
    by default, of all of them."""
    pattern = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?[\s)](?:" + opcode
                         + r")(?:-start|-done)?\(", re.M)
    return set(pattern.findall(hlo))


def instruction_name(event_name: str) -> str:
    """libtpu 0.0.34 names an ``XLA Ops`` event by the whole HLO instruction,
    ``%fusion.9 = (f32[...]) fusion(...), kind=...`` (kilobytes each); the CPU
    backend by the instruction's name alone. Either way: the name alone."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str, platform: str, log=print):
    """``(planes, annotation)`` from the ``.xplane.pb`` under ``trace_dir``,
    by ``SELECTORS[platform]``. Prints what the trace holds, line by line, on
    the way: the next reader of a surprising number starts there."""
    import jax

    sel = SELECTORS[platform]
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise TraceUnreadable(f"expected one .xplane.pb under {trace_dir}, "
                              f"found {found}")
    data = jax.profiler.ProfileData.from_file(found[0])
    planes, annotation = {}, None
    bracket_line = sel["bracket"].get("line")
    bracket_name = sel["bracket"].get("annotation")
    for plane in data.planes:
        m = re.search(sel["plane"], plane.name)
        for line in plane.lines:
            events = list(line.events)
            log(f"[trace] plane {plane.name!r} line {line.name!r}: "
                f"{len(events)} events, first "
                f"{[instruction_name(e.name)[:40] for e in events[:3]]}")
            if bracket_name and annotation is None:
                for ev in events:
                    if ev.name == bracket_name:
                        annotation = (ev.start_ns, ev.start_ns + ev.duration_ns)
            if not m:
                continue
            is_ops = re.search(sel["ops_line"], line.name)
            is_modules = bracket_line and re.search(bracket_line, line.name)
            is_async = "async_line" in sel and re.search(sel["async_line"],
                                                         line.name)
            if not (is_ops or is_modules or is_async):
                continue
            where = "ops" if is_ops else "modules" if is_modules else "async"
            for ev in events:
                key = int(m.group(1)) if m.groups() else 0
                if is_ops and "op_stat" in sel:
                    stats = dict(ev.stats)
                    if sel["op_stat"] not in stats:
                        continue
                    key = int(stats.get(sel["device_stat"], 0))
                dest = planes.setdefault(
                    key, {"ops": [], "modules": [], "async": []})
                dest[where].append(
                    (ev.start_ns, ev.duration_ns, instruction_name(ev.name)))
    ordered = [planes[k] for k in sorted(planes)]
    log(f"[trace] {len(ordered)} device plane(s): " + ", ".join(
        f"{len(p['ops'])} ops / {len(p['modules'])} programs" for p in ordered))
    return ordered, annotation

"""Device time per round: the trace's busy seconds (one line of each device
plane, merged and clipped, mean over planes) over the whole rounds inside the
trace bracket."""

from __future__ import annotations


def read(run):
    t = run.trace
    if not t or not t["rounds"]:
        return None
    return t["busy_s"] / t["rounds"] * 1e3

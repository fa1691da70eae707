"""Mean duration of one of the program's telemetry spans over the measured
segment: ``telemetry.get().mark()`` at the segment's first tick,
``.delta(mark)`` at its last (count and total subtract exactly)."""

from __future__ import annotations


def read(run, span: str):
    got = run.spans.get(span)
    if not got or not got["count"]:
        return None
    return got["total"] / got["count"] * 1e3

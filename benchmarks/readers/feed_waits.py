"""Input stall per round: how long the run loop sat blocked on the
``RoundFeeder`` for each of the segment's rounds. ``engine.feed_waits`` holds
one entry per popped round (the last 4,096), and the run ends with the
segment, so the segment's rounds are the list's last entries."""

from __future__ import annotations


def read(run):
    n = run.window.segment_rounds
    waits = run.feed_waits[-n:] if run.feed_waits else []
    if len(waits) < n:
        return None
    return sum(waits) / n * 1e3

"""Metrics of the harness's own host clock (``harness/window.py``)."""

from __future__ import annotations

import statistics


def read(run, what: str, percentile: int = 95):
    w = run.window
    if what == "setup_s":          # process start -> first fetched loss
        return w.ticks[0] - w.t_start
    if what == "build_s":          # process start -> train() called
        return w.t_train - w.t_start
    if what == "first_round_s":    # train() called -> first fetched loss
        return w.ticks[0] - w.t_train
    if what == "units_per_s_chip":  # all the segment's rounds over all its time
        return (w.segment_rounds * run.units_per_round / w.segment_s
                / run.chips)
    if what == "round_percentile_ms":
        times = w.round_times_s()
        # Only a cell whose segment holds some hundreds of rounds declares
        # this: a p95 of 25 rounds is a maximum by another name.
        if len(times) < 2:
            return None
        return statistics.quantiles(times, n=100)[percentile - 1] * 1e3
    raise ValueError(f"clock reader knows no {what!r}")

"""Model FLOP/s utilization: this run's own rate times the operations the
forward and backward passes need per unit (from the configuration's shapes,
``families/<family>.py``; recomputed operations do not count) over the chip's
published bf16 peak. A multiple of throughput: it says nothing of idle time."""

from __future__ import annotations

from benchmarks.readers import clock


def read(run):
    rate = clock.read(run, "units_per_s_chip")
    return rate * run.flops_per_unit / run.peak["bf16_flops_per_s"] * 100.0

"""Unified telemetry: spans, counters, gauges, exporters, and the run report.

The single instrumentation layer every execution path reports through
(ISSUE 1): engine run loops (dispatch/retire latency per block mode), the
data plane (queue depth, input-stall time), inference (chunk latency,
pending rows, per-shard skew), and the disciplines' staleness schedule.

Usage — the ambient registry (per-process aggregation)::

    from distkeras_tpu import telemetry

    with telemetry.span("dispatch"):
        ...                                   # nested spans -> "a/b" paths
    telemetry.counter("rounds").add(1)
    telemetry.gauge("queue_depth").set(3)

    telemetry.write_jsonl(telemetry.get(), "run.jsonl")   # append-only JSONL
    print(telemetry.prometheus_text(telemetry.get()))     # Prometheus dump

Disable with ``DKTPU_TELEMETRY=0`` (all calls become no-ops). Render a
report with ``python -m distkeras_tpu.telemetry report run.jsonl``.
"""

from __future__ import annotations

from distkeras_tpu.telemetry.core import (
    Counter,
    Gauge,
    Histogram,
    Telemetry,
    current_labels,
    enabled,
    get,
    label_suffix,
    reset,
    sanitize_label,
    scoped_labels,
)
from distkeras_tpu.telemetry.exporters import (
    parse_prometheus,
    prometheus_text,
    read_jsonl,
    write_jsonl,
)
from distkeras_tpu.telemetry.training import (
    DisciplineMonitor,
    dynsgd_scales,
    flag_stragglers,
    staleness_schedule,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "Telemetry",
    "enabled", "get", "reset",
    "span", "counter", "gauge", "histogram", "event",
    "scoped_labels", "current_labels", "label_suffix", "sanitize_label",
    "write_jsonl", "read_jsonl", "prometheus_text", "parse_prometheus",
    "DisciplineMonitor", "flag_stragglers", "staleness_schedule",
    "dynsgd_scales",
]


# -- module-level shorthands routing to the ambient registry ---------------
def span(name: str, id=None):
    return get().span(name, id)


def counter(name: str):
    return get().counter(name)


def gauge(name: str):
    return get().gauge(name)


def histogram(name: str):
    return get().histogram(name)


def event(kind: str, fields=None):
    return get().event(kind, fields)

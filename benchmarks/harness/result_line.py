"""Build the run's last line from what the cell declares, and refuse to print
one that the driver would refuse.

PR 22 lost its number to a traced line whose ``device`` block broke the
contract in one cell; the check therefore runs in the process that prints,
before it prints. A value its source could not produce fails the run with a
message. It is never replaced by ``null``, 0 or a clamp.
"""

from __future__ import annotations

import json
import math

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_DEVICE_KEYS = ("busy_s", "window_s")


class LineRefused(ValueError):
    """The line would not meet the contract; the message says where."""


def declared_metrics(manifest: dict, cell: str, traced: bool) -> dict:
    """``{name: unit}`` of the metrics ``cell`` reports in this mode: its
    per-layer metrics in a traced run, its end-to-end metrics otherwise. A
    metric without a ``workloads`` key belongs to every cell."""
    group = manifest["per_layer" if traced else "end_to_end"]
    return {m["name"]: m["unit"] for m in group
            if cell in m.get("workloads", [cell])}


def _finite_number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def validate(line: dict, declared: dict, chips: int, traced: bool,
             platform: str = "tpu") -> None:
    """Raise :class:`LineRefused` unless ``line`` is what the contract asks
    of a cell that declares ``declared`` (``{name: unit}``) on ``chips`` chips."""
    allowed = set(TOP_KEYS) | ({"breakdown"} if traced else set())
    if set(line) - allowed or set(TOP_KEYS) - set(line):
        raise LineRefused(f"keys {sorted(line)}; the contract names "
                          f"{sorted(allowed)}")
    if not isinstance(line["correct"], bool):
        raise LineRefused(f"correct is {line['correct']!r}, not a boolean")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or isinstance(line[key], bool) \
                or line[key] < 0:
            raise LineRefused(f"{key} is {line[key]!r}, not a count")
    if line["failed"] > line["attempted"]:
        raise LineRefused("more rounds failed than were attempted")
    metrics = line["metrics"]
    missing, extra = set(declared) - set(metrics), set(metrics) - set(declared)
    if missing or extra:
        raise LineRefused(f"metrics missing {sorted(missing)}, not declared "
                          f"for this cell and mode {sorted(extra)}")
    for name, unit in declared.items():
        m = metrics[name]
        if set(m) != {"value", "unit"}:
            raise LineRefused(f"{name}: keys {sorted(m)}")
        if not _finite_number(m["value"]):
            raise LineRefused(f"{name}: value {m['value']!r} is not a "
                              "finite number")
        if m["unit"] != unit:
            raise LineRefused(f"{name}: unit {m['unit']!r}, declared {unit!r}")
    dev = line["device"]
    want = set(DEVICE_KEYS) | (set(TRACE_DEVICE_KEYS) if traced else set())
    if set(dev) != want:
        raise LineRefused(f"device keys {sorted(dev)}, wanted {sorted(want)}")
    if dev["platform"] != platform:
        raise LineRefused(f"device.platform {dev['platform']!r}, not "
                          f"{platform!r}")
    if not isinstance(dev["kind"], str) or not dev["kind"]:
        raise LineRefused(f"device.kind {dev['kind']!r}")
    if dev["count"] != chips:
        raise LineRefused(f"device.count {dev['count']!r}; the cell asks "
                          f"for {chips}")
    if not _finite_number(dev["memory_peak_bytes"]) \
            or dev["memory_peak_bytes"] <= 0:
        raise LineRefused(f"device.memory_peak_bytes "
                          f"{dev['memory_peak_bytes']!r}")
    if traced:
        busy, window = dev["busy_s"], dev["window_s"]
        if not (_finite_number(busy) and _finite_number(window)
                and 0 < busy <= window):
            raise LineRefused(f"device.busy_s {busy!r} and window_s "
                              f"{window!r}: need 0 < busy_s <= window_s")
        for key, rows in line.get("breakdown", {}).items():
            if key not in ("device_ops", "idle_gaps") or len(rows) > 10 \
                    or not all(len(r) == 2 and isinstance(r[0], str)
                               and _finite_number(r[1]) for r in rows):
                raise LineRefused(f"breakdown.{key}: {rows!r}")


def build(*, correct: bool, attempted: int, failed: int, values: dict,
          declared: dict, device: dict, breakdown: dict | None = None) -> dict:
    """The line as a dict: ``values`` (``{name: number}``) under the declared
    units. Validation is the caller's next step, not skipped here."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {name: {"value": values[name], "unit": unit}
                        for name, unit in declared.items() if name in values},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return line


def dumps(line: dict) -> str:
    return json.dumps(line, allow_nan=False)

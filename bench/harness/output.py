"""The result of one run and the JSON line that reports it."""
from __future__ import annotations

import dataclasses
import math
import sys

from .cell import quantity


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with the plain reference, and its limit: the run
    is correct only if ``value <= limit`` for every check."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    end_to_end: dict                 # metric name -> value (trace 0)
    checks: list                     # [Check]
    memory_peak_bytes: int
    observations: dict = dataclasses.field(default_factory=dict)  # trace 1
    busy_s: float | None = None
    window_s: float | None = None
    breakdown: dict | None = None


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks, default=0))


def result_line(cell, result: Result, devices, trace: bool, readers) -> dict:
    """The last stdout line: correct, attempted, failed, metrics, device,
    breakdown (trace 1), and the compared numbers beside their limits."""
    correct = result.failed == 0 and all(c.ok for c in result.checks)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = readers[m["name"]](result.observations)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = result.end_to_end[quantity(m["name"], result.end_to_end)]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": result.memory_peak_bytes}
    if trace:
        device["busy_s"] = result.busy_s
        device["window_s"] = result.window_s
    line = {"correct": correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": device}
    if trace and result.breakdown is not None:
        line["breakdown"] = result.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in result.checks}
    return line


def print_checks(result: Result, file=sys.stderr) -> None:
    """The compared numbers, each beside its limit, as the last lines of
    standard error."""
    for c in result.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=file, flush=True)

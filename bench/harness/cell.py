"""Find a cell's files by the names in BENCHMARK.json.

A cell is one entry of ``workloads``. Its configuration file, its traffic
file (``bench/traffic/<traffic>.json``), the driver that traffic's ``kind``
names (``bench/drivers/<kind>.py``) and each per-layer metric's reader
(``bench/metrics/<name>.py``) are found by name, so a later change adds a
cell, a mix or a metric by adding files and entries, not by editing these.
A metric split by cell, so that each part has a bound of its own
(``fit_s.millionsongs``), is the quantity it splits (``fit_s``): it is read
by that quantity's reader unless it has a file of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file; ``kind`` names the driver
    end_to_end: tuple     # BENCHMARK.json end_to_end entries this cell reports
    per_layer: tuple      # BENCHMARK.json per_layer entries this cell reports


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = _load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {spec_path.name}; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(ROOT / configs[w["config"]]["file"])
    traffic = _load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(m for m in spec["end_to_end"] if _reports(m, workload))
    layer = tuple(m for m in spec["per_layer"] if _reports(m, workload))
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)


def load_module(path: Path):
    """Import one file by path (names may hold dots, e.g. ``a.fit.py``)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def driver(cell: Cell):
    return load_module(BENCH / "drivers" / f"{cell.traffic['kind']}.py")


def quantity(name: str, known) -> str:
    """``name`` where ``known`` holds it, else ``name`` without its last
    dotted part: the quantity a metric split by cell reports."""
    return name if name in known else name.rsplit(".", 1)[0]


def metric_reader(name: str):
    known = {p.name[:-len(".py")] for p in (BENCH / "metrics").glob("*.py")}
    return load_module(BENCH / "metrics" / f"{quantity(name, known)}.py")


def program_path() -> None:
    """Put the program under test (``<checkout>/src``) on ``sys.path``."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)

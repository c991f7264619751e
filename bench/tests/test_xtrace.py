"""Trace reduction: hand-built planes, and a small trace recorded on the
chip (tests/data/small.xplane.pb, by tests/record_trace.py)."""
import json
import types
from pathlib import Path

import numpy as np
import pytest

from harness import xtrace

DATA = Path(__file__).resolve().parent / "data"


def _plane(name, lines):
    ev = lambda n, s, d: types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs]) for ln, evs in lines])


def _fake():
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [("%dot.1 = f32[8] dot(..)", 100, 50), ("%exp = f32[8] exp(..)", 120, 60),
                     ("%while.3 = (f32[8]) while(..)", 400, 100),
                     ("%dot.1 = f32[8] dot(..)", 400, 100)]),
        ("XLA Modules", [("jit_f(123)", 100, 400)]),
    ])
    host = _plane("/host:CPU", [("python", [("bench.fit", 0, 1000),
                                            ("PjitFunction(f)", 90, 20),
                                            ("lower", 190, 200)])])
    other = _plane("/device:TPU:0 SparseCore", [("XLA Ops", [("x", 0, 1000)])])
    return xtrace.Trace([dev, host, other])


def test_merge_and_overlap():
    s, e = xtrace.merge(np.array([5., 0., 1., 10.]), np.array([7., 2., 3., 11.]))
    assert s.tolist() == [0., 5., 10.] and e.tolist() == [3., 7., 11.]
    assert xtrace.overlap(s, e, 1., 6.) == 3.0


def test_busy_ops_and_gaps_by_hand():
    tr = _fake()
    assert list(tr.devices) == ["/device:TPU:0"]          # SparseCore plane aside
    win = tr.spans("bench.fit")
    assert win == [(0, 1000)]
    assert tr.busy_ns(win) == 80 + 100                   # [100,180) and [400,500)
    ops = dict(tr.top_ops(win))              # the while loop holds its dot
    assert ops == pytest.approx({"jit_f/%dot.1": 150e-9, "jit_f/%exp": 60e-9})
    gaps = tr.idle_gaps(win)
    assert [g[1] for g in gaps] == pytest.approx([500e-9, 220e-9, 100e-9])
    assert gaps[1][0] == "lower"                         # [180, 400) is host lowering
    assert gaps[2][0] == "PjitFunction(f)"               # [0, 100): the dispatch


@pytest.mark.skipif(not (DATA / "small.xplane.pb").exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    facts = json.loads((DATA / "small.json").read_text())
    tr = xtrace.Trace.from_file(str(DATA / "small.xplane.pb"))
    assert sorted(tr.devices) == facts["planes"] and tr.devices
    (lo, hi), = tr.spans("bench.test")
    (slo, shi), = tr.spans("host.sleep")
    d = tr.devices["/device:TPU:0"]
    # host and device clocks of one trace agree to a few milliseconds
    assert abs(d["starts"].min() - lo) < 5e6
    # the sleep leaves the device idle for at least its length
    assert tr.busy_ns([(slo, shi)]) < 0.01 * (shi - slo)
    gaps = tr.idle_gaps([(lo, hi)])
    assert gaps[0][0] == "host.sleep" and gaps[0][1] >= facts["sleep_s"]
    # 12 bf16 2048^3 products can run no faster than the chip's peak, and
    # each took most of the time of its program
    n = facts["n"]
    least = facts["calls"] * facts["matmuls_per_call"] * 2 * n ** 3 / 197e12
    busy = tr.busy_ns([(-np.inf, np.inf)]) * 1e-9
    assert least <= busy < 1.5 * least
    top = tr.top_ops([(-np.inf, np.inf)])
    assert all(name.startswith("jit__lambda/%fusion") for name, _ in top[:2])

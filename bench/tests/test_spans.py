"""Program spans and launch attribution (harness.spans): hand-built planes,
and a chip trace with program spans around launched programs
(tests/data/spans.xplane.pb, by tests/record_spans.py)."""
import json
import types
from pathlib import Path

import numpy as np
import pytest

from harness import spans, xtrace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start * MS, duration_ns=dur * MS,
                                 stats=list(stats))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[_ev(*e) for e in evs]) for ln, evs in lines])


def _fake():
    """A fit window [0, 1000) ms. ``falkon.gram`` launches one program and
    ends before it does; ``falkon.cg`` launches two, the second ending after
    the span; ``falkon.cond`` launches one that ends inside it. One module
    run was launched before the trace began."""
    runs = [("jit_old(1)", -100, 50), ("jit_gram(2)", 40, 260), ("jit_scan(3)", 300, 80),
            ("jit_scan(3)", 380, 120), ("jit_scan(3)", 520, 40)]
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [("%fusion.1 = f32[8] fusion(..)", s, d) for _, s, d in runs]),
        ("XLA Modules", runs)])
    host = _plane("/host:CPU", [
        ("python", [("bench.fit", 0, 1000), ("falkon.fit", 10, 980, [("n", 8), ("t", 5)]),
                    ("falkon.gram", 20, 80), ("falkon.cg", 120, 280, [("sweeps", 5)]),
                    ("falkon.cond", 500, 300, [("sweeps", 26)])]),
        ("main", [(spans.LAUNCH, t, 1) for t in (30, 130, 140, 510)])])
    return spans.SpanTrace([dev, host])


def test_launches_match_runs_in_order_and_skip_earlier_runs():
    tr = _fake()
    assert tr.launches.tolist() == [30 * MS, 130 * MS, 140 * MS, 510 * MS]
    assert tr.run_of.tolist() == [1, 2, 3, 4]          # run 0 predates the trace
    by = {s.name: s for s in tr.program_spans()}
    assert tr.parent(by["falkon.cg"]).name == "falkon.fit"
    assert tr.parent(by["falkon.fit"]) is None
    assert by["falkon.fit"].stats == {"n": 8, "t": 5}
    assert tr.launches_in(by["falkon.cg"]).tolist() == [1, 2]
    assert tr.device_end(by["falkon.gram"]) == 300 * MS


def test_stage_seconds_run_to_the_later_of_host_and_device_end():
    tr = _fake()
    win = tr.spans("bench.fit")
    assert tr.stage_seconds("falkon.gram", win) == pytest.approx(0.280)   # device end 300
    assert tr.stage_seconds("falkon.cg", win) == pytest.approx(0.380)     # device end 500
    assert tr.stage_seconds("falkon.cond", win) == pytest.approx(0.300)   # host end 800
    assert tr.stage_seconds("falkon.select", win) is None
    assert tr.count("sweeps", win) == 31 and tr.count("bytes", win) is None
    assert tr.count("sweeps", [(600 * MS, 700 * MS)]) is None


def test_window_split_between_spans():
    tr = _fake()
    busy, idle = tr.split_by_span(tr.spans("bench.fit"))
    # each module run goes to the span that launched it; the run launched
    # before the trace lies outside the window
    assert busy == pytest.approx({"falkon.gram": 0.260, "falkon.cg": 0.200, "falkon.cond": 0.040})
    # idle [0, 40), [500, 520), [560, 1000) goes to the innermost span open
    assert idle == pytest.approx({spans.UNATTRIBUTED: 0.020, "falkon.fit": 0.200,
                                  "falkon.gram": 0.020, "falkon.cond": 0.260})
    assert sum(busy.values()) + sum(idle.values()) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["small", "spans"])
def test_trace_methods_unchanged_on_recorded_traces(name):
    path = DATA / f"{name}.xplane.pb"
    if not path.exists():
        pytest.skip("no recorded trace")
    base, ext = xtrace.Trace.from_file(str(path)), spans.SpanTrace.from_file(str(path))
    win = [(-np.inf, np.inf)]
    assert ext.host == base.host and list(ext.devices) == list(base.devices)
    assert ext.busy_ns(win) == base.busy_ns(win)
    assert ext.top_ops(win) == base.top_ops(win)
    assert ext.idle_gaps(win) == base.idle_gaps(win)


@pytest.mark.skipif(not (DATA / "spans.xplane.pb").exists(), reason="no recorded trace")
def test_recorded_launches_and_stage_seconds():
    facts = json.loads((DATA / "spans.json").read_text())
    tr = spans.SpanTrace.from_file(str(DATA / "spans.xplane.pb"))
    test = tr.spans("bench.test")
    (_, wait_end), = [tuple(w) for w in facts["wait"]]
    by = {s.name: s for s in tr.program_spans(windows=test)}
    # every program launched inside a span is found there, and ran
    for name, n in facts["launches"].items():
        idx = tr.launches_in(by[name])
        assert len(idx) == n and (tr.run_of[idx] >= 0).all()
        assert all(tr.innermost(t).name == name for t in tr.launches[idx])
    assert tr.count("sweeps", test) == sum(facts["launches"].values())
    assert tr.parent(by["falkon.test.async"]).name == "falkon.test"
    # the async span ends long before its programs do; its stage runs to
    # their device end, which the host's wait saw within a few ms
    a = by["falkon.test.async"]
    stage = tr.stage_ns(a)
    assert stage > 3 * (a.end - a.start)
    assert wait_end - 5 * MS < a.start + stage <= wait_end + 1 * MS
    # spans that waited for their programs, or launched none, end on the host
    for name in ("falkon.test.sync", "falkon.test.sleep"):
        s = by[name]
        assert tr.stage_ns(s) == s.end - s.start
    assert tr.stage_ns(by["falkon.test.sleep"]) >= facts["sleep_s"] * 1e9
    # the device time charged to each span holds its products at no more
    # than the chip's peak
    per_call = facts["matmuls_per_call"] * 2 * facts["n"] ** 3 / 197e12
    busy, idle = tr.split_by_span(test)
    for name, n in facts["launches"].items():
        assert busy.get(name, 0.0) >= n * per_call
    # the sleep left the device idle, and its idle time is the sleep's
    assert idle["falkon.test.sleep"] >= facts["sleep_s"]
    (lo, hi), = test
    assert sum(busy.values()) + sum(idle.values()) == pytest.approx((hi - lo) * 1e-9)

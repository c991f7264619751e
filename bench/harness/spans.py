"""The program's own spans in a trace, and the device time each launched.

The program marks its stages with ``jax.profiler.TraceAnnotation`` spans
named ``falkon.*``, with counts as the events' stats (``sweeps``, ``bytes``,
the factor loop's ``FactorStats``). ``SpanTrace`` is ``xtrace.Trace`` with
more kept from the same ``.xplane.pb``: those spans and their stats, the
module (program) runs of the first device's ``XLA Modules`` line, and the
host launches (``PJRT_LoadedExecutable_Execute``). Module runs are matched
to launches in order, the device running its programs in the order they
were launched; a run that starts more than
``CLOCK_SLACK_NS`` before the next unmatched launch was launched before the
trace began and is skipped. Each launch belongs to the innermost program
span open at its host start.

A span's stage seconds run from its host start to the later of its host end
and the device end of the last program launched inside it. Where dispatch
runs ahead of the device, the stage seconds of consecutive spans overlap;
``split_by_span`` instead splits a window's seconds between spans, so that
they sum to the window. Every method of ``Trace`` returns on a
``SpanTrace`` what it returns on a ``Trace``.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from . import xtrace

PROGRAM_PREFIX = "falkon."
LAUNCH = "PJRT_LoadedExecutable_Execute"
UNATTRIBUTED = "host:unattributed"
# the device clock of a trace reads up to a few ms early against the host's
CLOCK_SLACK_NS = 5e6


class Span(NamedTuple):
    start: float
    end: float
    name: str
    stats: dict
    line: str        # the host thread's line: spans nest within one thread


def _inside(t: float, windows) -> bool:
    return any(lo <= t < hi for lo, hi in windows)


class SpanTrace(xtrace.Trace):
    """``Trace`` plus program spans, module runs and launch attribution."""

    def __init__(self, planes):
        planes = list(planes)
        super().__init__(planes)
        self.program: list[Span] = []
        launches = []
        first = next(iter(self.devices), None)
        # the first device's module runs, (start, end, program), in order
        self.runs: list[tuple[float, float, str]] = []
        for plane in planes:
            if plane.name == xtrace.HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PROGRAM_PREFIX):
                            self.program.append(Span(e.start_ns, e.start_ns + e.duration_ns,
                                                     e.name, dict(e.stats), line.name))
                        elif e.name == LAUNCH:
                            launches.append(e.start_ns)
            elif plane.name == first:
                self.runs = sorted(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name.split("(", 1)[0])
                    for line in plane.lines if line.name == xtrace.MODULES_LINE
                    for e in line.events)
        self.program.sort(key=lambda s: (s.start, -s.end))
        self.launches = np.sort(np.array(launches, float))
        # the index in ``runs`` of each launch's module run (-1 where none)
        self.run_of = np.full(len(self.launches), -1)
        j = 0
        for i, t in enumerate(self.launches):
            while j < len(self.runs) and self.runs[j][0] < t - CLOCK_SLACK_NS:
                j += 1
            if j < len(self.runs):
                self.run_of[i] = j
                j += 1

    def program_spans(self, name: str | None = None, windows=None) -> list[Span]:
        """Program spans in start order: all, or those called ``name``; with
        ``windows``, those that start inside them."""
        return [s for s in self.program if (name is None or s.name == name)
                and (windows is None or _inside(s.start, windows))]

    def innermost(self, t: float) -> Span | None:
        """The shortest program span open at host time ``t``."""
        open_ = [s for s in self.program if s.start <= t < s.end]
        return min(open_, key=lambda s: s.end - s.start) if open_ else None

    def parent(self, span: Span) -> Span | None:
        """The innermost program span that holds ``span`` on its thread."""
        holders = [s for s in self.program if s is not span and s.line == span.line
                   and s.start <= span.start and span.end <= s.end
                   and (s.start, -s.end) < (span.start, -span.end)]
        return min(holders, key=lambda s: s.end - s.start) if holders else None

    def launches_in(self, span: Span) -> np.ndarray:
        """Indices of the launches made inside ``span``."""
        return np.flatnonzero((self.launches >= span.start) & (self.launches < span.end))

    def device_end(self, span: Span) -> float | None:
        """Device end of the last module run launched inside ``span`` (None
        where it launched none that ran on the first device)."""
        ends = [self.runs[r][1] for r in self.run_of[self.launches_in(span)] if r >= 0]
        return max(ends) if ends else None

    def stage_ns(self, span: Span) -> float:
        """Host start to the later of host end and ``device_end``."""
        dev = self.device_end(span)
        return max(span.end, dev if dev is not None else span.end) - span.start

    def stage_seconds(self, name: str, windows) -> float | None:
        """Stage seconds of the spans called ``name`` inside ``windows``,
        summed; None where there is none."""
        spans = self.program_spans(name, windows)
        return sum(self.stage_ns(s) for s in spans) * 1e-9 if spans else None

    def count(self, key: str, windows) -> int | None:
        """The stat ``key`` summed over program spans inside ``windows``;
        None where no span carries it."""
        vals = [s.stats[key] for s in self.program_spans(windows=windows) if key in s.stats]
        return int(sum(vals)) if vals else None

    def split_by_span(self, windows) -> tuple[dict[str, float], dict[str, float]]:
        """The seconds of ``windows`` split between program spans, as (busy,
        idle): while the first device runs a module, the instant goes to the
        innermost span open at that module's launch; while it runs none, to
        the innermost span open on the host then. ``UNATTRIBUTED`` takes
        what no span holds. Together they sum to the windows' length."""
        busy: dict[str, float] = {}
        idle: dict[str, float] = {}

        def charge(acc, span, ns):
            name = span.name if span else UNATTRIBUTED
            acc[name] = acc.get(name, 0.0) + ns * 1e-9

        launch_of = {int(r): i for i, r in enumerate(self.run_of) if r >= 0}
        runs = self.runs
        rs, re_ = xtrace.merge(np.array([r[0] for r in runs], float),
                               np.array([r[1] for r in runs], float))
        edges = sorted({s.start for s in self.program} | {s.end for s in self.program})
        for lo, hi in windows:
            for j, (s, e, _) in enumerate(runs):
                if min(e, hi) > max(s, lo):
                    i = launch_of.get(j)
                    charge(busy, self.innermost(self.launches[i]) if i is not None else None,
                           min(e, hi) - max(s, lo))
            starts = np.maximum(np.concatenate([[lo], re_]), lo)
            ends = np.minimum(np.concatenate([rs, [hi]]), hi)
            for a, b in zip(starts, ends):
                cuts = [a] + [t for t in edges if a < t < b] + [b]
                for x, y in zip(cuts, cuts[1:]):
                    if y > x:
                        charge(idle, self.innermost((x + y) / 2), y - x)
        return busy, idle

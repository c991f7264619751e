"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), averaged
over the devices traced. Windows are the host spans the benchmark itself
opened with ``jax.profiler.TraceAnnotation`` (``bench.*``), found by name on
the host plane, so both sides share the trace's one clock.
"""
from __future__ import annotations

import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# ops that only hold other ops: their time is counted in what they hold
CONTAINERS = {"while", "conditional", "call"}


def op_name(text: str) -> str:
    """``%fusion.5 = f32[..] fusion(..)`` -> ``%fusion.5``."""
    return text.split(" = ", 1)[0]


def _kind(name: str) -> str:
    return name.lstrip("%").rsplit(".", 1)[0]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def merge(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Union of [start, end) intervals as sorted, disjoint (starts, ends)."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(s) - 1]])
    return s[first], e[last]


def overlap(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float) -> float:
    """Length of the part of disjoint intervals that lies in [lo, hi)."""
    return float(np.sum(np.clip(np.minimum(ends, hi) - np.maximum(starts, lo), 0, None)))


class Trace:
    """Device operations, their union and the host events of one trace."""

    def __init__(self, planes):
        self.devices: dict[str, dict] = {}
        self.host: list[tuple[float, float, str]] = []
        for plane in planes:
            name = plane.name
            if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):].isdigit():
                lines = {line.name: list(line.events) for line in plane.lines}
                evs = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                       for e in lines.get(OPS_LINE, [])]
                mods = sorted((e.start_ns, e.name.split("(", 1)[0])
                              for e in lines.get(MODULES_LINE, []))
                s = np.array([e[0] for e in evs], float)
                t = np.array([e[1] for e in evs], float)
                # each op named within the module (program) that ran it
                at = np.searchsorted(np.array([m[0] for m in mods], float), s,
                                     side="right") - 1
                names = [f"{mods[j][1] if j >= 0 else '?'}/{e[2]}"
                         for j, e in zip(at, evs)]
                bs, be = merge(s, t)
                self.devices[name] = dict(starts=s, ends=t, names=names,
                                          busy_starts=bs, busy_ends=be)
            elif name == HOST_PLANE:
                self.host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                                 for line in plane.lines for e in line.events)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        import jax
        return cls(jax.profiler.ProfileData.from_file(path).planes)

    def spans(self, name: str) -> list[tuple[float, float]]:
        """Host intervals of every event called ``name``, in order."""
        return sorted((s, e) for s, e, n in self.host if n == name)

    def busy_ns(self, windows) -> float:
        """Device-busy nanoseconds inside ``windows``, averaged over devices
        (0 when the trace holds no device)."""
        if not self.devices:
            return 0.0
        total = [sum(overlap(d["busy_starts"], d["busy_ends"], lo, hi)
                     for lo, hi in windows) for d in self.devices.values()]
        return float(np.mean(total))

    def top_ops(self, windows, k: int = 10) -> list[list]:
        """The k device operations (``program/op``) that took most time
        inside ``windows``, summed over their calls and averaged over
        devices, in seconds; loops and calls aside, whose time is that of
        the ops they hold."""
        acc: dict[str, float] = {}
        for d in self.devices.values():
            for lo, hi in windows:
                dur = np.clip(np.minimum(d["ends"], hi) - np.maximum(d["starts"], lo),
                              0, None)
                for i in np.flatnonzero(dur):
                    name = d["names"][i]
                    if _kind(name.rsplit("/", 1)[-1]) not in CONTAINERS:
                        acc[name] = acc.get(name, 0.0) + dur[i]
        n = max(len(self.devices), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n * 1e-9] for name, ns in top]

    def idle_gaps(self, windows, k: int = 10) -> list[list]:
        """The k longest device-idle gaps inside ``windows`` (first device),
        each named by the host event that overlaps it most, ``bench.*``
        spans aside; in seconds."""
        if not self.devices:
            return []
        d = next(iter(self.devices.values()))
        gaps = []
        for lo, hi in windows:
            bs, be = d["busy_starts"], d["busy_ends"]
            inside = (be > lo) & (bs < hi)
            edges_s = np.concatenate([[lo], np.minimum(be[inside], hi)])
            edges_e = np.concatenate([np.maximum(bs[inside], lo), [hi]])
            for a, b in zip(edges_s, edges_e):
                if b > a:
                    gaps.append((b - a, a, b))
        gaps.sort(reverse=True)
        host = [(s, e, n) for s, e, n in self.host if not n.startswith("bench.")]
        hs = np.array([h[0] for h in host], float)
        he = np.array([h[1] for h in host], float)
        out = []
        for length, a, b in gaps[:k]:
            ov = np.clip(np.minimum(he, b) - np.maximum(hs, a), 0, None)
            # most overlap; among ties the shortest (most specific) event
            name = "host:unattributed"
            if len(ov) and ov.max() > 0:
                cand = np.flatnonzero(ov >= ov.max())
                name = host[min(cand, key=lambda i: he[i] - hs[i])][2]
            out.append([name, length * 1e-9])
        return out

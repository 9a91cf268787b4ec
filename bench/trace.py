"""Reduce a JAX profiler trace to the numbers the benchmark reports.

From one ``.xplane.pb`` (``jax.profiler.ProfileData``) this computes, per
device: the union of the intervals in which an operation ran (busy), the
traced window, device time per program (XLA module) and per operation
(kernels, fusions), and the idle gaps between busy intervals,
each attributed to the benchmark's own host span (``TraceAnnotation``)
that overlaps it most. ``Summary.breakdown()`` is the ``breakdown`` of a
``--trace 1`` result line.

The reduction works on plain tuples (``Event``) so it can be checked on a
small recorded trace without a chip (bench/tests/test_trace.py).
"""

from __future__ import annotations

import dataclasses
import glob
import pathlib
from collections import defaultdict
from typing import NamedTuple


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Device:
    name: str
    ops: list          # Event per device operation
    modules: list      # Event per program execution


def load(path) -> tuple[list[Device], list[Event]]:
    """(devices, host spans) of the ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.is_dir():
        files = sorted(glob.glob(str(path / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = pathlib.Path(files[-1])
    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops += evs
                elif line.name == "XLA Modules":
                    modules += evs
            devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns, e.duration_ns)
                          for e in line.events if e.name in HOST_SPANS]
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return devices, sorted(spans, key=lambda e: e.start_ns)


# the benchmark's own host spans, by which idle gaps are attributed
HOST_SPANS = ("segment dispatch", "probe", "idle wait")


def union(events) -> list[tuple[float, float]]:
    """Merged [start, end) intervals of the events, in order."""
    out = []
    for e in sorted(events, key=lambda e: e.start_ns):
        s, t = e.start_ns, e.end_ns
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [tuple(x) for x in out]


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for s, t in intervals:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, t)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, t) for s, t in out if t > s]


def attribute(gap: tuple[float, float], spans: list[Event]) -> str:
    """The host span whose name covers most of the gap ("host" if none)."""
    cover = defaultdict(float)
    for e in spans:
        ov = min(gap[1], e.end_ns) - max(gap[0], e.start_ns)
        if ov > 0:
            cover[e.name] += ov
    return max(cover, key=cover.get) if cover else "host"


def short_name(op: str) -> str:
    """An HLO op's event name cut to its instruction and result shape:
    ``%fusion.3 = f32[64,100]{...} fusion(...)`` -> ``fusion.3 f32[64,100]``."""
    head, _, rest = op.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{head.lstrip('%')} {shape}".strip()[:96]


# ops that only contain other ops: their time is the time of what they hold
CONTAINERS = ("while", "conditional", "call")


class Summary:
    """The reduced trace. Times are seconds, averaged over the devices."""

    def __init__(self, devices: list[Device], spans: list[Event]):
        if not devices:
            raise ValueError("trace holds no TPU device plane")
        self.devices, self.spans = devices, spans
        starts = [e.start_ns for d in devices for e in d.ops + d.modules]
        ends = [e.end_ns for d in devices for e in d.ops + d.modules]
        starts += [e.start_ns for e in spans]
        ends += [e.end_ns for e in spans]
        # the window: from the first host span or device event to the last
        self.lo, self.hi = min(starts), max(ends)
        self.window_s = (self.hi - self.lo) * 1e-9
        self._busy = [union(d.ops or d.modules) for d in devices]
        self.busy_s = sum(sum(t - s for s, t in b) for b in self._busy) \
            * 1e-9 / len(devices)

    def module(self, prefix: str) -> tuple[float, float]:
        """(executions, seconds) of programs whose name starts with
        ``prefix`` or ``jit_<prefix>``, per device on average."""
        calls = secs = 0.0
        for d in self.devices:
            for e in d.modules:
                if e.name.startswith(prefix) or \
                        e.name.startswith("jit_" + prefix):
                    calls += 1
                    secs += e.dur_ns * 1e-9
        n = len(self.devices)
        return calls / n, secs / n

    def top_ops(self, k: int = 10, within: str | None = None) -> list[list]:
        """The k operations with most device time (containers left out);
        with ``within``, only those inside executions of that program."""
        acc = defaultdict(float)
        for d in self.devices:
            spans = [(m.start_ns, m.end_ns) for m in d.modules
                     if within is not None and (
                         m.name.startswith(within)
                         or m.name.startswith("jit_" + within))]
            for e in d.ops:
                if within is not None and not any(
                        a <= e.start_ns < b for a, b in spans):
                    continue
                name = short_name(e.name)
                if name.split(".", 1)[0].split(" ", 1)[0] in CONTAINERS:
                    continue
                acc[name] += e.dur_ns * 1e-9 / len(self.devices)
        return [[n, s] for n, s in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The k longest idle gaps (device 0's), by what the host did."""
        found = [(t - s, attribute((s, t), self.spans))
                 for s, t in gaps(self._busy[0], self.lo, self.hi)]
        found.sort(reverse=True)
        return [[name, secs * 1e-9] for secs, name in found[:k]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def summarize(path) -> Summary:
    return Summary(*load(path))

"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time, the
device time under each host span, and the longest idle gaps.

Device events are those on the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane (``XLA Modules`` where a plane has no op line).  Host spans are the
benchmark's own ``TraceAnnotation``s, whose names start with ``bench.``; the
one named ``bench.window`` marks the traced window, and everything is
clipped to it.  Busy time is the union of the device intervals, per chip,
averaged over the chips."""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.window"


@dataclasses.dataclass
class Interval:
    t0: float                  # seconds, trace time base
    t1: float
    name: str
    stats: dict


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    busy: list[list[tuple[float, float]]]        # per chip, merged, clipped
    ops: list[Interval]                           # every chip's device events
    spans: list[Interval]                         # host spans named bench.*

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        if not self.busy:
            return 0.0
        return sum(_length(b) for b in self.busy) / len(self.busy)

    def spans_named(self, name: str) -> list[Interval]:
        w0, w1 = self.window
        return [s for s in self.spans if s.name == name and s.t0 >= w0 and s.t1 <= w1]

    def device_s_in(self, spans: list[Interval]) -> float:
        """Busy device seconds inside the given host spans (averaged over chips)."""
        cover = merge([(s.t0, s.t1) for s in spans])
        if not self.busy:
            return 0.0
        return sum(_length(intersect(b, cover)) for b in self.busy) / len(self.busy)

    def labels(self, times: list[float]) -> list[str]:
        """For each time, the name of the shortest host span (other than the
        window) open then, or ``host`` where none is: a sweep over the spans
        in start order."""
        order = sorted(range(len(times)), key=times.__getitem__)
        spans = [s for s in self.spans if s.name != WINDOW]   # sorted by t0
        out = ["host"] * len(times)
        open_: list[Interval] = []
        k = 0
        for i in order:
            t = times[i]
            while k < len(spans) and spans[k].t0 <= t:
                open_.append(spans[k])
                k += 1
            open_ = [s for s in open_ if s.t1 > t]
            if open_:
                best = min(open_, key=lambda s: s.t1 - s.t0)
                out[i] = best.name[len("bench."):]
        return out

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Longest gaps in chip 0's busy time, named by the host span open at
        the gap's middle."""
        if not self.busy:
            return []
        w0, w1 = self.window
        edges = [w0] + [x for iv in self.busy[0] for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        gaps = gaps[:top]
        names = self.labels([(a + b) / 2 for a, b in gaps])
        return [[n, b - a] for n, (a, b) in zip(names, gaps)]

    def device_ops(self, top: int = 10) -> list[list]:
        """Device seconds by (host span open at the op's start, program):
        the union of each group's op intervals, since a loop's op spans the
        ops of its body (averaged over chips)."""
        names = self.labels([op.t0 for op in self.ops])
        groups: dict[str, list] = {}
        for n, op in zip(names, self.ops):
            key = f"{n}:{op.stats.get('module', op.name)}"
            groups.setdefault(key, []).append((op.t0, op.t1))
        chips = max(len(self.busy), 1)
        acc = {k: _length(merge(v)) / chips for k, v in groups.items()}
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def _length(ivs) -> float:
    return sum(b - a for a, b in ivs)


def merge(ivs) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def intersect(xs, ys) -> list[tuple[float, float]]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _short(name: str) -> str:
    """An op's instruction name (``%fusion.76 = ...`` -> ``fusion.76``)."""
    return name.split(" = ")[0].lstrip("%") if " = " in name else name


def _assign_modules(ops: list[Interval], modules: list[tuple]) -> None:
    """Give each op the name of the program (XLA module) it ran in."""
    modules = sorted(modules)
    j = 0
    for op in sorted(ops, key=lambda o: o.t0):
        while j < len(modules) and modules[j][1] <= op.t0:
            j += 1
        if j < len(modules) and modules[j][0] <= op.t0:
            op.stats["module"] = modules[j][2]


def reduce(profile) -> Reduced:
    """``profile``: a ``jax.profiler.ProfileData`` (or a path to its file)."""
    if isinstance(profile, str):
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(profile)
    spans: list[Interval] = []
    per_chip: list[list[Interval]] = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append(Interval(ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                              ev.name, _stats(ev)))
        elif DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
            if line is None:
                continue
            ops = [Interval(ev.start_ns * 1e-9, ev.end_ns * 1e-9, _short(ev.name), {})
                   for ev in line.events]
            if MODULES_LINE in lines:
                _assign_modules(ops, [(ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                       ev.name.split("(")[0])
                                      for ev in lines[MODULES_LINE].events])
            per_chip.append(ops)
    windows = [s for s in spans if s.name == WINDOW]
    if not windows:
        raise ValueError("trace has no bench.window span")
    w0, w1 = windows[0].t0, windows[0].t1
    busy, ops = [], []
    for events in per_chip:
        clipped = [e for e in events if e.t1 > w0 and e.t0 < w1]
        ops.extend(clipped)
        busy.append(merge([(max(e.t0, w0), min(e.t1, w1)) for e in clipped]))
    spans.sort(key=lambda s: s.t0)
    return Reduced(window=(w0, w1), busy=busy, ops=ops, spans=spans)

"""The program's own spans in a ``--trace 1`` run's profile.

``repro.obs.trace`` writes every span into the JAX profile as a host event
named ``repro.<layer>.<what>``, on the clock of the device's events, with
its attrs as stats.  ``events(ctx)`` gives the run's ``repro.*`` events that
start inside the traced window (``ctx.trace.window``), each cut at the
window's end, with the thread line it ran on; the profile is parsed once per
run.  A program that writes no such events gives none, and the readers that
use them then return ``None``."""
from __future__ import annotations

import dataclasses
import functools
import os

from bench import harness, trace_reduce

PREFIX = "repro."


@dataclasses.dataclass(frozen=True)
class Event:
    t0: float                  # seconds, the trace's time base
    t1: float
    name: str
    stats: dict
    thread: str                # "<line index>:<line name>" on its host plane


def parse(profile) -> list[Event]:
    """Every ``repro.*`` host event of a ``jax.profiler.ProfileData``, by start."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Event(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name,
                                     {k: v for k, v in ev.stats}, f"{i}:{line.name}"))
    out.sort(key=lambda e: e.t0)
    return out


@functools.lru_cache(maxsize=1)
def _load(path: str, stamp: int) -> tuple[Event, ...]:
    from jax.profiler import ProfileData
    return tuple(parse(ProfileData.from_file(path)))


def events(ctx) -> list[Event]:
    """The run's ``repro.*`` events that start in the traced window."""
    try:
        path = trace_reduce.find_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return []
    w0, w1 = ctx.trace.window
    return [dataclasses.replace(e, t1=min(e.t1, w1))
            for e in _load(path, os.stat(path).st_mtime_ns) if w0 <= e.t0 < w1]


def named(ctx, *names: str) -> list[Event]:
    return [e for e in events(ctx) if e.name in names]


def starting_in(evs: list[Event], spans: list[Event]) -> list[Event]:
    """The events that start inside one of ``spans``."""
    cover = trace_reduce.merge([(s.t0, s.t1) for s in spans])
    return [e for e in evs if any(a <= e.t0 < b for a, b in cover)]


def idle_s_in(ctx, spans: list[Event]) -> float | None:
    """Seconds inside ``spans`` (their union) in which chip 0 ran no
    operation; ``None`` where the trace has no device events."""
    if not ctx.trace.busy:
        return None
    cover = trace_reduce.merge([(s.t0, s.t1) for s in spans])
    inside = trace_reduce.intersect(ctx.trace.busy[0], cover)
    return sum(b - a for a, b in cover) - sum(b - a for a, b in inside)

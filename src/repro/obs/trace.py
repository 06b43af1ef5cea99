"""Span-based tracing for the semantic-operator stack.

One ``Tracer`` per traced run (or per gateway); spans nest through a
thread-local context so every layer — session, plan stage, operator,
partition fragment, dispatcher batch, kernel dispatch, index build, cache
lookup, engine scoring and scheduler step — attributes its work to the
right parent without passing handles through call signatures.  Tracing is
off by default: the module-level ``span()`` returns a shared no-op context
manager when no tracer is installed on the calling thread and no JAX
profile is being taken, so the off path costs a thread-local read and a
profiler flag read per call site.

Cross-thread propagation mirrors ``core.accounting``: the coordinating
thread snapshots its context with ``capture()`` and fragment / worker /
dispatcher threads re-install it with ``activate_ctx()``, so spans opened
on other threads still parent into the owning session or operator span.

Exports: ``Tracer.export_jsonl()`` (one span per line) and
``Tracer.export_chrome()`` (Chrome ``trace_event`` JSON, loadable in
Perfetto / ``chrome://tracing``).

Every span has a second sink: while a JAX profile is being taken, the span
also opens a ``jax.profiler.TraceAnnotation``, so it lands in the profile on
the same clock as the device's events, with or without a ``Tracer``.  The
event's name is stable and carries no ids: the span's ``event`` where the
call site gives one (``repro.dispatch.oracle.predicate``), else
``repro.<kind>`` with the span's name as the stat ``name``.  Scalar attrs
become the event's stats, including those set after the span opened.  No
span waits for the device.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import sys
import threading
import time

_ctx = threading.local()
_annotation = None        # jax.profiler.TraceAnnotation, once JAX is loaded


def current_tracer() -> "Tracer | None":
    return getattr(_ctx, "tracer", None)


def current_span() -> "Span | None":
    return getattr(_ctx, "span", None)


def _profiler():
    """``jax.profiler.TraceAnnotation`` while a JAX profile is being taken,
    else None.  A process that has not imported JAX takes no profile, so the
    simulated-backend path never imports it from here."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation if _annotation.is_enabled() else None


def active() -> bool:
    """Whether a span opened on this thread is recorded anywhere: a tracer
    is installed here or a JAX profile is being taken.  Call sites whose
    span attrs cost something to build test this first."""
    return current_tracer() is not None or _profiler() is not None


def _stats(attrs: dict) -> dict:
    """The attrs a profiler event can carry as stats: scalars (bools as
    ints); anything else stays with the tracer's span only."""
    out = {}
    for k, v in attrs.items():
        if isinstance(v, bool):
            out[k] = int(v)
        elif isinstance(v, (int, float, str)):
            out[k] = v
    return out


def _annotate(name: str, kind: str, event: "str | None", attrs: dict):
    """An unopened profiler event for a span, or None when no profile is
    being taken."""
    ann = _profiler()
    if ann is None:
        return None
    stats = _stats(attrs)
    if event is None:
        event = f"repro.{kind}"
        stats["name"] = name
    return ann(event, **stats)


class Span:
    """One timed unit of work.  ``attrs`` are typed-by-convention: counts
    are ints, seconds/thresholds are floats, identifiers are strings."""

    __slots__ = ("span_id", "parent_id", "name", "kind", "t0", "t1",
                 "attrs", "thread", "_ann")

    def __init__(self, span_id: int, parent_id: int | None, name: str,
                 kind: str, attrs: dict):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.t0 = time.monotonic()
        self.t1: float | None = None
        self.attrs = attrs
        self.thread = threading.get_ident()
        self._ann = None          # the profiler's event, while profiling

    @property
    def dur_s(self) -> float:
        return ((self.t1 if self.t1 is not None else time.monotonic())
                - self.t0)

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_stats(attrs))

    def add(self, key: str, n: float = 1) -> None:
        self.set(**{key: self.attrs.get(key, 0) + n})

    def as_dict(self, origin: float = 0.0) -> dict:
        return {
            "span_id": self.span_id, "parent_id": self.parent_id,
            "name": self.name, "kind": self.kind,
            "ts_us": round((self.t0 - origin) * 1e6, 1),
            "dur_us": round(self.dur_s * 1e6, 1),
            "thread": self.thread, "attrs": _jsonable(self.attrs),
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, kind={self.kind!r}, "
                f"dur={self.dur_s * 1e3:.2f}ms, attrs={self.attrs})")


class _NoopSpan:
    """Shared sink for all span mutation on the tracing-off path."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def add(self, key: str, n: float = 1) -> None:
        pass


class _NoopCM:
    __slots__ = ()

    def __enter__(self) -> _NoopSpan:
        return NOOP_SPAN

    def __exit__(self, *exc) -> bool:
        return False


NOOP_SPAN = _NoopSpan()
_NOOP_CM = _NoopCM()


class _ProfiledSpan:
    """A span that only the JAX profiler records (no tracer installed):
    its context manager and its handle in one.  It installs nothing as the
    thread's current span."""

    __slots__ = ("_ann", "_attrs")

    def __init__(self, ann, attrs: dict):
        self._ann = ann
        self._attrs = attrs

    def __enter__(self) -> "_ProfiledSpan":
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        self._attrs.update(attrs)
        self._ann.set_metadata(**_stats(attrs))

    def add(self, key: str, n: float = 1) -> None:
        self.set(**{key: self._attrs.get(key, 0) + n})


def _profiled(name: str, kind: str, event: "str | None", attrs: dict):
    ann = _annotate(name, kind, event, attrs)
    return _NOOP_CM if ann is None else _ProfiledSpan(ann, attrs)

# attribute keys whose values are summed when aggregating spans
_COUNTER_KEYS = ("oracle_calls", "proxy_calls", "embed_calls",
                 "compare_calls", "generate_calls", "cache_hits",
                 "scanned_bytes", "candidate_pairs",
                 "pairs_pruned_by_inference", "block_prompts",
                 "block_fallbacks")


class Tracer:
    """Collects finished spans; thread-safe; bounded (oldest runs should
    export and ``reset()`` — a serving gateway traces forever otherwise)."""

    def __init__(self, *, max_spans: int = 1_000_000):
        self._lock = threading.Lock()
        self._spans: list[Span] = []
        self._ids = itertools.count(1)
        self._max_spans = max_spans
        self.dropped = 0
        self.origin = time.monotonic()

    # -- span lifecycle ---------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, kind: str = "span", *, event: "str | None" = None,
             **attrs):
        """Open a span parented to this thread's current span (if this
        tracer is the one installed here), install it as current, and
        record it on exit (and in the JAX profile, while one is taken)."""
        parent = current_span() if current_tracer() is self else None
        sp = Span(next(self._ids),
                  parent.span_id if parent is not None else None,
                  name, kind, attrs)
        sp._ann = _annotate(name, kind, event, attrs)
        if sp._ann is not None:
            sp._ann.__enter__()
        prev = (current_tracer(), current_span())
        _ctx.tracer, _ctx.span = self, sp
        try:
            yield sp
        finally:
            if sp._ann is not None:
                sp._ann.__exit__(None, None, None)
            sp.t1 = time.monotonic()
            _ctx.tracer, _ctx.span = prev
            with self._lock:
                if len(self._spans) < self._max_spans:
                    self._spans.append(sp)
                else:
                    self.dropped += 1

    # -- queries ----------------------------------------------------------
    def spans(self, kind: str | None = None) -> list[Span]:
        with self._lock:
            out = list(self._spans)
        if kind is not None:
            out = [s for s in out if s.kind == kind]
        out.sort(key=lambda s: s.t0)
        return out

    def roots(self) -> list[Span]:
        return [s for s in self.spans() if s.parent_id is None]

    def children_index(self) -> dict:
        """span_id -> list of child spans (each list sorted by start)."""
        idx: dict = {}
        for s in self.spans():
            if s.parent_id is not None:
                idx.setdefault(s.parent_id, []).append(s)
        return idx

    def subtree(self, root: Span) -> list[Span]:
        idx = self.children_index()
        out, todo = [], [root]
        while todo:
            s = todo.pop()
            out.append(s)
            todo.extend(idx.get(s.span_id, ()))
        return out

    def session_spans(self, sid: str | None = None) -> list[Span]:
        return [s for s in self.spans(kind="session")
                if sid is None or s.attrs.get("sid") == sid]

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    # -- aggregation ------------------------------------------------------
    def stage_summary(self) -> dict:
        """Per-(kind, name) wall/count/call roll-up — the gateway snapshot's
        span-derived stage breakdown.  Wall is *inclusive* per span; only
        compare totals within one kind."""
        out: dict = {}
        for s in self.spans():
            row = out.setdefault(f"{s.kind}/{s.name}",
                                 {"count": 0, "wall_s": 0.0})
            row["count"] += 1
            row["wall_s"] = round(row["wall_s"] + s.dur_s, 6)
            for k in _COUNTER_KEYS:
                v = s.attrs.get(k)
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    row[k] = row.get(k, 0) + v
        return out

    # -- export -----------------------------------------------------------
    def export_jsonl(self, path: str) -> int:
        spans = self.spans()
        with open(path, "w") as f:
            for s in spans:
                f.write(json.dumps(s.as_dict(self.origin)) + "\n")
        return len(spans)

    def chrome_trace(self) -> dict:
        """Chrome ``trace_event`` document (complete 'X' events, µs)."""
        events = []
        for s in self.spans():
            events.append({
                "name": s.name, "cat": s.kind, "ph": "X",
                "ts": round((s.t0 - self.origin) * 1e6, 1),
                "dur": round(s.dur_s * 1e6, 1),
                "pid": 1, "tid": s.thread,
                "args": _jsonable({**s.attrs, "span_id": s.span_id,
                                   "parent_id": s.parent_id}),
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> int:
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"])


# -- module-level context helpers ----------------------------------------

def span(name: str, kind: str = "span", *, event: "str | None" = None,
         **attrs):
    """Open a span on this thread's installed tracer, or in the JAX profile
    alone while one is taken; no-op (and no attrs evaluation cost beyond
    the call) when neither records.  ``event`` names the profiler's event
    (default ``repro.<kind>``)."""
    t = current_tracer()
    if t is None:
        return _profiled(name, kind, event, attrs)
    return t.span(name, kind, event=event, **attrs)


def span_in(tracer: "Tracer | None", name: str, kind: str = "span", *,
            event: "str | None" = None, **attrs):
    """Open a span on an explicit tracer (dispatcher/subscription threads
    that hold a tracer handle rather than inheriting thread context)."""
    if tracer is None:
        return _profiled(name, kind, event, attrs)
    return tracer.span(name, kind, event=event, **attrs)


def capture() -> tuple:
    """Snapshot (tracer, span) for re-installation on another thread."""
    return (current_tracer(), current_span())


@contextlib.contextmanager
def activate_ctx(ctx: tuple):
    """Install a captured (tracer, span) pair on this thread; fragment
    workers use this so their spans parent into the coordinator's span."""
    prev = (current_tracer(), current_span())
    _ctx.tracer, _ctx.span = ctx
    try:
        yield
    finally:
        _ctx.tracer, _ctx.span = prev


@contextlib.contextmanager
def activate(tracer: "Tracer | None"):
    """Install a tracer (with no current span) on this thread — the entry
    point for a traced run on a worker thread."""
    prev = (current_tracer(), current_span())
    _ctx.tracer, _ctx.span = tracer, None
    try:
        yield tracer
    finally:
        _ctx.tracer, _ctx.span = prev


def _jsonable(d: dict) -> dict:
    out = {}
    for k, v in d.items():
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        elif isinstance(v, (list, tuple)):
            out[k] = [x if isinstance(x, (str, int, float, bool)) else repr(x)
                      for x in v]
        else:
            out[k] = repr(v)
    return out

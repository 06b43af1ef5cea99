"""Observability layer: span tracing, EXPLAIN ANALYZE, and the stats store.

Covers the tentpole surface — span nesting/propagation across threads,
trace export formats, explain_analyze's predicted-vs-observed comparison,
StatsStore accumulation + persistence, gateway trace integration — plus the
satellite fixes: the accounting details roll-up, the log-scale latency
histogram, explain_plan's predicted selectivity, and the shared-OpStats
concurrency stress test.
"""
import json
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core import accounting
from repro.core.backends import synth
from repro.core.frame import SemFrame, Session
from repro.core.plan.optimize import explain_plan, predicted_node_metrics
from repro.kernels import ops
from repro.obs import (StatsStore, Tracer, explain_analyze,
                       node_fingerprint, predicate_fingerprint)
from repro.obs import trace as T
from repro.serve import Gateway
from repro.serve.metrics import GatewayMetrics, LatencyHistogram


def _session(world, *, with_proxy=False, sample_size=40):
    return Session(
        oracle=synth.SimulatedModel(world, "oracle"),
        proxy=synth.SimulatedModel(world, "proxy") if with_proxy else None,
        embedder=synth.SimulatedEmbedder(world), sample_size=sample_size)


def _join_world(n=30, m=8, seed=7):
    left, right, world, *_ = synth.make_join_world(n, m, seed=seed)
    synth.add_phrase_predicate(world, left, "is checkable", 0.4, seed=seed)
    return left, right, world


def _pipeline(left, right, world):
    return (SemFrame(left, _session(world)).lazy()
            .sem_filter("the {abstract} is checkable")
            .sem_join(right, "the {abstract} reports the {reaction:right}"))


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_spans_nest_and_parent_on_the_active_thread():
    tr = Tracer()
    with T.activate(tr):
        with T.span("outer", kind="session", sid="s1"):
            with T.span("inner", kind="operator") as sp:
                sp.add("oracle_calls", 3)
    outer, inner = tr.spans()
    assert (outer.name, outer.kind, outer.parent_id) == ("outer", "session", None)
    assert inner.parent_id == outer.span_id
    assert inner.attrs["oracle_calls"] == 3
    assert inner.t0 >= outer.t0 and inner.t1 <= outer.t1


def test_tracing_off_is_a_shared_noop():
    assert T.current_tracer() is None
    cm = T.span("anything", kind="operator", x=1)
    assert cm is T._NOOP_CM
    with cm as sp:
        sp.set(a=1)
        sp.add("b", 2)          # silently absorbed
    assert T.span_in(None, "x") is T._NOOP_CM


def test_capture_activate_parents_spans_across_threads():
    tr = Tracer()
    with T.activate(tr):
        with T.span("coordinator", kind="operator"):
            ctx = accounting.capture()

            def work():
                with accounting.activate(ctx):
                    with T.span("remote", kind="fragment"):
                        pass

            th = threading.Thread(target=work)
            th.start()
            th.join()
    remote = tr.spans(kind="fragment")[0]
    coord = tr.spans(kind="operator")[0]
    assert remote.parent_id == coord.span_id
    assert remote.thread != coord.thread


def test_track_copies_opstats_onto_the_operator_span():
    tr = Tracer()
    with T.activate(tr):
        with accounting.track("sem_filter"):
            accounting.record("oracle", 4)
            accounting.record("cache_hit", 2)
    (sp,) = tr.spans(kind="operator")
    assert sp.name == "sem_filter"
    assert sp.attrs["oracle_calls"] == 4
    assert sp.attrs["cache_hits"] == 2
    assert sp.attrs["wall_s"] >= 0


def test_tracer_caps_spans_and_counts_drops():
    tr = Tracer(max_spans=2)
    with T.activate(tr):
        for i in range(5):
            with T.span(f"s{i}"):
                pass
    assert len(tr.spans()) == 2 and tr.dropped == 3


# ---------------------------------------------------------------------------
# satellite: accounting details roll-up + concurrency stress
# ---------------------------------------------------------------------------


def test_nested_track_merges_numeric_details_additively():
    with accounting.track("parent") as parent:
        parent.details["scanned_bytes"] = 100
        parent.details["index_kind"] = "ivf"
        with accounting.track("child") as child:
            child.details["scanned_bytes"] = 40
            child.details["rerank_rows"] = 7
            child.details["index_kind"] = "exact"   # non-numeric: parent wins
    assert parent.details["scanned_bytes"] == 140
    assert parent.details["rerank_rows"] == 7
    assert parent.details["index_kind"] == "ivf"


def test_shared_opstats_concurrent_records_sum_exactly():
    """Many fragment threads add into ONE shared OpStats (the partitioned
    executor's contract); totals must be exact, not approximately right —
    this is the regression guard on the ``_add_lock`` serialization."""
    n_threads, n_iter = 12, 300
    with accounting.track("parent") as parent:
        ctx = accounting.capture()

        def fragment(pi):
            with accounting.activate(ctx):
                with accounting.track(f"fragment[{pi}]") as st:
                    for _ in range(n_iter):
                        accounting.record("oracle", 1)
                        accounting.record("cache_hit", 2)
                    st.details["scanned_bytes"] = 10

        threads = [threading.Thread(target=fragment, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert parent.oracle_calls == n_threads * n_iter
    assert parent.cache_hits == 2 * n_threads * n_iter
    assert parent.details["scanned_bytes"] == 10 * n_threads


def test_fragment_spans_parent_into_the_partitioned_operator():
    records, world, *_ = synth.make_filter_world(60, seed=31)
    synth.add_phrase_predicate(world, records, "is rare", 0.3, seed=31)
    tr = Tracer()
    with T.activate(tr):
        out = (SemFrame(records, _session(world)).lazy()
               .sem_filter("the {claim} is rare")
               .collect(n_partitions=4, partition_min_rows=8,
                        fragment_workers=4))
    assert out.records
    frags = tr.spans(kind="fragment")
    assert len(frags) >= 2
    by_id = {s.span_id: s for s in tr.spans()}
    for f in frags:
        assert f.parent_id in by_id          # parented, not orphaned
        assert by_id[f.parent_id].kind in ("operator", "plan_stage")


# ---------------------------------------------------------------------------
# export formats
# ---------------------------------------------------------------------------


def test_jsonl_export_is_one_valid_span_per_line(tmp_path):
    tr = Tracer()
    with T.activate(tr):
        with T.span("a", kind="session"):
            with T.span("b", kind="operator", oracle_calls=2):
                pass
    p = tmp_path / "trace.jsonl"
    assert tr.export_jsonl(str(p)) == 2
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert len(lines) == 2
    for row in lines:
        assert {"span_id", "parent_id", "name", "kind", "ts_us", "dur_us",
                "attrs"} <= set(row)


def test_chrome_export_is_loadable_trace_event_json(tmp_path):
    tr = Tracer()
    with T.activate(tr):
        with T.span("sess", kind="session"):
            with T.span("op", kind="operator"):
                pass
    p = tmp_path / "trace.json"
    tr.export_chrome(str(p))
    doc = json.loads(p.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert len(events) == 2
    for ev in events:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float))
        assert {"name", "cat", "pid", "tid", "args"} <= set(ev)


# ---------------------------------------------------------------------------
# kernel spans
# ---------------------------------------------------------------------------


def test_kernel_dispatch_spans_only_when_traced(rng):
    q = rng.normal(size=(4, 16)).astype(np.float32)
    c = rng.normal(size=(32, 16)).astype(np.float32)
    ops.similarity(q, c)                    # untraced: no tracer to record to
    tr = Tracer()
    with T.activate(tr):
        ops.similarity(q, c)
    (sp,) = tr.spans(kind="kernel")
    assert sp.name == "kernel/similarity"
    assert sp.attrs["nq"] == 4 and sp.attrs["nc"] == 32
    assert "impl" in sp.attrs


# ---------------------------------------------------------------------------
# explain_plan / explain_analyze
# ---------------------------------------------------------------------------


def test_explain_plan_prints_predicted_selectivity():
    left, right, world = _join_world()
    lz = _pipeline(left, right, world)
    text = explain_plan(lz.plan)
    assert "sel~" in text
    assert "sel~" in lz.explain()


def test_predicted_node_metrics_shape():
    left, right, world = _join_world()
    lz = _pipeline(left, right, world)
    pred = predicted_node_metrics(lz.plan)
    assert set(pred) == {"rows", "selectivity", "oracle_calls"}
    assert pred["rows"] >= 0 and pred["oracle_calls"] >= 0


def test_explain_analyze_reports_predicted_and_observed_per_node():
    left, right, world = _join_world()
    lz = _pipeline(left, right, world)
    store = StatsStore()
    rep = explain_analyze(lz, stats_store=store)
    # records match a plain collect() of the same pipeline
    expect = _pipeline(left, right, world).collect()
    assert rep.records == expect.records
    text = rep.render()
    assert "EXPLAIN ANALYZE" in text
    executed = [r for r in rep.nodes if r.observed is not None]
    assert executed, "no node carried observations"
    for r in executed:
        assert r.predicted["rows"] >= 0
        assert r.observed["rows_out"] >= 0
        assert r.observed["wall_s"] >= 0
    flt = next(r for r in rep.nodes if type(r.node).__name__ == "Filter")
    assert flt.observed["rows_in"] == len(left)
    assert 0 < flt.observed["selectivity"] < 1
    assert flt.observed["oracle_calls"] > 0
    # the stats store now knows this predicate's observed selectivity
    assert len(store) >= 2
    obs_sel = store.selectivity_for_node(flt.node)
    assert obs_sel == pytest.approx(flt.observed["selectivity"])


def test_explain_analyze_flags_cost_model_drift():
    left, right, world = _join_world()
    rep = explain_analyze(_pipeline(left, right, world), tolerance=1e-6)
    # with a near-zero tolerance at least one node must drift (wall-clock
    # perfect predictions don't exist), and the flag renders
    assert rep.drifted
    assert "!! drift" in rep.render()


def test_explain_analyze_unoptimized_matches_collect():
    left, right, world = _join_world(seed=9)
    expect = _pipeline(left, right, world).collect(optimize=False)
    rep = explain_analyze(_pipeline(left, right, world), optimize=False)
    assert rep.records == expect.records


# ---------------------------------------------------------------------------
# stats store
# ---------------------------------------------------------------------------


def test_fingerprint_depends_on_semantics_not_data():
    fp1 = predicate_fingerprint("Filter", "the {a} is x")
    fp2 = predicate_fingerprint("Filter", "the {a} is x")
    fp3 = predicate_fingerprint("Filter", "the {a} is y")
    assert fp1 == fp2 != fp3
    left, right, world = _join_world()
    lz_small = (SemFrame(left[:5], _session(world)).lazy()
                .sem_filter("the {abstract} is checkable"))
    lz_big = (SemFrame(left, _session(world)).lazy()
              .sem_filter("the {abstract} is checkable"))
    assert node_fingerprint(lz_small.plan) == node_fingerprint(lz_big.plan)
    assert node_fingerprint(lz_small.plan.children()[0]) is None  # Scan


def test_stats_store_accumulates_and_persists(tmp_path):
    s = StatsStore()
    s.observe("filter", "abc", rows_in=100, rows_out=30, wall_s=0.5,
              stats={"oracle_calls": 100})
    s.observe("filter", "abc", rows_in=50, rows_out=20, wall_s=0.5,
              stats={"oracle_calls": 50})
    obs = s.get("filter", "abc")
    assert obs.runs == 2
    assert obs.selectivity == pytest.approx(50 / 150)
    assert obs.oracle_calls == 150
    assert obs.mean_wall_s == pytest.approx(0.5)
    p = tmp_path / "stats.json"
    s.save(str(p))
    # load merges additively: same entry twice -> doubled counts
    merged = StatsStore(str(p))
    merged.load(str(p))
    m = merged.get("filter", "abc")
    assert m.runs == 4 and m.rows_in == 300 and m.oracle_calls == 300
    assert m.selectivity == pytest.approx(50 / 150)


# ---------------------------------------------------------------------------
# latency histogram (satellite)
# ---------------------------------------------------------------------------


def test_latency_histogram_percentiles_within_bucket_error(rng):
    h = LatencyHistogram()
    xs = rng.lognormal(mean=-2.0, sigma=1.0, size=5000)
    for x in xs:
        h.record(x)
    for q in (50, 95, 99):
        exact = float(np.percentile(xs, q))
        got = h.percentile(q)
        assert abs(got - exact) / exact < 0.08   # half-bucket ≈ 3.7%
    assert len(h) == 5000
    assert h.percentile(0) <= h.percentile(50) <= h.percentile(100)


def test_latency_histogram_clamps_out_of_range():
    h = LatencyHistogram()
    h.record(1e-9)
    h.record(1e9)
    assert h.percentile(0) == LatencyHistogram.LO
    assert h.percentile(100) == LatencyHistogram.HI


def test_metrics_snapshot_keeps_field_names_and_adds_p99():
    m = GatewayMetrics()
    for x in (0.01, 0.02, 0.04, 0.08, 0.5):
        m.on_finish("done", x, 1)
    snap = m.snapshot()
    assert {"p50_latency_s", "p95_latency_s", "p99_latency_s"} <= set(snap)
    assert snap["p50_latency_s"] == pytest.approx(0.04, rel=0.1)
    assert snap["completed"] == 5
    empty = GatewayMetrics().snapshot()
    assert empty["p50_latency_s"] is None and empty["p99_latency_s"] is None


# ---------------------------------------------------------------------------
# gateway integration
# ---------------------------------------------------------------------------


def test_gateway_tracing_off_by_default():
    left, right, world = _join_world()
    with Gateway(_session(world), max_inflight=2) as gw:
        sess = gw.submit(_pipeline(left, right, world))
        assert sess.result(timeout=30.0)
        assert gw.tracer is None
        assert "stages" not in gw.snapshot()
        with pytest.raises(RuntimeError):
            gw.export_trace("/dev/null")


def test_gateway_trace_spans_sessions_and_exports(tmp_path):
    left, right, world = _join_world()
    with Gateway(_session(world), max_inflight=2, trace=True) as gw:
        s1 = gw.submit(_pipeline(left, right, world))
        s2 = gw.submit(_pipeline(left, right, world), tenant="b")
        r1, r2 = s1.result(timeout=30.0), s2.result(timeout=30.0)
        assert r1 == r2
        # one root session span per serve session, tagged with its sid
        roots = gw.tracer.session_spans()
        assert {s.attrs["sid"] for s in roots} == {s1.sid, s2.sid}
        # the session subtree spans layers: plan stages, operators, and the
        # dispatcher's fused batches (which run on the dispatcher thread)
        kinds = {s.kind for s in gw.session_trace(s1.sid)}
        assert {"session", "plan_stage", "operator"} <= kinds
        all_kinds = {s.kind for s in gw.tracer.spans()}
        assert "dispatch_batch" in all_kinds
        assert "cache_lookup" in all_kinds
        for sp in gw.tracer.spans(kind="dispatch_batch"):
            assert "fused_calls" in sp.attrs
        # snapshot carries the span-derived stage breakdown
        stages = gw.snapshot()["stages"]
        assert any(k.startswith("session/") for k in stages)
        assert any(k.startswith("operator/") for k in stages)
        # exports: JSONL lines and a Perfetto-loadable chrome trace
        pj = tmp_path / "gw.jsonl"
        pc = tmp_path / "gw.json"
        n = gw.export_trace(str(pj))
        assert n == len(gw.tracer.spans())
        assert all(json.loads(l) for l in pj.read_text().splitlines())
        gw.export_trace(str(pc), fmt="chrome")
        doc = json.loads(pc.read_text())
        assert len(doc["traceEvents"]) == n


def test_gateway_persists_stats_store_next_to_cache(tmp_path):
    left, right, world = _join_world()
    persist = str(tmp_path / "cache.json")
    with Gateway(_session(world), max_inflight=1,
                 persist_path=persist) as gw:
        gw.submit(_pipeline(left, right, world)).result(timeout=30.0)
        assert len(gw.stats_store) >= 1
    saved = StatsStore(persist + ".stats.json")
    assert len(saved) >= 1
    assert any(e["selectivity"] is not None for e in saved.snapshot())
    # a second gateway warm-starts from the persisted observations
    with Gateway(_session(world), max_inflight=1,
                 persist_path=persist) as gw2:
        assert len(gw2.stats_store) >= 1


def test_traced_run_is_record_identical_to_untraced():
    left, right, world = _join_world(seed=13)
    untraced = _pipeline(left, right, world).collect()
    tr = Tracer()
    with T.activate(tr):
        traced = _pipeline(left, right, world).collect()
    assert traced.records == untraced.records
    assert tr.spans(kind="plan_stage")


# ---------------------------------------------------------------------------
# the JAX profiler as a second sink
# ---------------------------------------------------------------------------

ROOT = Path(__file__).resolve().parents[1]

# every span the benchmark's readers use, with the stats each must carry
PROGRAM_SPANS = {
    "repro.dispatch.oracle.predicate": {"fused_calls", "unique_prompts", "backend_prompts",
                                        "store_hits", "wait_ms_sum", "wait_ms_max"},
    "repro.dispatch.oracle.generate": {"fused_calls", "wait_ms_sum", "wait_ms_max"},
    "repro.engine.score": {"rows", "tokens", "padded_tokens", "chunks", "narrowed_rows"},
    "repro.engine.score.prep": {"rows", "width"},
    "repro.runner.fetch": set(),
    "repro.engine.generate": {"requests"},
    "repro.sched.prefill": {"tokens", "bucket"},
    "repro.sched.decode": {"live", "slots"},
    "repro.sched.sample": {"rows"},
}


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _bench():
    """The benchmark's span helpers (``bench/`` at the repository's root)."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness, program_spans, trace_reduce
    return harness, program_spans, trace_reduce


def _profiled_events(tmp_path, monkeypatch, body):
    """Run ``body`` under a JAX profile, inside a ``bench.window`` event, and
    read the program's events back as the benchmark's readers do."""
    import jax
    harness, program_spans, trace_reduce = _bench()
    with jax.profiler.trace(str(tmp_path), profiler_options=_profile_options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            body()
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    red = trace_reduce.reduce(trace_reduce.find_xplane(str(tmp_path)))
    ctx = harness.MetricContext(trace=red, totals={}, cell=None, peak={})
    return program_spans.events(ctx)


def test_no_tracer_and_no_profile_is_the_shared_noop():
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()
    assert T.current_tracer() is None and not T.active()
    assert T.span("engine/score", "engine", event="repro.engine.score", rows=1) \
        is T._NOOP_CM
    assert T.span_in(None, "dispatch/oracle.predicate", "dispatch_batch") is T._NOOP_CM


def test_gateway_on_a_real_engine_emits_every_program_span(tmp_path, monkeypatch):
    from repro.configs import get_smoke
    from repro.core.backends.jax_engine import EngineModel
    from repro.data.tokenizer import TOKENIZER
    from repro.engine.engine import InferenceEngine

    cfg = get_smoke("llama3.2-3b").with_(vocab_size=TOKENIZER.vocab_size, num_layers=1,
                                         d_model=32, d_ff=64)
    engine = InferenceEngine(cfg, max_slots=2, max_seq=64)
    session = Session(oracle=EngineModel(engine, max_new_tokens=3))
    records = [{"claim": f"claim {i}"} for i in range(3)]

    def queries():
        with Gateway(session, max_inflight=2) as gw:
            gw.submit(SemFrame(records, session).lazy()
                      .sem_filter("the {claim} holds")).result(timeout=120.0)
            gw.submit(SemFrame(records, session).lazy()
                      .sem_map("shorten {claim}")).result(timeout=120.0)

    events = _profiled_events(tmp_path, monkeypatch, queries)
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)
    for name, stats in PROGRAM_SPANS.items():
        assert name in by_name, name
        assert all(stats <= set(e.stats) for e in by_name[name]), name
    (score,) = by_name["repro.engine.score"]
    assert score.stats["rows"] == 3 and score.stats["chunks"] == 1
    assert score.stats["padded_tokens"] == 8 * by_name["repro.engine.score.prep"][0] \
        .stats["width"]
    (gen,) = by_name["repro.engine.generate"]
    assert gen.stats["requests"] == 3
    assert len(by_name["repro.sched.prefill"]) == 3
    assert all(e.stats["slots"] == 2 and 1 <= e.stats["live"] <= 2
               for e in by_name["repro.sched.decode"])
    # the session span is named by its kind, with the sid as a stat
    assert {e.stats["name"] for e in by_name["repro.session"]} \
        == {e.stats["sid"] for e in by_name["repro.session"]}
    assert not any(":" in e.name or "/" in e.name for e in events)


def test_tracer_exports_are_unchanged_under_a_profile(tmp_path, monkeypatch):
    left, right, world = _join_world(seed=5)

    def traced():
        tr = Tracer()
        with T.activate(tr):
            _pipeline(left, right, world).collect()
        return tr

    def stable(tr):
        rows = [json.loads(json.dumps(s.as_dict(tr.origin))) for s in tr.spans()]
        for r in rows:
            for k in ("span_id", "parent_id", "ts_us", "dur_us", "thread"):
                r.pop(k)
            r["attrs"].pop("node_id", None)
            r["attrs"].pop("wall_s", None)
        summary = {k: {f: v for f, v in row.items() if f != "wall_s"}
                   for k, row in tr.stage_summary().items()}
        chrome = [(e["name"], e["cat"], e["ph"], sorted(e["args"]))
                  for e in tr.chrome_trace()["traceEvents"]]
        return sorted(map(json.dumps, rows)), summary, sorted(chrome)

    plain = traced()
    box = {}
    events = _profiled_events(tmp_path, monkeypatch, lambda: box.setdefault("tr", traced()))
    assert stable(box["tr"]) == stable(plain)
    # and the same spans reached the profile
    assert len([e for e in events if e.name == "repro.plan_stage"]) \
        == len(plain.spans(kind="plan_stage"))


def test_attrs_set_after_open_reach_the_profile(tmp_path, monkeypatch):
    def body():
        with T.span("a/b", "x", event="repro.test.late", n=1) as sp:
            sp.set(m=2.5, flag=True, skipped=[1, 2])
            sp.add("n", 2)
        tr = Tracer()
        with T.activate(tr), T.span("op", kind="operator", k="v") as sp:
            sp.add("oracle_calls", 4)

    events = {e.name: e for e in _profiled_events(tmp_path, monkeypatch, body)}
    assert events["repro.test.late"].stats == {"n": 3, "m": 2.5, "flag": 1}
    assert events["repro.operator"].stats == {"k": "v", "name": "op", "oracle_calls": 4}


def test_kernel_span_under_a_profile_alone_does_not_sync(tmp_path, monkeypatch, rng):
    q = rng.normal(size=(4, 16)).astype(np.float32)
    c = rng.normal(size=(32, 16)).astype(np.float32)
    seen = []
    real = ops._ready
    monkeypatch.setattr(ops, "_ready", lambda out, sp: seen.append(sp) or real(out, sp))
    events = _profiled_events(tmp_path, monkeypatch, lambda: ops.similarity(q, c))
    (sp,) = seen
    assert sp is not None and not isinstance(sp, T.Span)   # no block_until_ready
    (ev,) = [e for e in events if e.name == "repro.kernel.similarity"]
    assert ev.stats["nq"] == 4 and ev.stats["nc"] == 32

"""The readers of the program's own spans, on a small synthetic trace."""
import jax
import pytest

from bench import harness, program_spans, trace_reduce

# window [0.2, 20) us; chip 0 busy over [1, 3), [6, 7) and [12, 14) us.
# Line 1 (the dispatcher thread): three fused batches in the window (waits
# 3 + 1 + 4 ms over 2 + 1 + 1 calls) and one that starts before it; two
# scoring calls, [1, 5) over 32 rows (2 us idle) and [5.5, 8) over 8 rows
# (1.5 us idle); one generate call [10, 18) (6 us idle) with a prefill, two
# decode steps (3 and 1 of 8 slots live) and a sampling step.
DISPATCH = [("oracle.predicate", 0.5, 5.0, 2, 3.0), ("proxy.predicate", 5.5, 8.0, 1, 1.0),
            ("oracle.generate", 9.0, 19.0, 1, 4.0), ("oracle.predicate", 0.0, 0.4, 5, 50.0)]
SPANS = [("repro.engine.score", 1.0, 5.0, {"rows": 32}),
         ("repro.engine.score", 5.5, 8.0, {"rows": 8}),
         ("repro.engine.generate", 10.0, 18.0, {"requests": 2}),
         ("repro.sched.prefill", 10.0, 12.0, {"tokens": 40, "bucket": 64}),
         ("repro.sched.decode", 12.0, 14.0, {"live": 3, "slots": 8}),
         ("repro.sched.decode", 14.0, 16.0, {"live": 1, "slots": 8}),
         ("repro.sched.sample", 16.0, 17.0, {"rows": 8})]
US = 1e-6
PS = 1e6                      # picoseconds per microsecond


def _event(meta: int, t0: float, t1: float, stats: dict, stat_ids: dict) -> str:
    st = " ".join(
        f"stats {{ metadata_id: {stat_ids[k]} "
        f"{'double_value' if isinstance(v, float) else 'int64_value'}: {v} }}"
        for k, v in stats.items())
    return (f"events {{ metadata_id: {meta} offset_ps: {int(t0 * PS)} "
            f"duration_ps: {int((t1 - t0) * PS)} {st} }}")


def _trace(spans, dispatch) -> str:
    names = ["bench.window"] + sorted({n for n, *_ in spans}
                                      | {f"repro.dispatch.{k}" for k, *_ in dispatch})
    meta = {n: i + 1 for i, n in enumerate(names)}
    stat_names = sorted({k for *_, st in spans for k in st} | {"fused_calls", "wait_ms_sum"})
    stat_ids = {n: i + 1 for i, n in enumerate(stat_names)}
    evs = [_event(meta[n], t0, t1, st, stat_ids) for n, t0, t1, st in spans]
    evs += [_event(meta[f"repro.dispatch.{k}"], t0, t1,
                   {"fused_calls": calls, "wait_ms_sum": wait}, stat_ids)
            for k, t0, t1, calls, wait in dispatch]
    ops = "".join(f"events {{ metadata_id: 1 offset_ps: {int(a * PS)} "
                  f"duration_ps: {int((b - a) * PS)} }}\n"
                  for a, b in ((1, 3), (6, 7), (12, 14)))
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ops} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = bf16[8]{{0}} fusion(...)" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {_event(meta["bench.window"], 0.2, 20.0, {}, stat_ids)} }}
  lines {{ id: 2 name: "python3" timestamp_ns: 0
    {" ".join(evs)} }}
  {" ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in meta.items())}
  {" ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in stat_ids.items())} }}
"""


def _ctx(tmp_path, monkeypatch, text):
    path = tmp_path / "run.xplane.pb"
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    return harness.MetricContext(trace=trace_reduce.reduce(str(path)), totals={},
                                 cell=None, peak={})


def _read(metric, ctx):
    return harness.reader(metric).read(ctx)


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    return _ctx(tmp_path, monkeypatch, _trace(SPANS, DISPATCH))


def test_events_start_in_the_window_with_stats_and_thread(ctx):
    evs = program_spans.events(ctx)
    assert len(evs) == len(SPANS) + 3                 # the early batch is left out
    assert {e.thread for e in evs} == {"1:python3"}
    (gen,) = program_spans.named(ctx, "repro.engine.generate")
    assert (gen.t0 / US, gen.t1 / US, gen.stats) == (pytest.approx(10.0),
                                                     pytest.approx(18.0), {"requests": 2})


def test_dispatch_wait_per_call(ctx):
    assert _read("dispatch_wait_ms.bulk", ctx) == pytest.approx((3.0 + 1.0 + 4.0) / 4)


def test_score_idle_per_row(ctx):
    assert _read("score_idle_ms_per_row.bulk", ctx) == pytest.approx(1e3 * 3.5 * US / 40)


def test_step_idle_per_step(ctx):
    assert _read("step_idle_ms.bulk", ctx) == pytest.approx(1e3 * 6.0 * US / 3)


def test_slot_occupancy(ctx):
    assert _read("slot_occupancy.bulk", ctx) == pytest.approx(100.0 * 4 / 16)


@pytest.mark.parametrize("metric", ["dispatch_wait_ms.bulk", "score_idle_ms_per_row.bulk",
                                    "step_idle_ms.bulk", "slot_occupancy.bulk"])
def test_readers_give_none_without_program_spans(tmp_path, monkeypatch, metric):
    assert _read(metric, _ctx(tmp_path, monkeypatch, _trace([], []))) is None


def test_readers_give_none_without_a_trace_file(tmp_path, monkeypatch, ctx):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "none")
    assert program_spans.events(ctx) == []
    assert _read("slot_occupancy.bulk", ctx) is None

"""The trace reduction on a small synthetic trace."""
import jax
import pytest

from bench import trace_reduce

# chip 0: ops at [1, 3) and [2, 4) us overlap (program jit_score), then [6, 7)
# (jit_decode); window [0, 10) us;
# one host scoring span over [1, 5) us, one decode span over [5, 8) us
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8]{0} fusion(...)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = bf16[8]{0} fusion(...)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_score(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_decode(456)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000
             stats { metadata_id: 1 int64_value: 32 } }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.oracle.score" } }
  event_metadata { key: 3 value { id: 3 name: "bench.oracle.decode" } }
  stat_metadata { key: 1 value { id: 1 name: "rows" } } }
"""
US = 1e-6


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(jax.profiler.ProfileData.from_text_proto(TRACE))


def test_busy_union_of_overlapping_events(red):
    assert red.window_s == pytest.approx(10 * US)
    assert red.busy[0] == [(pytest.approx(1 * US), pytest.approx(4 * US)),
                           (pytest.approx(6 * US), pytest.approx(7 * US))]
    assert red.busy_s == pytest.approx(4 * US)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.6)


def test_device_time_under_host_spans(red):
    score = red.spans_named("bench.oracle.score")
    assert [s.stats["rows"] for s in score] == [32]
    assert red.device_s_in(score) == pytest.approx(3 * US)
    assert red.device_s_in(red.spans_named("bench.oracle.decode")) == pytest.approx(1 * US)


def test_device_time_per_program_and_gaps(red):
    ops = dict(red.device_ops())
    assert ops["oracle.score:jit_score"] == pytest.approx(3 * US)   # union of [1,3), [2,4)
    assert ops["oracle.decode:jit_decode"] == pytest.approx(1 * US)
    gaps = red.idle_gaps()   # [7, 10), [4, 6) under the decode span, [0, 1)
    assert [g[0] for g in gaps] == ["host", "oracle.decode", "host"]
    assert [g[1] / US for g in gaps] == pytest.approx([3.0, 2.0, 1.0])


def test_trace_without_window_is_refused():
    no_window = TRACE.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError):
        trace_reduce.reduce(jax.profiler.ProfileData.from_text_proto(no_window))

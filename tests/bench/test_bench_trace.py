"""The trace reduction on synthetic traces: busy union, idle share, device
time attributed to harness spans, program events, the breakdown."""

import pytest
import trace as trace_lib
from trace import HostSpan, Trace


def _trace():
    # window 0..100 ns; device ops overlap at 10-30 and 20-40, then 60-70
    ops = {"/device:TPU:0": [("fusion.1", 10.0, 30.0), ("fusion.2", 20.0, 40.0),
                             ("copy.3", 60.0, 70.0)]}
    modules = {"/device:TPU:0": [("jit_decode_step(7)", 10.0, 40.0),
                                 ("jit_prefill_step(3)", 60.0, 70.0)]}
    spans = [HostSpan("traced", 0.0, 100.0, {}),
             HostSpan("tick", 5.0, 45.0, {"admitted": 0}),
             HostSpan("tick", 55.0, 80.0, {"admitted": 1}),
             HostSpan("gateway", 80.0, 95.0, {})]
    return Trace(ops=ops, modules=modules, spans=spans, window=(0.0, 100.0))


def test_union_and_covered():
    merged = trace_lib.union([(20, 40), (10, 30), (60, 70), (70, 75)])
    assert merged == [(10, 40), (60, 75)]
    assert trace_lib.covered(merged, 0, 100) == 45
    assert trace_lib.covered(merged, 35, 65) == 10


def test_busy_and_idle_share():
    t = _trace()
    assert t.busy_ns(0, 100) == 40.0
    assert t.busy_s() == pytest.approx(40e-9)
    assert t.window_s() == pytest.approx(100e-9)
    assert t.idle_share() == pytest.approx(0.6)


def test_device_time_attributed_to_spans():
    t = _trace()
    ticks = t.named("tick")
    assert [s.args["admitted"] for s in ticks] == [0, 1]
    assert t.busy_in(ticks[:1]) == 30.0
    assert t.busy_in(ticks) == 40.0
    # the decode program inside the first tick, none in the second
    assert len(t.module_events("jit_decode_step", 5.0, 45.0)) == 1
    assert t.module_events("jit_decode_step", 55.0, 80.0) == []
    assert [e[0] for e in t.module_events("jit_prefill")] == ["jit_prefill_step(3)"]


def test_breakdown():
    t = _trace()
    top = t.top_ops(2)
    assert [n for n, _s in top] == ["fusion.1", "fusion.2"]
    assert top[0][1] == pytest.approx(20e-9)
    gaps = dict((n, s) for n, s in t.idle_gaps())
    # each gap goes to the span around its midpoint: 0-10 (5: a tick),
    # 40-60 (50: no span), 70-100 (85: the gateway)
    assert gaps == pytest.approx({"tick": 10e-9, "host": 20e-9,
                                  "gateway": 30e-9})


def test_from_profile_text_proto():
    """The same reduction from an XSpace, as the profiler writes it."""
    from jax.profiler import ProfileData

    text = """
    planes {
      id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 10000 duration_ps: 20000 }
        events { metadata_id: 2 offset_ps: 50000 duration_ps: 10000 } }
      lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 3 offset_ps: 10000 duration_ps: 50000 } }
      event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
      event_metadata { key: 2 value { id: 2 name: "copy.2" } }
      event_metadata { key: 3 value { id: 3 name: "jit_decode_step(1)" } }
    }
    planes {
      id: 2 name: "/host:CPU"
      lines { id: 3 name: "python" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
        events { metadata_id: 2 offset_ps: 5000 duration_ps: 40000 } }
      event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
      event_metadata { key: 2 value { id: 2 name: "bench.tick" } }
    }
    """
    t = trace_lib.from_profile(ProfileData.from_text_proto(text))
    assert t.window == (1000.0, 1100.0)
    assert t.busy_ns(*t.window) == 30.0
    assert t.idle_share() == pytest.approx(0.7)
    assert t.busy_in(t.named("tick")) == 20.0
    assert len(t.module_events("jit_decode_step")) == 1

"""The program's own spans (``jita.*``) beside the harness's in a reduced
trace: the DS cell's per-layer readers read what they read without them,
and the breakdown puts each idle gap down to the innermost span around it,
a program span where there is one."""

import json

import pytest
import run
from conftest import REPO
from spans import Recorder, Span
from trace import HostSpan, Trace

CELL = "ds16-paper.closed"
READERS = [m["name"] for m in json.loads((REPO / "BENCHMARK.json").read_text())
           ["per_layer"] if CELL in m.get("workloads", [CELL])]
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def _recorder():
    """Harness spans of one traced stretch (0-100 ns): a warm-up task that
    no reader counts, the planner, one host task and one device task."""
    rec = Recorder()
    rec.spans = [
        Span("task", 0, 1, {"task": "ingest", "backend": "host", "round": -1,
                            "share": 0.5}),
        Span("planner", 1, 5, {"inst": 0}),
        Span("task", 5, 31, {"task": "summarize", "backend": "host",
                             "round": 0, "share": 0.25}),
        Span("task", 39, 56, {"task": "kmeans", "backend": "device",
                              "round": 0, "share": 0.25, "flops": 4e3,
                              "bytes": 8e3}),
    ]
    return rec


#: the program's spans of the same stretch, as a reduction that keeps
#: ``jita.*`` host events under their full names would hold them
PROGRAM = [
    HostSpan("jita.planner.submit", 1.5, 2.0, {"instance": "ds_workload#0",
                                               "tasks": 16}),
    HostSpan("jita.planner.step", 2.0, 4.5, {}),
    HostSpan("jita.executor.task", 4.0, 32.0, {
        "task": "summarize", "op": "summarize", "pe": "arm0",
        "backend": "host", "cross_bytes": 0}),
    HostSpan("jita.host.summarize", 6.0, 30.0, {"op": "summarize"}),
    HostSpan("jita.executor.wait", 31.0, 32.0, {"task": "summarize"}),
    HostSpan("jita.executor.task", 35.0, 95.0, {
        "task": "kmeans", "op": "kmeans", "pe": "v100", "backend": "device",
        "cross_bytes": 4096}),
    HostSpan("jita.device.kmeans", 40.0, 55.0, {"op": "kmeans"}),
    HostSpan("jita.executor.wait", 80.0, 95.0, {"task": "kmeans"}),
]


def _trace(program=()):
    """Device busy 20-30 and 60-70 ns of a 0-100 ns window."""
    ops = {"/device:TPU:0": [("fusion.1", 20.0, 30.0), ("fusion.2", 60.0, 70.0)]}
    modules = {"/device:TPU:0": [("jit_add(1)", 20.0, 30.0),
                                 ("jit_dot(2)", 60.0, 70.0)]}
    spans = [HostSpan("traced", 0.0, 100.0, {}),
             HostSpan("planner", 1.0, 5.0, {"inst": 0}),
             HostSpan("task", 5.0, 31.0, {"task": "summarize",
                                          "backend": "host", "round": 0}),
             HostSpan("task", 39.0, 56.0, {"task": "kmeans",
                                           "backend": "device", "round": 0})]
    spans = sorted(spans + list(program), key=lambda s: s.t0)
    return Trace(ops=ops, modules=modules, spans=spans, window=(0.0, 100.0))


def _read(name, trace):
    run_ = run.LayerRun(rec=_recorder(), trace=trace, layer={}, peaks=PEAKS,
                        t0=0, t1=100)
    return run.load_module(run.BENCH / "metrics" / f"{name}.py").read(run_)


@pytest.mark.parametrize("name", READERS)
def test_readers_unchanged_by_program_spans(name):
    without = _read(name, _trace())
    assert without is not None
    assert _read(name, _trace(PROGRAM)) == without


def test_idle_gaps_named_by_the_program_span_around_them():
    # gaps 0-20 (midpoint 10), 30-60 (45) and 70-100 (85)
    assert dict(_trace().idle_gaps()) == pytest.approx(
        {"task": 50e-9, "host": 30e-9})
    gaps = dict(_trace(PROGRAM).idle_gaps())
    assert gaps == pytest.approx({"jita.host.summarize": 20e-9,
                                  "jita.device.kmeans": 30e-9,
                                  "jita.executor.wait": 30e-9})


def test_device_time_inside_program_spans():
    # the harness's window and spans stand as they were; device time is put
    # to a program span by the same arithmetic as to a harness span
    t = _trace(PROGRAM)
    assert t.window == (0.0, 100.0)
    assert [s.name for s in t.named("task")] == ["task", "task"]
    device = [s for s in t.spans if s.name == "jita.executor.task"
              and s.args["backend"] == "device"]
    assert t.busy_in(device) == 10.0
    assert t.busy_in([s for s in t.spans if s.name == "jita.device.kmeans"]) == 0.0
    assert len(t.module_events("jit_", device[0].t0, device[0].t1)) == 1

"""The program's spans (``jita.*``) in a profiler trace: the executor's task
and wait spans with their attributes and counter, the operators' host and
device spans nested in them, and the planner's spans; none changes what
the program computes or plans."""

import glob
import os
from urllib.parse import unquote

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.cost_model import CostModel
from repro.core.executor import Executor
from repro.core.online import OnlineDriver
from repro.core.resources import paper_pool
from repro.core.schedulers import schedule
from repro.core.spans import PREFIX
from repro.pipeline.workloads import ds_workload_executable


def _jita_events(directory):
    """Host events named ``jita.*`` in the trace written under
    ``directory``, as ``(name, start_ns, end_ns, args)`` in start order."""
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    args = {k: unquote(v) if isinstance(v, str) else v
                            for k, v in e.stats}
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                args))
    return sorted(out, key=lambda ev: ev[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def executed(tmp_path_factory):
    """The 16-task workload at 2048 rows, placed by EFT on the paper's pool
    (host and device tasks both), run untraced and then traced."""
    wl = ds_workload_executable()
    pool = paper_pool()
    sched = schedule(wl, pool, CostModel(), policy="eft")
    raw = np.random.default_rng(0).normal(0, 1, (2048, 8)).astype(np.float32)
    plain = Executor(pool).execute(wl, sched, inputs={"ingest": raw})
    directory = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(directory)):
        traced = Executor(pool).execute(wl, sched, inputs={"ingest": raw})
    return wl, raw, plain, traced, _jita_events(directory)


def test_one_task_span_per_executed_task(executed):
    _wl, _raw, _plain, rep, events = executed
    tasks = [ev for ev in events if ev[0] == "jita.executor.task"]
    assert sorted(ev[3]["task"] for ev in tasks) == sorted(r.task for r in rep.runs)
    by_task = {ev[3]["task"]: ev[3] for ev in tasks}
    for r in rep.runs:
        assert by_task[r.task]["op"] == r.op
        assert by_task[r.task]["pe"] == r.pe
        assert by_task[r.task]["backend"] == r.backend
    assert {r.backend for r in rep.runs} == {"host", "device"}


def test_operator_and_wait_spans_nest_in_the_task_span(executed):
    _wl, _raw, _plain, rep, events = executed
    for r in rep.runs:
        (task,) = [ev for ev in events if ev[0] == "jita.executor.task"
                   and ev[3]["task"] == r.task]
        ops = [ev for ev in events if ev[0] == f"jita.{r.backend}.{r.op}"
               and _inside(ev, task)]
        assert len(ops) == 1 and ops[0][3]["op"] == r.op
        waits = [ev for ev in events if ev[0] == "jita.executor.wait"
                 and ev[3]["task"] == r.task]
        assert len(waits) == 1 and _inside(waits[0], task)
        # the operator is issued before the executor waits for its result
        assert ops[0][2] <= waits[0][1]


def test_cross_bytes_counts_inputs_from_the_other_side(executed):
    wl, raw, _plain, rep, events = executed
    got = {ev[3]["task"]: ev[3]["cross_bytes"] for ev in events
           if ev[0] == "jita.executor.task"}
    placed = {r.task: r.backend for r in rep.runs}
    numpy_in_device = 0
    for r in rep.runs:
        args = [rep.outputs[p.name] for p in wl.predecessors(r.task)]
        if r.task == "ingest":
            args.append(raw)
        leaves = jax.tree_util.tree_leaves(args)
        if r.backend == "device":
            want = sum(x.nbytes for x in leaves if isinstance(x, (np.ndarray, np.generic)))
            numpy_in_device += want
        else:
            want = sum(x.nbytes for x in leaves if isinstance(x, jax.Array))
        assert got[r.task] == want, r.task
    # the placement sends host outputs to device tasks, so the count is live
    assert numpy_in_device > 0
    assert "device" in placed.values()


def test_traced_outputs_are_bitwise_identical(executed):
    _wl, _raw, plain, traced, _events = executed
    assert sorted(plain.outputs) == sorted(traced.outputs)
    for name in sorted(plain.outputs):
        a = jax.tree_util.tree_leaves(plain.outputs[name])
        b = jax.tree_util.tree_leaves(traced.outputs[name])
        assert len(a) == len(b)
        for x, y in zip(a, b):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and x.shape == y.shape
            assert x.tobytes() == y.tobytes(), name


def _plan(n):
    wl = ds_workload_executable()
    drv = OnlineDriver(paper_pool(), CostModel(), policy="eft")
    steps = 0
    for i in range(n):
        drv.submit(wl.instance(i), arrival_t=0.5 * i)
    while drv.step() is not None or drv.pending:
        steps += 1
    return drv, steps


def test_planner_spans_and_unchanged_schedule(tmp_path):
    plain, _ = _plan(3)
    with jax.profiler.trace(str(tmp_path)):
        traced, steps = _plan(3)
    events = _jita_events(tmp_path)
    submits = [ev[3] for ev in events if ev[0] == "jita.planner.submit"]
    assert [s["instance"] for s in submits] == [f"ds_workload#{i}" for i in range(3)]
    assert all(s["tasks"] == 16 for s in submits)
    # every call to step opens one span, the last (which finds nothing) too
    assert sum(ev[0] == "jita.planner.step" for ev in events) == steps + 1
    assert repr(traced.eng.assignments) == repr(plain.eng.assignments)
    assert traced.completions == plain.completions

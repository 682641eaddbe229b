"""Real execution of scheduled DAGs (the workload manager, live)."""

import numpy as np
import pytest

from repro.core.cost_model import CostModel, LearnedCostModel
from repro.core.executor import Executor
from repro.core.resources import paper_pool
from repro.core.schedulers import schedule
from repro.pipeline.workloads import ds_workload_executable


@pytest.fixture(scope="module")
def setup():
    wl = ds_workload_executable()
    pool = paper_pool()
    sched = schedule(wl, pool, CostModel(), policy="eft")
    raw = np.random.default_rng(0).normal(0, 1, (256, 8)).astype(np.float32)
    return wl, pool, sched, raw


def test_executes_all_tasks_with_finite_outputs(setup):
    wl, pool, sched, raw = setup
    rep = Executor(pool).execute(wl, sched, inputs={"ingest": raw})
    assert len(rep.runs) == 16
    digest = np.asarray(rep.outputs["export"])
    assert digest.shape == (3,) and np.isfinite(digest).all()
    # both tiers actually executed work (JITA disaggregation)
    assert rep.by_backend.get("host", 0) > 0
    assert rep.by_backend.get("device", 0) > 0


def test_host_device_end_to_end_parity(setup):
    wl, pool, sched, raw = setup
    host = Executor(pool, backend_of=lambda pe: "host")
    dev = Executor(pool, backend_of=lambda pe: "device")
    r_h = host.execute(wl, sched, inputs={"ingest": raw})
    r_d = dev.execute(wl, sched, inputs={"ingest": raw})
    a = np.asarray(r_h.outputs["export"])
    b = np.asarray(r_d.outputs["export"])
    np.testing.assert_allclose(a, b, rtol=2e-3)


def test_execution_feeds_learned_cost_model(setup):
    wl, pool, sched, raw = setup
    learned = LearnedCostModel(min_samples=1)
    Executor(pool, learn_into=learned).execute(wl, sched, inputs={"ingest": raw})
    assert learned._obs  # observations recorded per (family, kind)


def test_zero_duration_predecessor_executes_before_successor():
    """Regression: execute() ordered by (start, task_name); a zero-cost
    predecessor sharing its successor's start time but sorting *after* it
    by name crashed on the missing predecessor output. Ties now break by
    topological order."""
    from repro.core.dag import PipelineDAG, Task
    g = PipelineDAG("zerocost")
    # work=0 → exec_time 0 → 'z_head' finishes the instant it starts, and
    # its successor 'a_tail' starts at the same timestamp; "a_tail" < "z_head"
    # by name, so the old sort ran the successor first
    heads = {"host": lambda: np.float32(3.0)}
    g.add_task(Task("z_head", "ingest", work=0.0, out_bytes=0.0, backends=heads))
    g.add_task(Task("a_tail", "export", work=1.0, backends={"host": lambda x: x * 2}))
    g.add_edge("z_head", "a_tail")
    pool = paper_pool(n_arm=1, n_volta=0, n_xeon=0, n_v100=0, n_alveo=0)
    sched = schedule(g, pool, CostModel(), policy="eft")
    a_by = {a.task: a for a in sched.assignments}
    assert a_by["z_head"].start == a_by["a_tail"].start  # the tie is real
    rep = Executor(pool).execute(g, sched)
    assert [r.task for r in rep.runs] == ["z_head", "a_tail"]
    assert float(rep.outputs["a_tail"]) == 6.0


def _one_task_dag(backends):
    from repro.core.dag import PipelineDAG, Task
    g = PipelineDAG("one")
    g.add_task(Task("t", "sql_transform", work=1.0, backends=backends))
    return g


def test_missing_device_backend_raises():
    """A device PE whose task has no device backend is an error, not a
    silent run on the host."""
    pool = paper_pool(n_arm=0, n_volta=0, n_xeon=1, n_v100=0, n_alveo=0)
    g = _one_task_dag({"host": lambda: np.float32(1.0)})
    sched = schedule(g, pool, CostModel(), policy="eft")
    with pytest.raises(ValueError, match="needs backend 'device'"):
        Executor(pool, backend_of=lambda pe: "device").execute(g, sched)


def test_device_error_at_block_until_ready_propagates():
    """With async dispatch a device fault surfaces when the result is
    awaited; the executor must not swallow it there."""

    class _FaultsOnSync:
        def block_until_ready(self):
            raise RuntimeError("device fault")

    pool = paper_pool(n_arm=0, n_volta=0, n_xeon=1, n_v100=0, n_alveo=0)
    g = _one_task_dag({"device": lambda: _FaultsOnSync()})
    sched = schedule(g, pool, CostModel(), policy="eft")
    with pytest.raises(RuntimeError, match="device fault"):
        Executor(pool, backend_of=lambda pe: "device").execute(g, sched)

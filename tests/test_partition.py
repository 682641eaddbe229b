"""Site-level fault domains: WAN partitions, heal, site loss, rejoin.

Partition is *pricing, not surgery*: the far site's horizons are raised
to its quarantine deadline, so reachable-side work keeps flowing
(degraded mode) and cross-partition work is deferred, not cancelled. A
heal inside the window restores the floors with zero recompute; a late
heal escalates to the PR-6 lost-work path. Every scenario must stay
byte-identical to ``restart_from_history`` with the durable record — now
including the horizon-event log.
"""

import numpy as np
import pytest

from repro.core.cost_model import CostModel
from repro.core.dag import PipelineDAG, Task
from repro.core.executor import Executor
from repro.core.federation import paper_federation
from repro.core.online import OnlineDriver, restart_from_history
from repro.core.resources import (BACKEND, FRONTEND, Link, ResourcePool,
                                  paper_pool)
from repro.core.schedulers import POLICIES, Assignment, Schedule
from repro.core.vos import ValueCurve
from repro.train.fault_tolerance import FailureEvent, FailureInjector
from repro.pipeline.workloads import ds_workload


def _tuples(sched):
    return [(a.task, a.op, a.pe, a.start, a.finish, a.comm_wait, a.energy)
            for a in sched.assignments]


def _template(seed: int, n: int = 8) -> PipelineDAG:
    rng = np.random.default_rng(seed)
    ops = ["ingest", "sql_transform", "kmeans", "summarize", "window_agg",
           "linreg", "anomaly", "export"]
    g = PipelineDAG(f"part{seed}")
    for i in range(n):
        g.add_task(Task(f"t{i}", str(rng.choice(ops)),
                        work=float(rng.uniform(0.5, 12)),
                        out_bytes=float(rng.uniform(0, 3e6)),
                        in_bytes=float(rng.uniform(0, 6e6)) if i == 0 else 0))
    for i in range(1, n):
        for j in rng.choice(i, size=min(i, 2), replace=False):
            g.add_edge(f"t{j}", f"t{i}")
    return g


def _driver(policy="eft", n=4, period=4.0, seed=0):
    fed = paper_federation()
    cost = CostModel(data_home=fed.data_home)
    drv = OnlineDriver(fed, cost, policy=policy)
    wl = _template(seed)
    for i in range(n):
        drv.submit(wl.instance(i), arrival_t=i * period)
    return drv, fed, cost


def _record(drv):
    return dict(
        history=list(drv.eng.assignments),
        admitted=[(inst.dag, inst.arrival) for inst in drv.instances],
        pending=drv.pending_submissions(),
        loc_of=dict(drv._loc_of),
        retry_floors=dict(drv.retry_floors),
        cancelled=list(drv.cancelled_instances),
        horizon_events=list(drv.horizon_events),
    )


def _restart(drv, cost, policy, rec, **kw):
    return restart_from_history(
        drv.pool, cost, policy, rec["admitted"], rec["history"],
        rec["pending"], rec["loc_of"], retry_floors=rec["retry_floors"],
        cancelled=rec["cancelled"], horizon_events=rec["horizon_events"],
        **kw)


# ---------------------------------------------------------------------------
# Degraded mode + trusted heal
# ---------------------------------------------------------------------------

def test_partition_defers_dc_work_and_trusted_heal_recomputes_nothing():
    drv, fed, cost = _driver()
    for _ in range(6):
        drv.step()
    t = max(a.start for a in drv.eng.assignments)
    n_before = len(drv.eng.assignments)
    rep = drv.partition(t, "dc")
    assert rep.site == "dc" and rep.unreachable == ("dc",)
    assert rep.deadline == t + drv.site_backoff.base
    dc_pes = set(fed.site("dc").pe_names)
    assert set(rep.floored_pes) <= dc_pes
    assert all(lk[0] in (FRONTEND, BACKEND) for lk in rep.floored_links)
    # degraded mode: the engine keeps placing; nothing lands on the far
    # side before the deadline
    for _ in range(8):
        if drv.step() is None:
            break
    for a in drv.eng.assignments[n_before:]:
        if a.pe in dc_pes:
            assert a.start >= rep.deadline - 1e-9
    assert drv.heal(t + 5.0, "dc") is None  # inside the window: trusted
    sched = drv.run()
    names = [a.task for a in sched.assignments]
    assert len(names) == len(set(names))  # nothing recomputed
    assert len(names) == sum(inst.n_tasks for inst in drv.instances)
    assert len(drv.recoveries) == 0


@pytest.mark.parametrize("policy", POLICIES)
def test_partition_restart_differential_mid_partition(policy):
    """Snapshot while the cut is live: the raise event must replay.

    Post-event placements put the event strictly *inside* the replayed
    history (the segmented-replay case) — except for rr, whose PE cycle
    is positional: as for repool/fail, its restart differential is pinned
    at rebind points (snapshot straight after the event)."""
    drv, fed, cost = _driver(policy=policy)
    for _ in range(5):
        drv.step()
    t = max(a.start for a in drv.eng.assignments)
    drv.partition(t, "dc")
    for _ in range(0 if policy == "rr" else 4):
        drv.step()
    rec = _record(drv)
    sched_a = drv.run()
    drv_b = _restart(drv, cost, policy, rec)
    assert _tuples(sched_a) == _tuples(drv_b.run())


@pytest.mark.parametrize("policy", POLICIES)
def test_partition_restart_differential_after_heal(policy):
    """Snapshot after the heal: raise + restore events must replay, in
    the recorded inter-booking positions."""
    drv, fed, cost = _driver(policy=policy)
    for _ in range(5):
        drv.step()
    t = max(a.start for a in drv.eng.assignments)
    drv.partition(t, "dc")
    for _ in range(0 if policy == "rr" else 3):
        drv.step()
    drv.heal(t + 10.0, "dc")
    for _ in range(0 if policy == "rr" else 3):
        drv.step()
    rec = _record(drv)
    sched_a = drv.run()
    drv_b = _restart(drv, cost, policy, rec)
    assert _tuples(sched_a) == _tuples(drv_b.run())


def test_late_heal_escalates_to_lost_work_path():
    drv, fed, cost = _driver()
    for _ in range(8):
        drv.step()
    t = max(a.start for a in drv.eng.assignments)
    rep = drv.partition(t, "dc")
    for _ in range(4):
        drv.step()
    late = rep.deadline + 100.0
    rec_rep = drv.heal(late, "dc")
    assert rec_rep is not None  # escalated: far-side outputs distrusted
    assert rec_rep.t == late and not rec_rep.dead_pes or rec_rep.dead_pes
    # the site is physically present: its PEs rejoined immediately
    assert {p.name for p in drv.pool.pes} >= set(fed.site("dc").pe_names)
    sched = drv.run()
    names = [a.task for a in sched.assignments]
    cancelled = set(drv.cancelled_instances)
    expected = sum(inst.n_tasks for inst in drv.instances
                   if inst.name not in cancelled)
    assert len(names) == len(set(names)) == expected
    # differential still holds after the whole sequence
    rec = _record(drv)
    drv_b = _restart(drv, cost, "eft", rec)
    # both fully drained: the record equals the final schedule
    assert _tuples(drv_b.run()) == _tuples(sched)


def test_repeat_partitions_back_off_exponentially():
    drv, fed, cost = _driver()
    for _ in range(4):
        drv.step()
    r1 = drv.partition(10.0, "dc")
    assert r1.deadline == 10.0 + 30.0
    drv.heal(12.0, "dc")
    r2 = drv.partition(20.0, "dc")
    assert r2.deadline == 20.0 + 60.0  # second flap: window doubles


# ---------------------------------------------------------------------------
# Site loss + rejoin
# ---------------------------------------------------------------------------

def test_fail_site_drops_pes_and_wan_links():
    drv, fed, cost = _driver()
    for _ in range(6):
        drv.step()
    t = max(a.start for a in drv.eng.assignments)
    rep = drv.fail_site(t, "dc", shed=1)
    assert set(rep.dead_pes) == set(fed.site("dc").pe_names)
    assert {p.name for p in drv.pool.pes} == set(fed.site("edge").pe_names)
    assert drv.pool._links == {}  # WAN attachments left with the site
    assert len(rep.shed) == 1
    # quarantine refuses an early rejoin wholesale
    acc, refused = drv.rejoin_site(t + 1.0, "dc")
    assert acc == [] and set(refused) == set(fed.site("dc").pe_names)
    # past the window the whole site (PEs + uplink) returns in one repool
    acc, refused = drv.rejoin_site(t + 31.0, "dc")
    assert set(acc) == set(fed.site("dc").pe_names) and refused == []
    assert set(drv.pool._links) == {(FRONTEND, BACKEND), (BACKEND, FRONTEND)}
    sched = drv.run()
    names = [a.task for a in sched.assignments]
    assert len(names) == len(set(names))


def test_fail_site_restart_differential():
    policy = "etf"
    drv, fed, cost = _driver(policy=policy)
    for _ in range(7):
        drv.step()
    t = max(a.start for a in drv.eng.assignments)
    drv.fail_site(t, "dc")
    for _ in range(3):
        drv.step()
    rec = _record(drv)
    sched_a = drv.run()
    # the restart re-plans on the reachable sub-topology: the surviving
    # pool equals fed.sub_pool(["edge"]) by construction
    sub = fed.sub_pool(["edge"])
    assert {p.name for p in drv.pool.pes} == {p.name for p in sub.pes}
    assert set(drv.pool._links) == set(sub._links)
    drv_b = _restart(drv, cost, policy, rec)
    assert _tuples(sched_a) == _tuples(drv_b.run())


def test_partitioned_site_dying_dissolves_the_cut():
    drv, fed, cost = _driver()
    for _ in range(4):
        drv.step()
    drv.partition(5.0, "dc")
    drv.fail_site(6.0, "dc")  # the dark site was actually dead
    assert drv._cut == set()
    with pytest.raises(ValueError, match="not partitioned"):
        drv.heal(7.0, "dc")
    drv.rejoin_site(6.0 + 30.0 * 2 + 1, "dc")  # 2nd site failure: 60 s window
    sched = drv.run()
    names = [a.task for a in sched.assignments]
    cancelled = set(drv.cancelled_instances)
    expected = sum(inst.n_tasks for inst in drv.instances
                   if inst.name not in cancelled)
    assert len(names) == len(set(names)) == expected


def test_site_event_guards():
    drv, fed, cost = _driver()
    with pytest.raises(ValueError, match="home site"):
        drv.partition(0.0, "edge")
    with pytest.raises(ValueError, match="unknown site"):
        drv.partition(0.0, "mars")
    with pytest.raises(ValueError, match="not partitioned"):
        drv.heal(0.0, "dc")
    drv.partition(1.0, "dc")
    with pytest.raises(ValueError, match="already partitioned"):
        drv.partition(2.0, "dc")
    drv.heal(3.0, "dc")
    with pytest.raises(ValueError, match="cannot fail the home"):
        drv.fail_site(4.0, "edge")
    with pytest.raises(ValueError, match="not down"):
        drv.rejoin_site(4.0, "dc")
    drv.fail_site(5.0, "dc")
    with pytest.raises(ValueError, match="already down"):
        drv.fail_site(6.0, "dc")
    with pytest.raises(ValueError, match="is down"):
        drv.partition(6.0, "dc")
    flat = OnlineDriver(paper_pool(), CostModel())
    with pytest.raises(ValueError, match="FederatedPool"):
        flat.partition(0.0, "dc")


def test_rejoin_link_only_fragment_regression():
    """A fragment with zero PEs but a new link must still repool — a WAN
    uplink healing on its own used to be silently dropped."""
    drv = OnlineDriver(paper_pool(), CostModel())
    frag = ResourcePool([], [Link(FRONTEND, "relay", 1e9),
                             Link("relay", FRONTEND, 1e9)])
    acc, refused = drv.rejoin(0.0, frag)
    assert acc == [] and refused == []
    assert (FRONTEND, "relay") in drv.pool._links
    assert ("relay", FRONTEND) in drv.pool._links
    # idempotent: re-offering the same links does not repool again
    pool_before = drv.pool
    drv.rejoin(1.0, frag)
    assert drv.pool is pool_before


# ---------------------------------------------------------------------------
# Executor: a real two-site run through a partition
# ---------------------------------------------------------------------------

def test_executor_partition_recomputes_only_cross_partition_subgraph():
    """Both sides keep executing what they can reach while the cut holds;
    a resume after the heal recomputes exactly the skipped cross-partition
    subgraph."""
    pool = paper_pool(n_arm=1, n_volta=0, n_xeon=1, n_v100=0, n_alveo=0)
    g = PipelineDAG("twosite")

    def add(name, fn, *preds):
        g.add_task(Task(name, "sql_transform", work=1.0,
                        backends={"host": fn}))
        for p in preds:
            g.add_edge(p, name)

    add("e0", lambda: np.float32(1.0))
    add("d0", lambda x: x + 1, "e0")            # dc consumes edge output
    add("e1", lambda x: x * 2, "e0")            # edge-local
    add("d1", lambda x: x * 10, "d0")           # dc-local
    add("e2", lambda x: x - 1, "d0")            # cross-partition: blocked
    add("d2", lambda x: x * 3, "e2")            # downstream of the block
    add("e3", lambda x: x + 5, "e1")            # edge-local, post-heal
    asg = [Assignment("e0", "sql_transform", "arm0", 0, 1, 0, 0),
           Assignment("d0", "sql_transform", "xeon0", 1, 2, 0, 0),
           Assignment("e1", "sql_transform", "arm0", 2, 3, 0, 0),
           Assignment("d1", "sql_transform", "xeon0", 3, 4, 0, 0),
           Assignment("e2", "sql_transform", "arm0", 4, 5, 0, 0),
           Assignment("d2", "sql_transform", "xeon0", 5, 6, 0, 0),
           Assignment("e3", "sql_transform", "arm0", 6, 7, 0, 0)]
    sched = Schedule(asg, pool, "manual")
    inj = FailureInjector([FailureEvent(2, "xeon0", "partition"),
                           FailureEvent(6, "xeon0", "heal")])
    ex = Executor(pool, backend_of=lambda pe: "host")  # host-only tasks
    rep1 = ex.execute(g, sched, injector=inj)
    # degraded mode: edge-local AND dc-local work both executed mid-cut
    assert [r.task for r in rep1.runs] == ["e0", "d0", "e1", "d1", "e3"]
    assert rep1.skipped == ["e2", "d2"]
    assert rep1.lost == [] and rep1.dead == []  # a cut loses nothing
    # resume after the heal: exactly the cross-partition subgraph reruns
    rep2 = ex.execute(g, sched, resume_from=rep1)
    assert [r.task for r in rep2.runs] == ["e2", "d2"]
    assert rep2.complete(g)
    assert float(rep2.outputs["d2"]) == float((1 + 1 - 1) * 3)
    assert float(rep2.outputs["e3"]) == float(1 * 2 + 5)


# ---------------------------------------------------------------------------
# Value curves across a partition deferral
# ---------------------------------------------------------------------------

def test_deferred_instance_readmits_at_time_shifted_value_floor():
    fed = paper_federation()
    cost = CostModel(data_home=fed.data_home)
    wl = ds_workload()
    curve = ValueCurve.linear_decay(30.0, 120.0, value=4.0)
    drv = OnlineDriver(fed, cost, policy="vos")
    drv.submit(wl.instance(0), arrival_t=0.0)
    for _ in range(6):
        drv.step()
    late = wl.instance(1)
    drv.submit(late, arrival_t=20.0, curve=curve)
    rep = drv.partition(8.0, "dc", defer="all")
    assert rep.deferred == (late.name,)
    deadline = rep.deadline
    assert drv.pending_submissions() == [(late, deadline)]
    # the floor the gate now sees is the *time-shifted* one
    shifted = drv.policy.arrival_floor(deadline, late)
    assert shifted == -curve.value(deadline)
    assert shifted > drv.policy.arrival_floor(20.0, late)  # value decayed
    # differential: a rebuilt driver given the shifted arrival + the same
    # curve map drains byte-identically
    rec = _record(drv)
    sched_a = drv.run()
    drv_b = _restart(drv, cost, "vos", rec, curves=drv.slo_curves())
    assert _tuples(sched_a) == _tuples(drv_b.run())


def test_heal_before_arrival_restores_original_schedule():
    """Partition + heal while a deferred instance had not yet arrived is
    a no-op: the drain is byte-identical to an undisturbed driver."""
    fed = paper_federation()
    cost = CostModel(data_home=fed.data_home)
    wl = ds_workload()
    curve = ValueCurve.linear_decay(40.0, 100.0, value=2.0)

    def mk():
        d = OnlineDriver(fed, cost, policy="vos")
        d.submit(wl.instance(0), arrival_t=0.0)
        for _ in range(4):
            d.step()
        d.submit(wl.instance(1), arrival_t=20.0, curve=curve)
        return d

    drv = mk()
    drv.partition(8.0, "dc", defer="all")
    drv.heal(10.0, "dc")  # heals before the deferred arrival (20 > 10)
    assert drv.pending_submissions()[0][1] == 20.0  # original arrival back
    assert _tuples(drv.run()) == _tuples(mk().run())

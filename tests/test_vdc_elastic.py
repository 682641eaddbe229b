"""VDC composition, elastic planning, health monitoring."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax

from repro.core.vdc import SLO, AllocationError, VDCManager
from repro.core import elastic as el


def test_vdc_compose_release_cycle():
    mgr = VDCManager()
    assert mgr.free_chips == mgr.total_chips == 1
    v = mgr.compose("a", {"data": 1, "model": 1})
    assert mgr.free_chips == 0
    assert v.axis_sizes == {"data": 1, "model": 1}
    with pytest.raises(AllocationError):
        mgr.compose("b", {"data": 1})
    with pytest.raises(AllocationError):
        mgr.compose("a", {"data": 1})  # duplicate even if free
    mgr.release("a")
    assert mgr.free_chips == 1


def test_vdc_slo_sizing_roofline():
    mgr = VDCManager(devices=list(jax.devices()) * 64)  # fake pool of 64
    slo = SLO(step_deadline_s=0.5)
    # 1e15 flops: needs ≥ ~11 chips at 197 TF/s... sized to power of two
    chips, terms = mgr.size_for_slo(slo, step_flops=1e15,
                                    step_hbm_bytes=1e11)
    assert terms.step_time <= 0.5
    assert chips <= 64
    # energy budget caps the size
    slo2 = SLO(step_deadline_s=1e-9, energy_budget_w=250 * 4)
    chips2, _ = mgr.size_for_slo(slo2, step_flops=1e15, step_hbm_bytes=1e11)
    assert chips2 <= 4


@settings(max_examples=50, deadline=None)
@given(devices=st.integers(1, 4096), model=st.integers(1, 64),
       cur=st.integers(1, 64))
def test_plan_remesh_properties(devices, model, cur):
    if devices < model:
        with pytest.raises(ValueError):
            el.plan_remesh(devices, model, cur)
        return
    plan = el.plan_remesh(devices, model, cur)
    assert plan.mesh_shape["model"] == model          # model axis preserved
    assert plan.n_devices <= devices                  # never oversubscribe
    assert plan.mesh_shape["data"] >= 1
    # uses as many devices as divisibility allows
    assert plan.n_devices > devices - model


@settings(max_examples=50, deadline=None)
@given(gb=st.integers(1, 4096), axis=st.integers(1, 64))
def test_rebalance_batch_properties(gb, axis):
    per, padded = el.rebalance_batch(gb, axis)
    assert per * axis == padded
    assert padded >= gb
    assert padded - gb < axis                         # minimal padding


def test_health_monitor_straggler_and_death():
    mon = el.HealthMonitor(["a", "b", "c", "d"], patience=2,
                           heartbeat_timeout=10.0)
    for step in range(4):
        for w in "abcd":
            mon.observe(w, 2.5 if w == "d" else 1.0, now=float(step))
        s = mon.stragglers()
    assert s == ["d"]
    mon.mark_dead("d")
    assert mon.healthy() == ["a", "b", "c"]
    assert mon.dead(now=100.0) == ["a", "b", "c"]     # all silent now


def test_health_monitor_repeated_polls_do_not_double_strike():
    """Regression: stragglers() used to mutate strike counts on every
    *call*, so polling twice between observations fired before ``patience``
    real observations. Strikes are accounted per observation, in
    observe()."""
    mon = el.HealthMonitor(["a", "b", "c"], patience=3)
    for w in "abc":
        mon.observe(w, 3.0 if w == "c" else 1.0, now=0.0)
    # one observation, many polls: far fewer than patience observations
    for _ in range(10):
        assert mon.stragglers() == []
    # two more slow observations reach patience=3 — exactly then it fires,
    # no matter how often the monitor was polled in between
    mon.observe("c", 3.0, now=1.0)
    assert mon.stragglers() == []
    assert mon.stragglers() == []
    mon.observe("c", 3.0, now=2.0)
    assert mon.stragglers() == ["c"]
    # healthy observations decay the EWMA below threshold → streak resets
    for k in range(3):
        mon.observe("c", 0.1, now=3.0 + k)
    assert mon.stragglers() == []


def test_health_monitor_batched_observations_still_flag():
    """Dual regression (of the double-count fix): observations arriving in
    batches between polls must each count toward ``patience`` — a worker
    slow for >= patience consecutive observations is flagged on the next
    poll no matter how sparsely the monitor is polled."""
    mon = el.HealthMonitor(["a", "b", "c"], patience=3)
    for step in range(5):
        for w in "abc":
            mon.observe(w, 3.0 if w == "c" else 1.0, now=float(step))
    # no poll happened during the 5 slow observations
    assert mon.stragglers() == ["c"]


def test_vdc_resize_rolls_back_on_failure():
    """Regression: resize released the VDC before composing the new shape,
    so a failed grow destroyed the original VDC and its mesh. Resize must
    be atomic — on failure the original allocation is fully restored."""
    mgr = VDCManager(devices=list(jax.devices()) * 8)
    a = mgr.compose("a", {"data": 4, "model": 1})
    mgr.compose("b", {"data": 3, "model": 1})
    assert mgr.free_chips == 1
    with pytest.raises(AllocationError):
        mgr.resize("a", {"data": 6, "model": 1})  # needs 6, only 4+1 free
    assert mgr.vdc("a") is a                       # original VDC restored
    assert a.n_chips == 4 and mgr.free_chips == 1  # allocation unchanged
    with a:                                        # mesh still usable
        pass
    # a feasible resize (reusing its own chips) still works afterwards
    a2 = mgr.resize("a", {"data": 5, "model": 1})
    assert a2.n_chips == 5 and mgr.free_chips == 0


def test_vdc_availability_reserve_enforced_after_allocation():
    """Regression: the reserve check credited already-allocated chips
    against the reserve, shrinking it to zero as the pool filled. The
    reserve is spare capacity that must stay *free after* every compose."""
    mgr = VDCManager(devices=list(jax.devices()) * 10)
    slo = SLO(min_availability=0.2)                # reserve = 2 of 10
    with pytest.raises(AllocationError):
        mgr.compose("too_big", {"data": 9}, slo=slo)
    mgr.compose("a", {"data": 5}, slo=slo)         # 5 free >= 2 reserve
    mgr.compose("b", {"data": 3}, slo=slo)         # boundary: 2 free == 2
    assert mgr.free_chips == 2
    with pytest.raises(AllocationError):
        # old (buggy) accounting: reserve - (total - avail) = 2 - 8 < 0,
        # so this allocation used to be admitted, leaving 1 < reserve free
        mgr.compose("c", {"data": 1}, slo=slo)
    assert mgr.free_chips == 2                     # failed compose is a no-op


def test_reshard_on_current_devices():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = {"w": np.ones((4, 4), np.float32)}
    out = el.reshard(tree, mesh, lambda leaf: P())
    assert np.asarray(out["w"]).sum() == 16


def test_prune_pool_also_drops_stragglers():
    """prune_pool(also_drop=monitor.stragglers()) rotates slow-but-alive
    workers out of the pool alongside the dead ones."""
    from repro.core.resources import paper_pool
    pool = paper_pool()
    mon = el.HealthMonitor([p.name for p in pool.pes])
    for p in pool.pes:
        for _ in range(4):
            mon.observe(p.name, step_s=10.0 if p.name == "xeon1" else 1.0,
                        now=1.0)
    assert mon.stragglers() == ["xeon1"]
    pruned = el.prune_pool(pool, mon, also_drop=mon.stragglers())
    names = {p.name for p in pruned.pes}
    assert "xeon1" not in names
    assert len(names) == len(pool.pes) - 1

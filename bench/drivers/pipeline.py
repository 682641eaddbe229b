"""Driver for configurations of kind ``pipeline``: DS pipeline instances.

The system under test is the repository's online path for the paper's
workload: ``OnlineDriver`` (the planner) places each instance's tasks on
the configured pool, and the ``Executor`` runs them, edge-placed tasks on
the host (numpy) and DC-placed tasks on the TPU (``pipeline/operators.py``).

The harness wraps each task's backends to record a host span per task
(it never waits for device work itself: the executor does, or not, as it
chooses), and records a span around each call into the planner.

Traffic ``closed``: ``in_flight`` sources, each submitting its next raw
batch to the planner as soon as its previous instance exports, with the
arrival set to the planner's own finish time of that instance. The
in-flight instances go to the executor together, as one merged DAG with
their planned placements, so the executor orders (and may overlap) the
tasks of all of them; a source's next instance is planned when the round
has run. ``pipelines_per_s`` counts every task whose span ended inside the
window by its share of the DAG's work units.

Correct: after the window, every task output of a sample of the window's
instances (drawn from the seed, with one instance of each distinct batch)
is compared with ``bench/reference/<config>.py`` run in float64 on the host.
"""

from __future__ import annotations

import sys
import time
import traceback
from typing import Any, Dict, List, Tuple

import numpy as np

import gen
import work
from spans import Recorder

# ---------------------------------------------------------------------------
# comparison with the reference
# ---------------------------------------------------------------------------


def _np(x: Any) -> Any:
    if isinstance(x, dict):
        return {k: _np(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return type(x)(_np(v) for v in x)
    return np.asarray(x)


def _err(got, ref, scale: float) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(got - ref), initial=0.0) / max(scale, 1e-30))


def _amax(x) -> float:
    return float(np.max(np.abs(np.asarray(x, np.float64)), initial=0.0))


def _assign_flips(x, cent_ref, cent_got, a_got, a_ref, tie: float) -> int:
    """Assignments that differ from the reference's beyond a near-tie.

    With the program's centroids within ``delta`` of the reference's, a
    point may go to another centroid only where the reference's own
    distances to the two differ by at most ``2 delta`` (plus ``tie``
    relative): the centroids themselves are held by their error check."""
    idx = np.flatnonzero(np.asarray(a_got) != np.asarray(a_ref))
    if not idx.size:
        return 0
    c = np.asarray(cent_ref, np.float64)
    cg = np.asarray(cent_got, np.float64)
    if cg.shape != c.shape:
        return int(idx.size)
    delta = float(np.sqrt(((cg - c) ** 2).sum(-1)).max())
    x = np.asarray(x, np.float64)[idx]
    d = np.sqrt(((x[:, None, :] - c[None]) ** 2).sum(-1))
    r = np.arange(idx.size)
    dg, dr = d[r, np.asarray(a_got)[idx]], d[r, np.asarray(a_ref)[idx]]
    return int(np.sum(dg - dr > 2 * delta + tie * np.maximum(dr, 1.0)))


def _flag_flips(got, ref, margin, tie: float) -> int:
    """0/1 flags that differ from the reference's where the reference's
    relative margin to the threshold is wider than ``tie``."""
    diff = np.asarray(got) != np.asarray(ref)
    return int(np.sum(diff & (np.abs(margin) > tie)))


def compare(got: Dict[str, Any], ref: Dict[str, Any], tie: float,
            settled: float) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Per output of one instance: the relative error of each continuous
    output, and the discrete decisions (assignments, flags, the sweep's k)
    that differ from the reference's beyond a near-tie. Each continuous
    output is scaled by the magnitude of the quantity it is computed from,
    so that values that cancel to about 0 (the intercept, the correlation of
    two principal components) are judged in the units of their inputs.

    The sweep's centroids are judged in units of the reference's own last
    Lloyd pass: where that pass moved them by more than ``settled``
    (relative to its input's magnitude), their error is divided by
    ``step / settled``. Where the iteration is still in mid-move after its
    last pass, a difference in the last digit of its input moves the result
    by that much more, in the program and in the reference alike."""
    err: Dict[str, float] = {}
    bad: Dict[str, int] = {}
    x_scale = _amax(ref["clean_missing"])
    for t in ("ingest", "sql_transform"):
        ok = np.isfinite(ref[t])
        err[t] = _err(np.where(ok, got[t], 0), np.where(ok, ref[t], 0), x_scale)
        bad[t] = int(np.sum(np.isfinite(np.asarray(got[t])) != ok))
    for t in ("clean_missing", "select_columns", "summarize", "window_agg"):
        err[t] = _err(got[t], ref[t], _amax(ref[t]))
    margin = ref["anomaly_margin"]
    bad["anomaly"] = _flag_flips(got["anomaly"], ref["anomaly"], margin, tie)
    f_ref = ref["filter_features"]["x"]
    err["filter_features"] = _err(got["filter_features"]["x"], f_ref, _amax(f_ref))
    p_ref = ref["pca"]["x"]
    err["pca"] = _err(got["pca"]["x"], p_ref, _amax(p_ref))
    for t in ("kmeans", "train_cluster"):
        c, a, inertia = ref[t]["fit"]
        gc, ga, gi = got[t]["fit"]
        err[f"{t}.centroids"] = _err(gc, c, _amax(ref[t]["x"]))
        err[f"{t}.inertia"] = _err(gi, inertia, abs(float(inertia)))
        bad[t] = _assign_flips(ref[t]["x"], c, gc, ga, a, tie)
    c, a, k = ref["sweep_clustering"]["fit"]
    gc, ga, gk = got["sweep_clustering"]["fit"]
    if int(gk) != int(k):
        bad["sweep_clustering.k"] = 1
    else:
        err["sweep_clustering.centroids"] = (
            _err(gc, c, _amax(p_ref)) / max(1.0, ref["sweep_step"] / settled))
        bad["sweep_clustering"] = _assign_flips(p_ref, c, gc, ga, a, tie)
    y_std = float(np.std(np.asarray(p_ref, np.float64)[:, 0]))
    w, b = ref["linreg"]["model"]
    gw, gb = got["linreg"]["model"]
    err["linreg.w"] = _err(gw, w, 1.0)
    err["linreg.b"] = _err(gb, b, y_std)
    pred, mse, r2 = ref["score"]
    gpred, gmse, gr2 = got["score"]
    err["score.pred"] = _err(gpred, pred, y_std)
    err["score.mse"] = _err(gmse, mse, y_std**2)
    err["score.r2"] = _err(gr2, r2, 1.0)
    j, gj = np.asarray(ref["join"]), np.asarray(got["join"])
    nf = np.asarray(ref["anomaly"]).shape[1]
    lo = j.shape[1] - nf - 1
    cont = [c for c in range(j.shape[1]) if not lo <= c < lo + nf]
    err["join"] = _err(gj[:, cont], j[:, cont], _amax(j[:, cont]))
    bad["join"] = _flag_flips(gj[:, lo:lo + nf], j[:, lo:lo + nf], margin, tie)
    e, ge = np.asarray(ref["export"]), np.asarray(got["export"])
    bad["export.count"] = int(ge[0] != e[0])
    err["export.mean"] = _err(ge[1], e[1], e[2] / np.sqrt(e[0]))
    err["export.l2"] = _err(ge[2], e[2], e[2])
    return err, bad


def reference_outputs(ref_mod, raw: np.ndarray, ops: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The float64 reference of one raw batch, plus the anomaly flags'
    relative margins for the near-tie rule and the size of the sweep's last
    Lloyd pass (``sweep_step``, see :func:`compare`).

    A flag compares ``|x - mean|`` with ``z`` standard deviations of a short
    window. The deviation comes from ``E[x^2] - mean^2``, so rounding moves
    it by about the rounding of ``E[x^2]`` over ``2 sd``, which is large
    where the window barely varies. The margin is taken relative to the
    magnitudes that enter it, so that a flag decided by rounding alone is a
    near-tie."""
    out = ref_mod.pipeline(raw, ops, np, np.float64, np.matmul)
    wa = out["window_agg"]
    an = ops["anomaly"]
    w = an["window"]
    mu = ref_mod._causal_mean(np, wa, w)
    ex2 = ref_mod._causal_mean(np, wa * wa, w)
    sd = np.sqrt(np.maximum(ex2 - mu * mu, 1e-12))
    scale = np.abs(wa) + np.abs(mu) + an["z"] * ex2 / (2 * sd)
    out["anomaly_margin"] = (np.abs(wa - mu) - an["z"] * sd) / scale
    sw = ops["sweep_clustering"]
    p = out["pca"]["x"]
    c, _a, k = out["sweep_clustering"]["fit"]
    before = ref_mod._kmeans(np, np.matmul, p, int(k), sw["iters"] - 1)[0]
    out["sweep_step"] = _err(c, before, _amax(p))
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def _wrap(rec, fn, task: str, backend: str, op: str, share: float,
          op_params: Dict[str, Any], state: Dict[str, Any]):
    """The task's backend inside a host span. The span ends when the
    backend returns: for a device task that is when its work is enqueued,
    and whatever the executor then waits for is its own affair."""

    def call(*args, **kw):
        with rec.span("task", task=task, backend=backend,
                      round=state["round"]) as attrs:
            out = fn(*args, **kw)
        attrs["share"] = share
        if backend == "device":
            attrs["flops"], attrs["bytes"] = work.ds_task_work(
                op, op_params, args, out)
        return out

    return call


def build(ctx):
    """The program objects of one configuration, with the harness's
    spans around each task."""
    conf = ctx.cell.config
    import repro.core.cost_model as cost_model
    import repro.core.dag as dag_mod
    import repro.core.executor as executor
    import repro.core.online as online
    import repro.core.resources as resources
    import repro.core.schedulers as schedulers
    import repro.pipeline.workloads as workloads

    wl = workloads.ds_workload_executable(raw_mb=conf["raw_mb"])
    total = sum(t.work for t in wl.tasks)
    state: Dict[str, Any] = {"round": -1}
    ops = conf["operators"]
    for t in wl.tasks:
        t.backends = {k: _wrap(ctx.rec, fn, t.name, k, t.op, t.work / total,
                               ops.get(t.op, {}), state)
                      for k, fn in t.backends.items()}
    pool = resources.paper_pool(**conf["pool"])
    return {"wl": wl, "pool": pool, "state": state,
            "cost": cost_model.CostModel(), "executor": executor,
            "online": online, "schedulers": schedulers, "dag": dag_mod}


class Loop:
    """The closed loop of one run: ``in_flight`` sources, each planned
    through the online planner when its previous instance exports, and
    each round of in-flight instances handed to the executor at once."""

    def __init__(self, p, conf, in_flight: int, batches, rec) -> None:
        self.p, self.rec, self.batches = p, rec, batches
        self.policy = conf["planner"]["policy"]
        self.drv = p["online"].OnlineDriver(p["pool"], p["cost"],
                                            policy=self.policy)
        self.ex = p["executor"].Executor(p["pool"])
        self.arrivals = [0.0] * in_flight
        self.n = 0

    def plan(self) -> List[Tuple[int, Any]]:
        """The next round: one instance per source, planned in order."""
        insts = []
        for arrival in self.arrivals:
            i, self.n = self.n, self.n + 1
            dag = self.p["wl"].instance(i)
            with self.rec.span("planner", inst=i):
                self.drv.submit(dag, arrival_t=arrival)
                while self.drv.step() is not None or self.drv.pending:
                    pass
            insts.append((i, dag))
        return insts

    def execute(self, insts):
        """Run one round's instances as one merged DAG with their planned
        placements; the executor orders the tasks of all of them."""
        names = {t.name for _i, d in insts for t in d.tasks}
        sched = self.p["schedulers"].Schedule(
            [a for a in self.drv.eng.assignments if a.task in names],
            self.p["pool"], self.policy)
        merged = self.p["dag"].merge([d for _i, d in insts], name="round")
        inputs = {f"ingest#{i}": self.batches[i % len(self.batches)]
                  for i, _d in insts}
        rep = self.ex.execute(merged, sched, inputs=inputs)
        done = dict(self.drv.completions)
        self.arrivals = [done[d.name] for _i, d in insts]
        return rep


def warm(p, conf, in_flight: int, batches: List[np.ndarray]) -> None:
    """Every device program the window can use: each distinct batch once
    with every task on the device (the planner may place any task there),
    and one round of the loop as the window plans it."""
    sched = p["schedulers"].schedule(p["wl"], p["pool"], p["cost"], policy="eft")
    ex = p["executor"].Executor(p["pool"], backend_of=lambda pe: "device")
    for b in batches:
        ex.execute(p["wl"], sched, inputs={"ingest": b})
    loop = Loop(p, conf, in_flight, batches, Recorder())
    loop.execute(loop.plan())


def run(ctx):
    conf, tr, rec = ctx.cell.config, ctx.cell.traffic, ctx.rec
    rows, cols = conf["batch"]["rows"], conf["batch"]["cols"]
    batches = gen.neubot_batches(conf["data"], rows, cols,
                                 tr["distinct_inputs"], ctx.seed)
    p = build(ctx)
    warm(p, conf, tr["in_flight"], batches)

    state = p["state"]
    loop = Loop(p, conf, tr["in_flight"], batches, rec)
    keep_rng = gen.rng_for(ctx.seed, 5)
    kept: Dict[int, Dict[str, Any]] = {}
    seen_batch: set = set()
    attempted = failed = 0
    setup_s = ctx.open_window()
    t_open = time.perf_counter_ns()
    t_end = t_open + int(ctx.seconds * 1e9)
    ctx.tracer.start()
    rnd = 0
    while time.perf_counter_ns() < t_end:
        insts = loop.plan()
        state["round"] = rnd
        attempted += len(insts)
        try:
            with rec.span("round", round=rnd):
                rep = loop.execute(insts)
        except Exception:  # noqa: BLE001 - a failed round is counted
            traceback.print_exc()
            failed += len(insts)
            break
        for i, dag in insts:
            b = i % len(batches)
            if not rep.complete(dag):
                print(f"instance {i} left {sorted(rep.skipped)}",
                      file=sys.stderr)
                failed += 1
            elif b not in seen_batch or keep_rng.random() < 0.25:
                seen_batch.add(b)
                suffix = f"#{i}"
                kept[i] = {t[:-len(suffix)]: out
                           for t, out in rep.outputs.items()
                           if t.endswith(suffix)}
        rnd += 1
        ctx.tracer.poll()
    state["round"] = -1
    ctx.close_window()

    done = [s for s in rec.named("task", t_open, t_end) if s.attrs["round"] >= 0]
    pipelines = sum(s.attrs["share"] for s in done)
    late = rec.named("round")
    notes = [f"instances started {attempted}, failed {failed}, rounds {rnd}, "
             f"pipeline-equivalents in window {pipelines!r}, "
             f"last round ended {(late[-1].t1 - t_end) * 1e-9 if late else 0!r} "
             f"s after the window"]

    # -- correct: a sample of the window's instances vs the reference --------
    checks = check(ctx, kept, batches)
    checks["instances_failed"] = [failed, 0]
    return {"setup_s": setup_s,
            "end_to_end": {"pipelines_per_s": pipelines / ctx.seconds},
            "attempted": attempted, "failed": failed, "checks": checks,
            "layer": {}, "notes": notes}


def check(ctx, kept: Dict[int, Dict[str, Any]], batches: List[np.ndarray]
          ) -> Dict[str, List[float]]:
    conf = ctx.cell.config
    lim = conf["checks"]
    ref_mod = ctx.reference
    refs: Dict[int, Dict[str, Any]] = {}
    readings = []
    for i in sorted(kept):
        b = i % len(batches)
        if b not in refs:
            refs[b] = reference_outputs(ref_mod, batches[b], conf["operators"])
        readings.append(compare(_np(kept[i]), refs[b], lim["near_tie"],
                                lim["sweep_settled"]))
    out = summarise(readings, lim)
    for c in ctx.controls:
        ctl = [compare(control_outputs(ref_mod, batches[b], conf["operators"], c),
                       ref, lim["near_tie"], lim["sweep_settled"])
               for b, ref in refs.items()]
        out.update({f"control_{c}.{k}": v for k, v in summarise(ctl, lim).items()})
    return out


#: outputs computed through matrix products (PCA, the regression, the
#: scoring): the ones a cheaper matmul precision moves first
PRODUCTS = ("pca", "linreg.w", "linreg.b", "score.pred", "score.mse",
            "score.r2", "sweep_clustering.centroids")


def summarise(readings, lim) -> Dict[str, List[float]]:
    """The compared numbers over the checked instances: the worst relative
    error of the matrix-product outputs, of every other continuous output,
    and the count of discrete decisions that differ beyond a near-tie."""
    if not readings:
        inf = float("inf")
        return {"rel_err.products": [inf, lim["rel_err.products"]],
                "rel_err.other": [inf, lim["rel_err.other"]],
                "discrete_mismatch": [inf, 0]}
    prod = max(v for e, _n in readings for k, v in e.items() if k in PRODUCTS)
    other = max(v for e, _n in readings for k, v in e.items() if k not in PRODUCTS)
    bad = sum(sum(n.values()) for _e, n in readings)
    return {"rel_err.products": [prod, lim["rel_err.products"]],
            "rel_err.other": [other, lim["rel_err.other"]],
            "discrete_mismatch": [bad, 0]}


def _split(x):
    """A float32 array as the sum of two bfloat16 arrays and a remainder."""
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot(x, y):
    import jax.numpy as jnp

    return jnp.matmul(x, y, preferred_element_type=jnp.float32)


def mm_high(a, b):
    """A float32 matrix product in three bfloat16 passes (the ``high``
    precision of a TPU), on any backend: the high and low halves of each
    operand, every product but low x low, accumulated in float32."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return _dot(ah, bh) + _dot(ah, bl) + _dot(al, bh)


def mm_bf16(a, b):
    """A float32 matrix product in one bfloat16 pass (a TPU's ``default``
    precision), on any backend: operands rounded to bfloat16, products
    accumulated in float32."""
    return _dot(_split(a)[0], _split(b)[0])


#: the controls: a matrix product at a precision below the program's
CONTROLS = {"high": mm_high, "bf16": mm_bf16}


def control_outputs(ref_mod, raw: np.ndarray, ops: Dict[str, Any],
                    control: str) -> Dict[str, Any]:
    """The control in the program's place: the reference in float32 on
    the default device with every matrix product at the control's
    precision (``high``: three bfloat16 passes; ``bf16``: one)."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    return _np(ref_mod.pipeline(raw, ops, jnp, jnp.float32, CONTROLS[control]))

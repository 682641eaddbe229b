"""Benchmark harness — one section per paper table/figure (deliverable d).

    PYTHONPATH=src python -m benchmarks.run [--quick]

Sections:
  fig6  — resource-pool configuration sweep (paper Fig. 6)
  fig7  — scheduling-policy sweep: exec time + mean utilisation (Fig. 7a/b)
  sched — scheduler engine wall-time per policy (see benchmarks/bench_sched.py)
  federation — edge↔DC scenario matrix: topology skew, WAN partition,
          site loss (see benchmarks/bench_federation.py)
  beyond — beyond-paper policies (HEFT / MinMin / VoS / Hwang-ETF)
  vos   — system-wide Value-of-Service per policy (paper §3/§4.2.3)
  exec  — real execution of the scheduled 16-task workload (host vs device)
  serve — request-scheduling policies on the serving engine
  kern  — kernel micro-benches at toy shapes; each row's unit names the
          platform it ran on (us_cpu_interpret on the CPU, us_tpu compiled)
  roofline — summary of the dry-run roofline table (if results exist)

Output: CSV-ish `section,name,value,unit` lines + human tables.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np


def row(section: str, name: str, value, unit: str) -> None:
    print(f"{section},{name},{value},{unit}")


# ---------------------------------------------------------------------------
# Paper emulation benchmarks
# ---------------------------------------------------------------------------

def bench_fig6(n_instances: int) -> None:
    from repro.core.simulator import sweep_resource_configs, summarize
    from repro.pipeline.workloads import ds_workload
    res = sweep_resource_configs(ds_workload(), n_instances=n_instances)
    print(summarize(res))
    for r in res:
        row("fig6", r.label.replace(",", "+"), f"{r.makespan:.1f}", "s")
    best = min(res, key=lambda r: r.makespan)
    so = [r for r in res if r.label == "Server only"][0]
    row("fig6", "best_vs_server_only_reduction",
        f"{100 * (1 - best.makespan / so.makespan):.1f}", "%")


def bench_fig7(n_instances: int) -> None:
    from repro.core.simulator import sweep_policies, summarize
    from repro.pipeline.workloads import ds_workload
    res = sweep_policies(ds_workload(), n_instances=n_instances)
    print(summarize(res))
    d = {r.policy: r for r in res}
    for pol, r in d.items():
        row("fig7", f"{pol}_makespan", f"{r.makespan:.1f}", "s")
        row("fig7", f"{pol}_mean_util", f"{r.mean_utilization:.3f}", "frac")
    for pol in ("eft", "etf"):
        row("fig7", f"{pol}_vs_rr_time_reduction",
            f"{100 * (1 - d[pol].makespan / d['rr'].makespan):.1f}", "%")
        row("fig7", f"{pol}_vs_rr_util_gain",
            f"{100 * (d[pol].mean_utilization - d['rr'].mean_utilization):.1f}",
            "pts")


def bench_sched(quick: bool) -> None:
    """Engine wall-time per policy (the perf trajectory for the incremental
    scheduler); delegates to the micro-harness so numbers match
    BENCH_sched.json."""
    try:
        from benchmarks import bench_sched as bs
    except ImportError:
        # script mode (`python benchmarks/run.py`): sys.path[0] is
        # benchmarks/, not the repo root — load the sibling file directly
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "bench_sched.py")
        spec = importlib.util.spec_from_file_location("bench_sched", path)
        bs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bs)
    sizes = [20, 100] if quick else [100, 300]
    bs.bench(sizes, ("rr", "etf", "eft", "heft", "minmin"))


def _load_sibling(name: str):
    """Import a benchmarks/ sibling whether run as a module or a script."""
    try:
        import importlib
        return importlib.import_module(f"benchmarks.{name}")
    except ImportError:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"{name}.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def bench_federation(quick: bool) -> None:
    """Edge↔DC federation scenario matrix (WAN bytes, degraded-mode and
    site-loss trajectories); numbers match BENCH_sched.json's
    "federation" section."""
    bf = _load_sibling("bench_federation")
    bf.bench(12 if quick else 24, 4.0, "eft", check_golden=False)


def bench_beyond_policies(n_instances: int) -> None:
    from repro.core.simulator import sweep_policies
    from repro.pipeline.workloads import ds_workload
    res = sweep_policies(ds_workload(), n_instances=n_instances,
                         policies=("eft", "heft", "minmin", "vos",
                                   "etf_hwang"))
    for r in res:
        row("beyond", f"{r.policy}_makespan", f"{r.makespan:.1f}", "s")


def bench_vos(n_instances: int) -> None:
    from repro.core.simulator import sweep_policies
    from repro.core.vos import slo_mix, system_vos, uniform_specs
    from repro.pipeline.workloads import ds_workload
    res = sweep_policies(ds_workload(), n_instances=n_instances,
                         policies=("eft", "etf", "rr", "vos"))
    # value curve: full value if an instance finishes in the first third
    horizon = max(r.makespan for r in res)
    specs = uniform_specs(n_instances, soft=horizon / 3, hard=horizon,
                          energy_weight=1e-7)
    for r in res:
        v = system_vos(r.schedule, specs)
        row("vos", f"{r.policy}_system_vos", f"{v:.2f}",
            f"of {n_instances}")
    # per-instance SLO curves (PR 5): the VoS scheduler maximises against
    # each instance's own curve; score the same mix it optimised
    # (strict=True: the mix must cover every instance)
    curves = slo_mix(n_instances, horizon=horizon / 2)
    het = sweep_policies(ds_workload(), n_instances=n_instances,
                         policies=("eft", "vos"), curves=curves)
    for r in het:
        v = system_vos(r.schedule, curves, strict=True)
        row("vos", f"{r.policy}_hetero_system_vos", f"{v:.2f}",
            f"of {n_instances}")


def bench_execute() -> None:
    from repro.core.cost_model import CostModel
    from repro.core.executor import Executor
    from repro.core.resources import paper_pool
    from repro.core.schedulers import schedule
    from repro.pipeline.workloads import ds_workload_executable
    wl = ds_workload_executable()
    pool = paper_pool()
    sched = schedule(wl, pool, CostModel(), policy="eft")
    raw = np.random.default_rng(0).normal(0, 1, (2048, 8)).astype(np.float32)
    for backend in ("mixed", "host", "device"):
        of = (None if backend == "mixed"
              else (lambda pe, b=backend: b))
        ex = Executor(pool) if of is None else Executor(pool, backend_of=of)
        t0 = time.perf_counter()
        rep = ex.execute(wl, sched, inputs={"ingest": raw})
        row("exec", f"{backend}_16task_wall", f"{rep.wall_seconds*1e3:.1f}",
            "ms")


def bench_serve() -> None:
    import jax
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve.engine import EngineConfig, Request, ServeEngine
    cfg = get_config("qwen3-0.6b", smoke=True)
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [dict(rid=i,
                 prompt=rng.integers(2, cfg.vocab_size,
                                     size=int(rng.integers(4, 20))
                                     ).astype(np.int32),
                 max_new_tokens=int(rng.integers(4, 12)),
                 arrival=i * 0.3) for i in range(12)]
    for policy in ("fcfs", "eft", "edf"):
        eng = ServeEngine(cfg, params,
                          EngineConfig(max_batch=3, max_seq=96,
                                       policy=policy))
        for kw in reqs:
            eng.submit(Request(**kw))
        eng.run()
        st = eng.latency_stats()
        row("serve", f"{policy}_mean_latency", f"{st['mean_latency']:.1f}",
            "ticks")
        row("serve", f"{policy}_p95_latency", f"{st['p95_latency']:.1f}",
            "ticks")


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.kmeans import kmeans_assign
    from repro.kernels.window_agg import window_agg
    from repro.kernels import platform
    rng = np.random.default_rng(0)
    unit = "us_" + jax.default_backend() + (
        "_interpret" if platform.interpret() else "")

    def timeit(fn, *args, n=3, **kw):
        fn(*args, **kw)  # compile/warm
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(*args, **kw))
        return (time.perf_counter() - t0) / n * 1e6

    q = jnp.asarray(rng.normal(0, 1, (1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 256, 2, 64)), jnp.float32)
    us = timeit(flash_attention, q, k, k, block_q=64, block_k=64)
    row("kern", "flash_attention_256x4x64", f"{us:.0f}", unit)

    qd = jnp.asarray(rng.normal(0, 1, (4, 8, 64)), jnp.float32)
    kd = jnp.asarray(rng.normal(0, 1, (4, 512, 2, 64)), jnp.float32)
    us = timeit(decode_attention, qd, kd, kd)
    row("kern", "decode_attention_c512", f"{us:.0f}", unit)

    x = jnp.asarray(rng.normal(0, 1, (2048, 16)), jnp.float32)
    c = jnp.asarray(rng.normal(0, 1, (16, 16)), jnp.float32)
    us = timeit(kmeans_assign, x, c)
    row("kern", "kmeans_assign_2048x16x16", f"{us:.0f}", unit)

    w = jnp.asarray(rng.normal(0, 1, (1024, 8)), jnp.float32)
    us = timeit(window_agg, w, window=16, agg="mean")
    row("kern", "window_agg_1024x8_w16", f"{us:.0f}", unit)


def bench_roofline() -> None:
    from benchmarks import roofline as rl
    rows = rl.load("results/dryrun")
    if not rows:
        row("roofline", "status", "no_dryrun_results", "-")
        return
    done = [d for d in rows if not d.get("skipped")]
    fits = sum(1 for d in done if d.get("fits_hbm"))
    row("roofline", "cells_compiled", len(done), "cells")
    row("roofline", "cells_skipped", len(rows) - len(done), "cells")
    row("roofline", "cells_fit_hbm", fits, "cells")
    for dom in ("compute_s", "memory_s", "collective_s"):
        n = sum(1 for d in done if d["roofline"]["dominant"] == dom)
        row("roofline", f"dominant_{dom.replace('_s','')}", n, "cells")
    print(rl.table(rows))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer instances for the emulation sweeps")
    ap.add_argument("--sections", default="all")
    args = ap.parse_args(argv)
    n = 20 if args.quick else 100
    sections = (("fig6", "fig7", "sched", "federation", "beyond", "vos",
                 "exec", "serve", "kern", "roofline")
                if args.sections == "all"
                else tuple(args.sections.split(",")))
    t0 = time.perf_counter()
    fns = {"fig6": lambda: bench_fig6(n), "fig7": lambda: bench_fig7(n),
           "sched": lambda: bench_sched(args.quick),
           "federation": lambda: bench_federation(args.quick),
           "beyond": lambda: bench_beyond_policies(n),
           "vos": lambda: bench_vos(n), "exec": bench_execute,
           "serve": bench_serve, "kern": bench_kernels,
           "roofline": bench_roofline}
    for s in sections:
        print(f"\n=== {s} ===")
        fns[s]()
    print(f"\ntotal {time.perf_counter() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Bring-up smoke run of the system's main paths on a TPU.

    python chip_smoke.py [--seed 0]            # one chip
    python chip_smoke.py --chips 4 [--seed 0]  # four chips: sharded step only

One process, no children, nothing downloaded: every input and weight is
made from ``--seed``. The phases, each printed with its wall seconds, its
shapes and the number of XLA programs it compiled:

* ``device``   — the default backend must be a TPU (never the CPU);
* ``kernels``  — the four Pallas kernels, compiled, against their jnp
  oracles at the widths the repository's compile tests use;
* ``pipeline`` — the paper's 16-task DS pipeline on a 16 MB raw input
  (524288 x 8 float32), planned with EFT over the paper's pool and run by
  the ``Executor`` as scheduled and with every task on the device, each
  task's output compared with an all-host (numpy) run;
* ``serve``    — qwen3-0.6b at full width behind the ``ServingGateway``:
  8 mixed-tier requests planned, served by the ``ServeEngine`` and checked
  against batch-1 ``greedy_generate`` and a cache-free forward pass on the
  same chip.

With ``--chips 4`` the run is instead one full-width qwen3-0.6b train step
sharded over a ``{"data": 2, "model": 2}`` VDC, compared with the same
step on one chip. These are bring-up timings, not benchmark numbers.

The last line of standard output is ``{"ok": true, "device": {...}}``; on
any failure the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.configs import get_config  # noqa: E402
from repro.core.cost_model import CostModel  # noqa: E402
from repro.core.executor import Executor  # noqa: E402
from repro.core.resources import paper_pool  # noqa: E402
from repro.core.schedulers import schedule  # noqa: E402
from repro.core.vdc import VDCManager  # noqa: E402
from repro.distributed import sharding as sh  # noqa: E402
from repro.kernels.decode_attention import (  # noqa: E402
    decode_attention,
    decode_attention_ref,
)
from repro.kernels.flash_attention import (  # noqa: E402
    flash_attention,
    flash_attention_ref,
)
from repro.kernels.kmeans import kmeans_assign, kmeans_assign_ref  # noqa: E402
from repro.kernels.window_agg import window_agg, window_agg_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.pipeline.workloads import ds_workload_executable  # noqa: E402
from repro.serve.engine import EngineConfig, RequestSpec, ServeEngine  # noqa: E402
from repro.serve.gateway import GatewayConfig, ServingGateway  # noqa: E402
from repro.train.optimizer import OptConfig  # noqa: E402
from repro.train.train_step import build_train_step, init_train_state  # noqa: E402

#: the only platform this script accepts
PLATFORM = "tpu"
#: host/device operator parity (tests/test_operators.py)
OP_RTOL = OP_ATOL = 3e-4
#: kernel-vs-oracle tolerances (tests/test_kernels.py)
KERNEL_TOL = {"float32": 2e-4, "bfloat16": 2e-2}
#: bf16 keeps 8 significant bits, so one rounding moves a logit by up to
#: 2^-8 of its size. Where the served tokens first leave the batch-1
#: reference, the served token may fall short of the reference's top logit
#: by at most this many such roundings of it: a near-tie, which a
#: different batch shape or fusion order may break either way.
MARGIN_ROUNDINGS = 8
#: past that point the two continue from different contexts; each served
#: token is then checked against a cache-free forward pass of its own
#: context, with room for bf16 differences compounded over 28 layers but
#: not for a wrong cache or position (a random token falls ~256 short)
FORWARD_ROUNDINGS = 32
#: sharded vs one-chip train-step loss and grad norm (bf16 activations,
#: a different reduction order across the model axis)
STEP_RTOL = 5e-3

SERVE_PROMPT_LENS = (64, 256)
SERVE_NEW_TOKENS = (16, 24, 32)
SERVE_TIERS = ("interactive", "batch")
TRAIN_BATCH, TRAIN_SEQ = 8, 256


class Counter:
    """Counts XLA programs compiled (and persistent-cache hits) through
    jax's monitoring events."""

    def __init__(self) -> None:
        self.programs = 0
        self.cache_hits = 0

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs += 1

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.programs, self.cache_hits


def run_phase(name, fn, counter, results):
    """Run one phase; print its line; record pass/fail."""
    p0, h0 = counter.snapshot()
    t0 = time.perf_counter()
    try:
        info = fn() or {}
        ok = True
    except Exception:  # noqa: BLE001 — report every phase, then fail the run
        traceback.print_exc()
        info, ok = {}, False
    dt = time.perf_counter() - t0
    p1, h1 = counter.snapshot()
    info = dict(info, seconds=dt, programs_compiled=p1 - p0, cache_hits=h1 - h0)
    print(f"[{name}] {'ok' if ok else 'FAILED'} {json.dumps(info)}", flush=True)
    results[name] = ok
    return ok


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(want: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != PLATFORM:
        raise RuntimeError(f"no TPU: default backend is {d.platform!r}")
    if len(devs) < want:
        raise RuntimeError(f"need {want} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def phase_kernels(seed: int):
    rng = np.random.default_rng(seed)

    def arr(shape, dt):
        return jnp.asarray(rng.normal(0, 1, shape), dt)

    def close(name, out, ref, dt):
        tol = KERNEL_TOL[jnp.dtype(dt).name]
        a = np.asarray(out, np.float32)
        b = np.asarray(ref, np.float32)
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol, err_msg=name)
        return float(np.abs(a - b).max())

    errs = {}
    bf = jnp.bfloat16
    q = arr((1, 2048, 16, 128), bf)
    k, v = arr((1, 2048, 8, 128), bf), arr((1, 2048, 8, 128), bf)
    kr = jnp.repeat(k, 2, 2).transpose(0, 2, 1, 3)
    vr = jnp.repeat(v, 2, 2).transpose(0, 2, 1, 3)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3), kr, vr, causal=True)
    errs["flash"] = close(
        "flash", flash_attention(q, k, v), ref.transpose(0, 2, 1, 3), bf
    )

    qd = arr((8, 16, 128), bf)
    kd, vd = arr((8, 4096, 8, 128), bf), arr((8, 4096, 8, 128), bf)
    valid = jnp.asarray(rng.random((8, 4096)) > 0.3)
    errs["decode"] = close(
        "decode",
        decode_attention(qd, kd, vd, valid),
        decode_attention_ref(qd, kd, vd, valid),
        bf,
    )

    x, c = arr((524288, 3), jnp.float32), arr((4, 3), jnp.float32)
    a, d2 = kmeans_assign(x, c)
    ar, d2r = kmeans_assign_ref(x, c)
    mismatched = int((np.asarray(a) != np.asarray(ar)).sum())
    if mismatched:
        raise AssertionError(f"kmeans: {mismatched} assignments differ")
    errs["kmeans_d2"] = close("kmeans", d2, d2r, jnp.float32)

    s = arr((524288, 4), jnp.float32)
    for agg, w in (("mean", 8), ("max", 16)):
        errs[f"window_{agg}"] = close(
            f"window_{agg}",
            window_agg(s, window=w, agg=agg),
            window_agg_ref(s, window=w, agg=agg),
            jnp.float32,
        )
    return {"max_abs_err": errs}


def _assignments_agree(x, cent, want, got) -> int:
    """k-means assignments may differ only at near-ties: where the host's
    distances to the two clusters are within the parity tolerance. Returns
    the number of such ties."""
    idx = np.flatnonzero(want != got)
    if idx.size:
        d2 = ((x[idx, None, :].astype(np.float64) - cent[None]) ** 2).sum(-1)
        rows = np.arange(idx.size)
        d_want, d_got = d2[rows, want[idx]], d2[rows, got[idx]]
        if np.any(np.abs(d_got - d_want) > OP_ATOL + OP_RTOL * d_want):
            raise AssertionError(f"{idx.size} assignments differ beyond ties")
    return int(idx.size)


def _task_outputs_agree(name, want, got) -> int:
    """Every output leaf within the parity tolerance (k-means-family fits
    up to near-tie assignments). Returns the number of such ties."""
    ties = 0
    if isinstance(want, dict) and "fit" in want:  # {"x", "fit": (cent, assign, …)}
        x, (cent, assign) = np.asarray(want["x"]), want["fit"][:2]
        ties = _assignments_agree(
            x, np.asarray(cent), np.asarray(assign), np.asarray(got["fit"][1])
        )
        want = dict(want, fit=(cent,) + tuple(want["fit"][2:]))
        got = dict(got, fit=(got["fit"][0],) + tuple(got["fit"][2:]))
    a_leaves, b_leaves = _leaves(want), _leaves(got)
    if len(a_leaves) != len(b_leaves):
        raise AssertionError(f"{name}: output structure differs")
    for a, b in zip(a_leaves, b_leaves, strict=True):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=OP_RTOL, atol=OP_ATOL, err_msg=name
        )
    return ties


def phase_pipeline(seed: int):
    raw_mb, n_cols = 16, 8
    n_rows = raw_mb * 2**20 // (4 * n_cols)  # 524288 x 8 float32
    raw = np.random.default_rng(seed).standard_normal(
        (n_rows, n_cols), dtype=np.float32
    )
    wl = ds_workload_executable(raw_mb=raw_mb)
    pool = paper_pool()
    sched = schedule(wl, pool, CostModel(), policy="eft")

    def run(backend_of):
        t0 = time.perf_counter()
        ex = Executor(pool, backend_of=backend_of)
        rep = ex.execute(wl, sched, inputs={"ingest": raw})
        return rep, time.perf_counter() - t0

    host, t_host = run(lambda pe: "host")
    info = {"raw_shape": list(raw.shape), "host_s": t_host, "runs": {}}
    for label, backend_of in (
        ("scheduled", None),
        ("all_device", lambda pe: "device"),
    ):
        rep, dt = run(backend_of)
        if len(rep.runs) != len(wl) or not rep.by_backend.get("device"):
            raise AssertionError(f"{label}: ran {rep.by_backend}")
        ties = 0
        for r in rep.runs:
            name = f"{label}/{r.task} ({r.backend})"
            ties += _task_outputs_agree(name, host.outputs[r.task], r.output)
            if r.backend != "device":
                continue
            arrays = [x for x in _leaves(r.output) if isinstance(x, jax.Array)]
            platforms = {d.platform for x in arrays for d in x.devices()}
            if platforms != {PLATFORM}:
                raise AssertionError(f"{name}: outputs on {platforms}")
        info["runs"][label] = {
            "seconds": dt,
            "by_backend": rep.by_backend,
            "kmeans_near_ties": ties,
        }
    return info


def _roundings_short(logits, tokens):
    """How far each chosen token's logit falls below its row's top logit,
    in bf16 roundings of that top logit (0 = the argmax)."""
    top = logits.max(-1)
    chosen = logits[np.arange(len(tokens)), tokens]
    return (top - chosen) / (2.0**-8 * np.maximum(np.abs(top), 1e-30))


def phase_serve(seed: int):
    cfg = get_config("qwen3-0.6b")
    params = jax.jit(M.init, static_argnums=0)(cfg, jax.random.PRNGKey(seed))
    ecfg = EngineConfig(max_batch=4, max_seq=512, policy="fcfs")
    rng = np.random.default_rng(seed)
    lens = [SERVE_PROMPT_LENS[i % len(SERVE_PROMPT_LENS)] for i in range(8)]
    specs = [
        RequestSpec(
            rid=i,
            prompt=rng.integers(2, cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=SERVE_NEW_TOKENS[i % len(SERVE_NEW_TOKENS)],
            arrival=0.5 * i,
            tier=SERVE_TIERS[i % len(SERVE_TIERS)],
        )
        for i, n in enumerate(lens)
    ]

    gw = ServingGateway(GatewayConfig(ecfg=ecfg))
    for s in specs:
        gw.offer(s)
    gw.drain()
    planned = sorted(rid for _t, rid in gw.plan_order())
    if planned != [s.rid for s in specs]:
        raise AssertionError(f"gateway planned {planned}")
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, params, ecfg)
    stats = gw.serve(eng)
    t_serve = time.perf_counter() - t0
    done = {r.rid: r for r in eng.finished}
    if sorted(done) != planned:
        raise AssertionError(f"finished {sorted(done)} of {planned}")

    t0 = time.perf_counter()
    diverged, worst = [], 0.0
    for s in specs:
        out = np.asarray(done[s.rid].output)
        n = s.max_new_tokens + 1
        if len(out) != n or out.min() < 0 or out.max() >= cfg.vocab_size:
            raise AssertionError(f"request {s.rid}: bad output {out}")
        prompt = jnp.asarray(s.prompt)[None]
        ref = np.asarray(M.greedy_generate(cfg, params, prompt, n, ecfg.max_seq))[0]
        diff = np.flatnonzero(out != ref)
        if diff.size:
            # the reference's own logits where the two first part ways
            pos = int(diff[0])
            ctx = jnp.asarray(np.concatenate([s.prompt, ref[:pos]]))[None]
            row = np.asarray(M.forward(cfg, params, ctx)[0][0, -1], np.float32)
            short = float(_roundings_short(row[None], out[pos : pos + 1])[0])
            top2 = np.sort(row)[-2:]
            print(
                f"  request {s.rid}: first differs at token {pos} (served "
                f"{out[pos]}, reference {ref[pos]}); reference top-2 margin "
                f"{top2[1] - top2[0]:.6g}, served token {short:.3g} bf16 "
                f"roundings below the top (tolerance {MARGIN_ROUNDINGS})",
                flush=True,
            )
            diverged.append(s.rid)
            if short > MARGIN_ROUNDINGS:
                raise AssertionError(f"request {s.rid}: not a near-tie at {pos}")
        # every served token, in its own context, against a forward pass
        # without the KV cache
        seq = jnp.asarray(np.concatenate([s.prompt, out[:-1]]))[None]
        lg = np.asarray(M.forward(cfg, params, seq)[0][0], np.float32)
        short = _roundings_short(lg[s.prompt_len - 1 :], out)
        worst = max(worst, float(short.max()))
        if short.max() > FORWARD_ROUNDINGS:
            bad = int(short.argmax())
            raise AssertionError(
                f"request {s.rid}: token {bad} is {short[bad]:.3g} bf16 "
                f"roundings below the cache-free forward's top logit"
            )
    return {
        "model": cfg.name,
        "layers": cfg.n_layers,
        "d_model": cfg.d_model,
        "vocab": cfg.vocab_size,
        "requests": len(specs),
        "prompt_lens": sorted({s.prompt_len for s in specs}),
        "new_tokens": sorted({s.max_new_tokens for s in specs}),
        "engine_ticks": eng.ticks,
        "serve_s": t_serve,
        "reference_s": time.perf_counter() - t0,
        "mean_latency_ticks": stats["mean_latency"],
        "diverged_at_near_ties": diverged,
        "max_roundings_below_top": worst,
    }


def phase_sharded_step(seed: int):
    cfg = get_config("qwen3-0.6b")
    oc = OptConfig(lr=1e-4, warmup_steps=1, total_steps=10)
    step = build_train_step(cfg, oc, remat=True)
    init = jax.jit(init_train_state, static_argnums=(0, 1))
    toks = np.random.default_rng(seed).integers(
        2, cfg.vocab_size, size=(TRAIN_BATCH, TRAIN_SEQ)
    )
    toks = toks.astype(np.int32)
    batch = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
    key = jax.random.PRNGKey(seed)

    # one chip
    t0 = time.perf_counter()
    state = init(cfg, oc, key)
    state, m1 = jax.jit(step, donate_argnums=0)(state, batch)
    one = {k: float(m1[k]) for k in ("loss", "grad_norm")}
    del state, m1  # free chip 0 for its share of the mesh
    t_one = time.perf_counter() - t0

    # the same step over a composed 2x2 VDC
    t0 = time.perf_counter()
    vdc = VDCManager().compose("train", {"data": 2, "model": 2})
    mesh = vdc.mesh
    rules = sh.strategy_for(cfg, mesh)

    def named(tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    with sh.logical_axis_rules(rules):
        st_sh = named(sh.param_specs(jax.eval_shape(init, cfg, oc, key)))
        b_sh = named(sh.batch_specs(batch))
    sharded_init = jax.jit(init_train_state, static_argnums=(0, 1), out_shardings=st_sh)
    state = sharded_init(cfg, oc, key)

    # every chip holds a shard, and a TP-sharded weight is split in two
    held = {d for x in _leaves(state) for d in x.sharding.device_set}
    if held != set(vdc.devices):
        raise AssertionError(f"state on {len(held)} of {vdc.n_chips} chips")
    wq = state["params"]["scan"][0]["attn"]["wq"]
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    want = {wq.shape[:-1] + (wq.shape[-1] // 2,)}
    if len(wq.addressable_shards) != vdc.n_chips or shard_shapes != want:
        raise AssertionError(f"wq {wq.shape} shards {shard_shapes}")

    def fn(s, b):
        with sh.logical_axis_rules(rules):
            return step(s, b)

    with jax.set_mesh(mesh):
        _, m2 = jax.jit(
            fn,
            in_shardings=(st_sh, b_sh),
            out_shardings=(st_sh, None),
            donate_argnums=0,
        )(state, batch)
    four = {k: float(m2[k]) for k in ("loss", "grad_norm")}
    t_four = time.perf_counter() - t0
    for k in one:
        if not abs(four[k] - one[k]) <= STEP_RTOL * abs(one[k]):
            raise AssertionError(f"{k}: 4 chips {four[k]} vs 1 chip {one[k]}")
    return {
        "model": cfg.name,
        "batch": [TRAIN_BATCH, TRAIN_SEQ],
        "mesh": vdc.axis_sizes,
        "one_chip": one,
        "four_chips": four,
        "one_chip_s": t_one,
        "four_chip_s": t_four,
        "wq_shard_shape": list(next(iter(shard_shapes))),
        "sharding_notes": rules.notes,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    print(f"compile cache: {use_compile_cache()}", flush=True)
    counter = Counter()
    results = {}
    if not run_phase("device", lambda: phase_device(args.chips), counter, results):
        return 1
    if args.chips == 4:
        phases = [("sharded_step", phase_sharded_step)]
    else:
        phases = [
            ("kernels", phase_kernels),
            ("pipeline", phase_pipeline),
            ("serve", phase_serve),
        ]
    for name, fn in phases:
        run_phase(name, lambda fn=fn: fn(args.seed), counter, results)
    print(
        f"programs compiled: {counter.programs}, "
        f"persistent-cache hits: {counter.cache_hits}",
        flush=True,
    )
    failed = [k for k, ok in results.items() if not ok]
    if failed:
        print(f"failed phases: {failed}", flush=True)
        return 1
    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

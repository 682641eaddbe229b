"""Multi-pod dry-run harness (deliverable e).

For every (architecture × input shape × mesh) cell:

    with mesh:
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…).lower(**specs)
        compiled = lowered.compile()
        memory_analysis() / cost_analysis() / HLO collective parse

and write one JSON per cell with the raw numbers §Roofline consumes
(scan-corrected FLOPs/bytes + per-collective ICI/DCN wire bytes — see
repro.launch.hlo_analysis; the cost_analysis scan caveat is DESIGN.md §6).

Usage:
    python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
        --mesh single --out results/dryrun/qwen3__train_4k__single.json
    python -m repro.launch.dryrun --all [--mesh both] [--out-dir results/dryrun]

``--all`` runs each cell in a fresh subprocess (compile state isolation;
one cell crashing doesn't take the sweep down).

A CPU-only tool: :func:`main` pins the ``cpu`` platform with 512
placeholder host devices before anything touches a backend, so on a
machine with an accelerator it never takes the chip.
"""

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.models.config import ModelConfig
from repro.models import model as model_lib
from repro.models import transformer as T
from repro.distributed import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.launch import hlo_analysis
from repro.train.optimizer import OptConfig
from repro.train.train_step import build_train_step, init_train_state
from repro.serve.serve_step import build_decode_step, build_prefill_step

# ---------------------------------------------------------------------------
# Assigned shapes (LM transformer shapes: seq_len × global_batch)
# ---------------------------------------------------------------------------

SHAPES: Dict[str, Dict[str, Any]] = {
    "train_4k":    {"kind": "train",   "seq": 4096,    "batch": 256},
    "prefill_32k": {"kind": "prefill", "seq": 32768,   "batch": 32},
    "decode_32k":  {"kind": "decode",  "seq": 32768,   "batch": 128},
    "long_500k":   {"kind": "decode",  "seq": 524288,  "batch": 1},
}

#: per-chip HW constants (v5e-class) — single source shared with §Roofline
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
DCN_BW = 25e9
CHIPS_PER_POD = 256


def cell_applicable(cfg: ModelConfig, shape: str) -> Tuple[bool, str]:
    if shape == "long_500k" and not cfg.supports_long_decode:
        return False, ("pure full-attention arch: a 524k dense KV cache is "
                       "unbounded by construction (DESIGN.md §4 skip table)")
    return True, ""


def opt_config_for(cfg: ModelConfig) -> OptConfig:
    # trillion-scale: factored second moments (fp32 m/v would be 8 TB)
    if cfg.param_counts()["total"] > 2e11:
        return OptConfig(name="adafactor", total_steps=10000)
    return OptConfig(name="adamw", total_steps=10000)


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: str) -> Dict[str, Any]:
    """Shape/dtype stand-ins (no allocation) for one cell's step inputs."""
    info = SHAPES[shape]
    B, S = info["batch"], info["seq"]
    sds = jax.ShapeDtypeStruct
    out: Dict[str, Any] = {}
    if info["kind"] == "train":
        out["batch"] = {"tokens": sds((B, S), jnp.int32),
                        "labels": sds((B, S), jnp.int32)}
        if cfg.family == "vlm":
            out["batch"]["vision"] = sds((B, cfg.n_vision_tokens,
                                          cfg.d_model), jnp.bfloat16)
    elif info["kind"] == "prefill":
        out["tokens"] = sds((B, S), jnp.int32)
        out["caches"] = jax.eval_shape(lambda: T.init_caches(cfg, B, S))
        if cfg.family == "vlm":
            out["vision"] = sds((B, cfg.n_vision_tokens, cfg.d_model),
                                jnp.bfloat16)
    else:  # decode: one new token against a cache of S
        out["token"] = sds((B,), jnp.int32)
        out["pos"] = sds((B,), jnp.int32)
        out["caches"] = jax.eval_shape(lambda: T.init_caches(cfg, B, S))
        if cfg.family == "vlm":
            out["vision"] = sds((B, cfg.n_vision_tokens, cfg.d_model),
                                jnp.bfloat16)
    return out


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS (the §Roofline "useful compute" reference)
# ---------------------------------------------------------------------------

def model_flops(cfg: ModelConfig, shape: str) -> float:
    info = SHAPES[shape]
    B, S = info["batch"], info["seq"]
    n_active = cfg.param_counts()["active"]
    if info["kind"] == "train":
        return 6.0 * n_active * B * S          # fwd 2ND + bwd 4ND
    if info["kind"] == "prefill":
        return 2.0 * n_active * B * S
    return 2.0 * n_active * B                  # one token per row


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape: str, mesh_kind: str,
             sequence_sharding: bool = False,
             grad_accum: int = 4,
             donate_caches: bool = True,
             strategy: str = "tp",
             moe_shard_map: bool = False,
             decode_flash_shard: bool = False,
             loss_chunk: int = 0) -> Dict[str, Any]:
    cfg = get_config(arch)
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "skipped": True, "reason": why}
    multi_pod = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    rules = sh.strategy_for(cfg, mesh, sequence_sharding=sequence_sharding,
                            mode=strategy, moe_shard_map=moe_shard_map,
                            decode_flash_shard=decode_flash_shard)
    info = SHAPES[shape]
    specs = input_specs(cfg, shape)
    t_all = time.time()

    with sh.logical_axis_rules(rules):
        if info["kind"] == "train":
            opt_cfg = opt_config_for(cfg)
            state_shape = jax.eval_shape(
                lambda: init_train_state(cfg, opt_cfg, jax.random.PRNGKey(0)))
            state_specs = sh.param_specs(state_shape)
            state_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), state_specs,
                is_leaf=lambda x: isinstance(x, P))
            batch_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                sh.batch_specs(specs["batch"]),
                is_leaf=lambda x: isinstance(x, P))
            step = build_train_step(cfg, opt_cfg, remat=True,
                                    grad_accum=grad_accum,
                                    loss_chunk=loss_chunk)

            def fn(state, batch):
                with sh.logical_axis_rules(rules):
                    return step(state, batch)

            t0 = time.time()
            with jax.set_mesh(mesh):
                lowered = jax.jit(
                    fn, in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, None)
                ).lower(state_shape, specs["batch"])
        else:
            params_shape = jax.eval_shape(
                lambda: model_lib.init(cfg, jax.random.PRNGKey(0)))
            params_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                sh.param_specs(params_shape),
                is_leaf=lambda x: isinstance(x, P))
            caches_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                sh.cache_specs(specs["caches"]),
                is_leaf=lambda x: isinstance(x, P))
            def bspec(leaf):
                return NamedSharding(
                    mesh, rules.spec(("batch",) + (None,) * (np.ndim(leaf) - 1),
                                     np.shape(leaf)))
            if info["kind"] == "prefill":
                pre = build_prefill_step(cfg)

                def fn(params, tokens, caches, vision=None):
                    with sh.logical_axis_rules(rules):
                        return pre(params, tokens, caches, vision=vision)

                args = [params_shape, specs["tokens"], specs["caches"]]
                shardings = [params_sh, bspec(specs["tokens"]), caches_sh]
            else:
                dec = build_decode_step(cfg)

                def fn(params, token, pos, caches, vision=None):
                    with sh.logical_axis_rules(rules):
                        return dec(params, token, pos, caches, vision=vision)

                args = [params_shape, specs["token"], specs["pos"],
                        specs["caches"]]
                shardings = [params_sh, bspec(specs["token"]),
                             bspec(specs["pos"]), caches_sh]
            kwargs = {}
            if "vision" in specs:
                args.append(specs["vision"])
                shardings.append(bspec(specs["vision"]))
            donate = ()
            if info["kind"] == "decode" and donate_caches:
                donate = (3,)
            t0 = time.time()
            with jax.set_mesh(mesh):
                lowered = jax.jit(
                    fn, in_shardings=tuple(shardings),
                    donate_argnums=donate).lower(*args)

        lower_s = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    hlo = hlo_analysis.analyze(txt, chips_per_pod=CHIPS_PER_POD)

    mf = model_flops(cfg, shape)
    per_dev = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "output_bytes": getattr(ma, "output_size_in_bytes", 0),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
    }
    per_dev["total_bytes"] = (per_dev["argument_bytes"]
                              + per_dev["temp_bytes"]
                              + per_dev["output_bytes"]
                              - per_dev["alias_bytes"])
    colls = {k: {"count": v.count, "ici_bytes": v.wire_bytes_ici,
                 "dcn_bytes": v.wire_bytes_dcn}
             for k, v in sorted(hlo.collectives.items())}

    # roofline terms (per-step seconds)
    compute_s = hlo.dot_flops / PEAK_FLOPS            # per-device flops
    memory_s = hlo.hbm_bytes / HBM_BW
    # TPU view: XLA:CPU loop-carry copies are elided by the TPU backend
    memory_nocopy_s = (hlo.hbm_bytes - hlo.copy_bytes) / HBM_BW
    ici_s = hlo.ici_bytes / ICI_BW
    dcn_s = hlo.dcn_bytes / DCN_BW
    coll_s = ici_s + dcn_s
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "memory_nocopy_s": memory_nocopy_s,
             "collective_s": coll_s, "ici_s": ici_s, "dcn_s": dcn_s}
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])

    return {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "kind": info["kind"], "n_chips": n_chips,
        "skipped": False,
        "lower_s": lower_s, "compile_s": compile_s,
        "wall_s": time.time() - t_all,
        "memory_per_device": per_dev,
        "fits_hbm": per_dev["total_bytes"] <= 16e9,
        "cost_analysis_raw": {"flops": ca.get("flops", 0.0),
                              "bytes_accessed": ca.get("bytes accessed", 0.0)},
        "hlo": {"dot_flops_per_dev": hlo.dot_flops,
                "hbm_bytes_per_dev": hlo.hbm_bytes,
                "copy_bytes_per_dev": hlo.copy_bytes,
                "n_while": hlo.n_while,
                "trip_counts": hlo.trip_counts,
                "collectives": colls},
        "model_flops_global": mf,
        "useful_flops_ratio": mf / max(hlo.dot_flops * n_chips, 1.0),
        "roofline": dict(terms, dominant=dominant,
                         step_time_lower_bound_s=max(terms["compute_s"],
                                                     terms["memory_s"],
                                                     terms["collective_s"])),
        "sharding_notes": rules.notes,
        "options": {"sequence_sharding": sequence_sharding,
                    "grad_accum": grad_accum, "strategy": strategy,
                    "moe_shard_map": moe_shard_map,
                    "decode_flash_shard": decode_flash_shard,
                    "loss_chunk": loss_chunk},
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _pin_host_platform() -> None:
    """CPU backend only, with enough placeholder devices for 2 pods. Must
    run before the first backend use: jax fixes both at initialisation."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 512)


def main(argv=None) -> int:
    _pin_host_platform()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--out")
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sequence-sharding", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=4)
    ap.add_argument("--strategy", choices=("tp", "fsdp"), default="tp")
    ap.add_argument("--moe-shard-map", action="store_true")
    ap.add_argument("--decode-flash-shard", action="store_true")
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args(argv)

    if args.all:
        os.makedirs(args.out_dir, exist_ok=True)
        meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
        failures = []
        for arch in ARCHS:
            for shape in SHAPES:
                for mk in meshes:
                    out = os.path.join(args.out_dir,
                                       f"{arch}__{shape}__{mk}.json")
                    if os.path.exists(out):
                        print(f"[skip existing] {out}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mk,
                           "--out", out]
                    print(">>", " ".join(cmd), flush=True)
                    try:
                        r = subprocess.run(cmd, timeout=args.timeout)
                        rc = r.returncode
                    except subprocess.TimeoutExpired:
                        rc = -9
                        print(f"[timeout after {args.timeout}s]", flush=True)
                    if rc != 0:
                        failures.append((arch, shape, mk, rc))
        if failures:
            print("FAILURES:", failures)
            return 1
        print("dry-run sweep complete")
        return 0

    if not (args.arch and args.shape):
        ap.error("--arch/--shape required (or --all)")
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    for mk in meshes:
        res = run_cell(args.arch, args.shape, mk,
                       sequence_sharding=args.sequence_sharding,
                       grad_accum=args.grad_accum,
                       strategy=args.strategy,
                       moe_shard_map=args.moe_shard_map,
                       decode_flash_shard=args.decode_flash_shard,
                       loss_chunk=args.loss_chunk)
        out = args.out or f"{args.arch}__{args.shape}__{mk}.json"
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(res, f, indent=2)
        if res.get("skipped"):
            print(f"[{args.arch} × {args.shape} × {mk}] SKIPPED: "
                  f"{res['reason']}")
        else:
            r = res["roofline"]
            print(f"[{args.arch} × {args.shape} × {mk}] compile "
                  f"{res['compile_s']:.1f}s | mem/dev "
                  f"{res['memory_per_device']['total_bytes']/1e9:.2f} GB "
                  f"(fits={res['fits_hbm']}) | compute {r['compute_s']*1e3:.2f} ms "
                  f"memory {r['memory_s']*1e3:.2f} ms coll "
                  f"{r['collective_s']*1e3:.2f} ms → {r['dominant']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

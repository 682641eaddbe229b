"""Serving driver: continuous batching + JITA request scheduling.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --requests 16 --policy eft

Compares admission policies (fcfs vs the paper's EFT rule vs edf) on the
same synthetic request trace and prints latency stats.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

import jax

from repro.configs import ARCHS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import frontends
from repro.models import model as model_lib
from repro.serve.engine import EngineConfig, Request, ServeEngine


def synth_requests(cfg, n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(4, 24))
        out.append(Request(
            rid=i,
            prompt=rng.integers(2, cfg.vocab_size, size=plen).astype(np.int32),
            max_new_tokens=int(rng.integers(4, 16)),
            arrival=float(i) * 0.25,
            deadline=float(i) * 0.25 + float(rng.uniform(50, 400))))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--policy", default="all",
                    choices=("fcfs", "eft", "edf", "all"))
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=True)
    params = model_lib.init(cfg, jax.random.PRNGKey(0))
    vision = (frontends.fake_patch_embeddings(cfg, 1)[0]
              if cfg.family == "vlm" else None)
    policies = (("fcfs", "eft", "edf") if args.policy == "all"
                else (args.policy,))
    for policy in policies:
        eng = ServeEngine(cfg, params,
                          EngineConfig(max_batch=args.max_batch,
                                       max_seq=args.max_seq, policy=policy),
                          vision=vision)
        for r in synth_requests(cfg, args.requests):
            eng.submit(r)
        done = eng.run()
        st = eng.latency_stats()
        print(f"{policy:<5} finished={len(done):>3}  "
              f"mean_latency={st['mean_latency']:8.1f}  "
              f"p95={st['p95_latency']:8.1f}  mean_wait={st['mean_wait']:7.1f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

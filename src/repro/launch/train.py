"""End-to-end training driver.

CPU quickstart (runs here, ~100M-class smoke or custom sizes):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 200 --batch-size 8 --seq-len 128

On a pod the same driver takes ``--mesh single|multi`` and shards the state
with the per-arch strategy (repro.distributed.sharding); the host-side data
pipeline, checkpointing, failure handling and straggler monitoring are the
same code paths exercised by the CPU run — that is the point of the
JITA-4DS layering (edge pipeline feeds VDC steps).
"""

from __future__ import annotations

import argparse
import sys


from repro.configs import ARCHS, get_config
from repro.data.loader import LoaderConfig, Prefetcher, TokenBatchLoader
from repro.launch.compile_cache import use_compile_cache
from repro.models import frontends
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer, TrainerConfig
from repro.train.fault_tolerance import FailureEvent, FailureInjector


def data_stream(cfg, batch_size: int, seq_len: int, seed: int = 0):
    epoch = 0
    while True:
        loader = TokenBatchLoader(LoaderConfig(
            batch_size=batch_size, seq_len=seq_len,
            vocab_size=cfg.vocab_size, n_docs=256, seed=seed + epoch))
        for batch in loader:
            if cfg.family == "vlm":
                batch = dict(batch, vision=frontends.fake_patch_embeddings(
                    cfg, batch_size, seed=seed))
            yield batch
        epoch += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced config (CPU-sized); --no-smoke for full")
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adamw8bit", "adafactor", "sgdm"))
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-failure-at", type=int, default=0,
                    help="simulate a worker death at this step (0 = off)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    opt = OptConfig(name=args.optimizer, lr=args.lr,
                    warmup_steps=max(args.steps // 20, 1),
                    total_steps=args.steps)
    injector = None
    if args.inject_failure_at:
        injector = FailureInjector([FailureEvent(
            step=args.inject_failure_at, worker="w1", kind="die")])
    trainer = Trainer(
        cfg, opt,
        TrainerConfig(n_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=10,
                      grad_accum=args.grad_accum, remat=args.remat),
        Prefetcher(data_stream(cfg, args.batch_size, args.seq_len)),
        injector=injector)
    out = trainer.train()
    first, last = out["history"][0]["loss"], out["history"][-1]["loss"]
    print(f"\ndone: loss {first:.4f} → {last:.4f} over {args.steps} steps, "
          f"{out['wall_s']:.1f}s wall, {out['restarts']} restart(s)")
    return 0 if last < first else 1


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set the benchmark's limits and rates, many in one process.

    python3 bench/calibrate.py readings --workload <cell> --seeds 1,2,3 \\
        --seconds 10 [--controls high,bf16|int8,fp8]
    python3 bench/calibrate.py sweep --workload <cell> --rates 2,3,4 \\
        --seconds 20 --seed 1 [--set engine.max_batch=12]

``readings`` runs the cell once per seed (set-up, a window of ``--seconds``,
the check), and with ``--controls`` also reads each named control on the
same inputs: ``high`` and ``bf16`` put the DS reference in float32 with its
matrix products in three or one bfloat16 passes in the program's place,
``int8``/``fp8`` put the language model's reference with weights rounded to
that type. ``sweep`` runs an open-loop cell at each
offered rate. ``--set`` changes a configuration value for these runs
only. Each run prints one JSON line: the compared numbers beside their
limits, and the end-to-end metrics. The benchmark's own
runs never read a control.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import run


def _emit(line: dict) -> None:
    print(json.dumps(run._finite(line)), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--set", action="append", default=[],
                    help="config override, e.g. engine.max_batch=12")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    for kv in args.set:
        key, value = kv.split("=", 1)
        *path, last = key.split(".")
        node = cell.config
        for k in path:
            node = node[k]
        node[last] = json.loads(value)
    controls = tuple(c for c in args.controls.split(",") if c)
    if args.mode == "readings":
        plan = [(int(s), None) for s in args.seeds.split(",")]
    else:
        plan = [(args.seed, float(r)) for r in args.rates.split(",")]
    try:
        for seed, rate in plan:
            c = copy.deepcopy(cell)
            if rate is not None:
                c.traffic["rate_per_s"] = rate
            t0 = time.perf_counter()
            res = run.execute(args.workload, seed, args.seconds, False, cell=c,
                              controls=controls, t_process=t0)
            _emit({"workload": args.workload, "seed": seed, "rate": rate,
                   "correct": res["correct"], "attempted": res["attempted"],
                   "failed": res["failed"],
                   "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                   "checks": res["checks"],
                   "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                   "run_s": time.perf_counter() - t0})
    except run.NoDevice as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())

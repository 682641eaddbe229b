"""Operations and bytes of the work the benchmark's cells ask for.

Each count is the algorithm's own: every operand read once and every result
written once, the least any implementation must move, and the arithmetic
the algorithm cannot skip. So a fused or hand-written kernel is held to the
same work as the plain one, and a share of the roofline computed from these
counts cannot pass 100% unless the time leaves part of the work out.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

import numpy as np


def nbytes(tree: Any) -> int:
    """Bytes of every array leaf in a (nested) tuple/list/dict."""
    if isinstance(tree, dict):
        return sum(nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(nbytes(v) for v in tree)
    size = getattr(tree, "size", None)
    dtype = getattr(tree, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * np.dtype(dtype).itemsize


#: operators whose output dict passes its input ``x`` through unchanged
_PASS_X = ("kmeans", "sweep_clustering", "train_cluster", "linreg")


def ds_operands(op: str, args, out) -> Tuple[list, Any]:
    """What the operator itself reads and writes, out of the executor's
    arguments (the predecessors' whole outputs) and the task's output."""
    if op in ("pca", "kmeans", "sweep_clustering", "linreg"):
        ins = [args[0]["x"]]
    elif op == "train_cluster":
        ins = [args[0]["x"], args[0]["fit"][0]]
    elif op == "score":
        ins = [args[1]["x"], args[1]["model"]]
    elif op == "join":
        ins = [args[0], args[1], args[2][0]]
    else:
        ins = list(args)
    if op in _PASS_X:
        out = {k: v for k, v in out.items() if k != "x"}
    return ins, out


def ds_task_work(op: str, params: Dict[str, Any], args: Iterable[Any],
                 out: Any) -> Tuple[float, float]:
    """``(flops, bytes)`` of one DS operator call, from the executor's
    arguments and the task's output.

    Bytes: what the operator reads and writes, once each (the clustering
    operators' iterations may keep their points on chip). Flops: per element
    of the ``n x d`` input, what the operator must compute; the clustering
    operators count ``3 n k d`` per assignment pass (difference, square,
    add) and ``n d`` per centroid update.
    """
    ins, res = ds_operands(op, list(args), out)
    bytes_ = float(nbytes(ins) + nbytes(res))
    shape = ins[0].shape
    n, d = int(shape[0]), int(shape[1]) if len(shape) > 1 else 1
    nd = float(n * d)
    k = params.get("k", 4)
    if op in ("ingest", "select_columns"):
        flops = 0.0
    elif op in ("export", "join", "window_agg"):
        flops = 2 * nd
    elif op == "sql_transform":
        flops = 3 * nd
    elif op in ("clean_missing", "summarize", "filter_features", "linreg",
                "score"):
        flops = 4 * nd
    elif op == "anomaly":
        flops = 8 * nd
    elif op == "pca":
        flops = 2 * nd * d + 2 * nd * k + params.get("iters", 16) * 2 * d * d * k
    elif op in ("kmeans", "train_cluster"):
        if op == "train_cluster":
            k = int(ins[1].shape[0])
        it = params.get("iters", 10 if op == "kmeans" else 20)
        flops = (it + 1) * 3 * nd * k + it * nd
    elif op == "sweep_clustering":
        it = params.get("iters", 10)
        flops = sum((it + 1) * 3 * nd * kk + it * nd
                    for kk in params.get("ks", (2, 3, 4, 6)))
    else:
        raise KeyError(f"no work count for operator {op!r}")
    return flops, bytes_


def lm_weight_counts(m: Dict[str, Any]) -> Dict[str, float]:
    """Parameter counts of a dense decoder from the configuration's
    ``model`` section: ``matmul`` (every weight a token multiplies,
    LM head included) and ``small`` (norms and biases); the embedding table
    is looked up, not multiplied."""
    d, L = m["d_model"], m["n_layers"]
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"]
    small_layer = 4 * d + (q + 2 * kv + d if m.get("use_bias") else 0)
    return {"matmul": float(L * per_layer + d * m["vocab_size"]),
            "small": float(L * small_layer + 2 * d)}


def decode_step_work(m: Dict[str, Any], kv_lens: Iterable[int],
                     weight_bytes: int = 2,
                     cache_bytes: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of one batched decode step over the active slots.

    ``kv_lens`` holds, per active slot, the positions its new token attends
    to (the cached ones and itself). Bytes: every matmul weight once, the
    embedding rows looked up, norms and biases, the K/V of each slot's
    valid positions read once and the new K/V written once. Flops: two per
    weight per token, and ``4 * heads * head_dim`` per layer and attended
    position (scores and values).
    """
    kv_lens = [int(v) for v in kv_lens]
    b = len(kv_lens)
    w = lm_weight_counts(m)
    L, d = m["n_layers"], m["d_model"]
    kv_row = 2 * m["n_kv_heads"] * m["head_dim"] * cache_bytes * L
    bytes_ = ((w["matmul"] + w["small"] + b * d) * weight_bytes
              + kv_row * (sum(kv_lens)))
    flops = 2 * w["matmul"] * b + 4 * m["n_heads"] * m["head_dim"] * L * sum(kv_lens)
    return float(flops), float(bytes_)


def min_time(flops: float, bytes_: float, peaks: Dict[str, float]) -> float:
    """The least time the chip needs for ``flops`` and ``bytes_``."""
    return max(flops / peaks["flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])

"""Operations and bytes of the work the benchmark's cells ask for.

Each count is the algorithm's own: every operand read once and every result
written once, the least any implementation must move, and the arithmetic
the algorithm cannot skip. So a fused or hand-written kernel is held to the
same work as the plain one, and a share of the roofline computed from these
counts cannot pass 100% unless the time leaves part of the work out.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

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


#: settings of the program's decoder whose work no count here covers
UNCOUNTED = ("cross_attn_period", "n_shared_experts", "qk_norm",
             "sandwich_norm")
#: the program's defaults of the decoder settings counted here, mirrored so
#: that the yardstick imports nothing of the program
DEFAULTS = {"layer_pattern": ("attn",), "first_k_dense": 0,
            "first_dense_d_ff": 0, "moe_period": 0, "moe_offset": 1,
            "n_experts": 0, "moe_d_ff": 0, "norm": "rmsnorm",
            "use_bias": False, "head_dim": 0, "ssm_state": 16, "ssm_conv": 4,
            "ssm_expand": 2, "ssm_dt_rank": 0}


def _setting(m: Dict[str, Any], key: str) -> Any:
    return m.get(key, DEFAULTS[key])


def layer_kinds(m: Dict[str, Any]) -> List[Tuple[str, str, int]]:
    """``(mixer, ff, d_ff)`` of every layer, in order, as the program lays
    them out from the configuration's keys: ``first_k_dense`` leading dense
    layers (``first_dense_d_ff`` wide) of the pattern's first mixer, then
    ``layer_pattern`` cycled, every ``moe_period``-th layer from
    ``moe_offset`` on an MoE layer where there are ``n_experts``. ``mixer``
    is ``attn`` or ``mamba``; ``ff`` is ``dense``, ``moe`` or ``none`` (no
    feed-forward where ``d_ff`` is 0)."""
    for key in UNCOUNTED:
        if m.get(key):
            raise ValueError(f"no decode work count for {key}={m[key]!r}")
    pattern = tuple(_setting(m, "layer_pattern"))
    lead = _setting(m, "first_k_dense")
    period, offset = _setting(m, "moe_period"), _setting(m, "moe_offset")
    experts = bool(_setting(m, "n_experts")) and bool(period)
    out = []
    for i in range(m["n_layers"]):
        if i < lead:
            mixer, moe = pattern[0], False
            d_ff = _setting(m, "first_dense_d_ff") or m["d_ff"]
        else:
            j = i - lead
            mixer = pattern[j % len(pattern)]
            moe = experts and j % period == offset % period
            d_ff = m["d_ff"]
        if mixer not in ("attn", "mamba"):
            raise ValueError(f"no decode work count for mixer {mixer!r}")
        ff = "moe" if moe else "dense" if m["d_ff"] > 0 else "none"
        out.append((mixer, ff, d_ff))
    return out


def _head_dim(m: Dict[str, Any]) -> int:
    return _setting(m, "head_dim") or m["d_model"] // m["n_heads"]


def _mamba_sizes(m: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """``(d_inner, d_state, dt_rank, conv)`` of a Mamba mixer."""
    d = m["d_model"]
    return (_setting(m, "ssm_expand") * d, _setting(m, "ssm_state"),
            _setting(m, "ssm_dt_rank") or -(-d // 16), _setting(m, "ssm_conv"))


def lm_weight_counts(m: Dict[str, Any]) -> Dict[str, float]:
    """Parameter counts of a decoder from the configuration's ``model``
    section, layer by layer (``layer_kinds``): ``matmul`` (every weight
    outside the routed experts that a token multiplies: projections, a
    Mamba mixer's depthwise conv, dense feed-forwards, routers and the LM
    head), ``small`` (norms, biases, a Mamba mixer's per-channel ``A``,
    ``D`` and step bias) and ``expert`` (one routed expert). The embedding
    table is looked up, not multiplied."""
    d = m["d_model"]
    q = m["n_heads"] * _head_dim(m)
    kv = m["n_kv_heads"] * _head_dim(m)
    norm = d * (2 if _setting(m, "norm") == "layernorm" else 1)
    di, n, r, c = _mamba_sizes(m)
    f = _setting(m, "moe_d_ff") or m["d_ff"]
    matmul, small = d * m["vocab_size"], norm
    for mixer, ff, d_ff in layer_kinds(m):
        if mixer == "attn":
            matmul += d * q + 2 * d * kv + q * d
            small += (q + 2 * kv + d) if _setting(m, "use_bias") else 0
        else:
            matmul += d * 2 * di + c * di + di * (r + 2 * n) + r * di + di * d
            small += di * (n + 3)
        small += norm * (1 if ff == "none" else 2)
        if ff == "dense":
            matmul += 3 * d * d_ff
        elif ff == "moe":
            matmul += d * m["n_experts"]
    return {"matmul": float(matmul), "small": float(small),
            "expert": float(3 * d * f)}


def decode_step_work(m: Dict[str, Any], kv_lens: Iterable[int],
                     weight_bytes: int = 2, cache_bytes: int = 2, *,
                     moe: Optional[Tuple[int, int]] = None
                     ) -> Tuple[float, float]:
    """``(flops, bytes)`` of one batched decode step over the active slots.

    ``kv_lens`` holds, per active slot, the positions its new token attends
    to (the cached ones and itself). ``moe`` is ``(experts_touched,
    rows_routed)`` summed over the step's MoE layers: the held experts that
    at least one token chose, and the token-choices routed to them. Only
    the program can count these, so a configuration with MoE layers needs
    them.

    Bytes: every weight outside the routed experts once (``lm_weight_counts``),
    the embedding rows looked up, each touched expert's weights once; per
    attention layer the K/V of each slot's valid positions read once and the
    new K/V written once; per Mamba layer each active slot's state (``h`` in
    float32 and the conv ring) read and written once. Flops: two per
    multiplied weight per token and per routed token-choice of an expert;
    ``4 * heads * head_dim`` per attention layer and attended position
    (scores and values); ``6 * d_inner * d_state`` per Mamba layer and
    token (the step times ``A``, the decay of ``h``, the step times ``B x``,
    the update's add, and ``C h``'s multiply and add).
    """
    kv_lens = [int(v) for v in kv_lens]
    b, kv = len(kv_lens), sum(kv_lens)
    kinds = layer_kinds(m)
    n_attn = sum(mixer == "attn" for mixer, _ff, _f in kinds)
    n_mamba = len(kinds) - n_attn
    if moe is None:
        if any(ff == "moe" for _mixer, ff, _f in kinds):
            raise ValueError("a configuration with MoE layers needs moe="
                             "(experts_touched, rows_routed)")
        moe = (0, 0)
    touched, routed = moe
    w = lm_weight_counts(m)
    d, hd = m["d_model"], _head_dim(m)
    di, n, _r, c = _mamba_sizes(m)
    kv_row = 2 * m["n_kv_heads"] * hd * cache_bytes * n_attn
    state = 2 * (4 * di * n + cache_bytes * (c - 1) * di) * n_mamba
    bytes_ = ((w["matmul"] + w["small"] + b * d) * weight_bytes
              + kv_row * kv + state * b + w["expert"] * touched * weight_bytes)
    flops = (2 * w["matmul"] * b + 4 * m["n_heads"] * hd * n_attn * kv
             + 6 * di * n * n_mamba * b + 2 * w["expert"] * routed)
    return float(flops), float(bytes_)


def min_time(flops: float, bytes_: float, peaks: Dict[str, float]) -> float:
    """The least time the chip needs for ``flops`` and ``bytes_``."""
    return max(flops / peaks["flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])

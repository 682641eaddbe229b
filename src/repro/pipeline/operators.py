"""The paper's DS operators with dual host/device backends ("flexible binary").

Paper §4: the compiler emits a *flexible binary* per task so the runtime can
invoke it on **any** processing element. The TPU-native analogue implemented
here: every operator has

  * a **host** backend — pure ``numpy``, runs on the pod-worker CPU ("edge");
  * a **device** backend — pure ``jax.numpy`` (jit-able), runs on a TPU mesh
    slice ("VDC");

with *identical semantics* (the test-suite asserts allclose parity), so the
scheduler's placement decision never changes results, only cost.

All operators are shape-static (masks instead of boolean filtering) so the
device backend compiles once per shape — a deliberate TPU adaptation of the
paper's dynamically-shaped Spark-style operators (DESIGN.md §2).

Operator catalogue = the 16 functions of the paper's DS workload (Fig. 5):
SQL transform, data summarisation, column selection, filter-based feature
selection, k-means clustering, time-series anomaly detection, sweep
clustering, train-clustering-model, PCA, linear regression, scoring, join,
ingest, window aggregation, cleaning, export.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import span


# ---------------------------------------------------------------------------
# Generic implementations, parameterised by the array namespace ``xp``
# (numpy or jax.numpy). Everything below is branch-free / shape-static.
# ---------------------------------------------------------------------------

def _ingest(xp, raw: Any) -> Any:
    """Parse raw sensor batch → float32 matrix (n_rows, n_cols)."""
    x = xp.asarray(raw, dtype=xp.float32)
    if x.ndim == 1:
        x = x[:, None]
    return x


def _sql_transform(xp, x, *, scale: float = 1.0, shift: float = 0.0,
                   clip_lo: float = -1e9, clip_hi: float = 1e9):
    """Projection + scalar WHERE-style clamp (SELECT scale*c+shift ...)."""
    return xp.clip(x * scale + shift, clip_lo, clip_hi)


def _clean_missing(xp, x):
    """Replace NaN/inf by the column mean of finite entries."""
    finite = xp.isfinite(x)
    safe = xp.where(finite, x, 0.0)
    cnt = xp.maximum(finite.sum(axis=0), 1).astype(x.dtype)
    mean = safe.sum(axis=0) / cnt
    return xp.where(finite, x, mean[None, :])


def _select_columns(xp, x, *, k: int = 4):
    """Keep the k highest-variance columns (stable order by index)."""
    k = min(k, x.shape[1])
    var = x.var(axis=0)
    # indices of top-k variance, re-sorted ascending for determinism
    idx = xp.sort(xp.argsort(-var)[:k])
    return xp.take(x, idx, axis=1)


def _summarize(xp, x):
    """Per-column summary stats → (5, n_cols): mean,std,min,max,median-ish."""
    mean = x.mean(axis=0)
    std = x.std(axis=0)
    lo = x.min(axis=0)
    hi = x.max(axis=0)
    med = xp.quantile(x, 0.5, axis=0).astype(x.dtype)
    return xp.stack([mean, std, lo, hi, med])


def _shift_down(xp, x, k: int, fill: float):
    """Row t of the result is x[t-k]; the first k rows (0 < k < n) are
    ``fill``."""
    head = xp.full((k,) + x.shape[1:], fill, dtype=x.dtype)
    return xp.concatenate([head, x[:x.shape[0] - k]], axis=0)


def _window_agg(xp, x, *, window: int = 8, agg: str = "mean"):
    """Sliding-window aggregate along axis 0 (same-length, causal).

    Window w uses rows [t-w+1, t] clamped at 0. Windows are reduced by
    binary doubling, ``P_2k[t] = op(P_k[t], P_k[t-k])`` combined over the
    set bits of w: O(log w) shape-static passes, the same additions in the
    same order on both backends (a prefix-sum difference would round
    differently per backend and grow its error with the stream length).
    """
    n = x.shape[0]
    w = max(1, min(window, n))
    if agg in ("mean", "sum"):
        op, fill = xp.add, 0.0
    elif agg == "max":
        op, fill = xp.maximum, -np.inf
    else:
        raise ValueError(f"unknown agg {agg!r}")
    acc, p, k, off = None, x, 1, 0
    while True:
        if w & k:
            term = p if off == 0 else _shift_down(xp, p, off, fill)
            acc = term if acc is None else op(acc, term)
            off += k
        if 2 * k > w:
            break
        p = op(p, _shift_down(xp, p, k, fill))
        k *= 2
    if agg != "mean":
        return acc
    cnt = xp.minimum(xp.arange(1, n + 1), w).astype(x.dtype)
    return acc / cnt.reshape((n,) + (1,) * (x.ndim - 1))


def _anomaly(xp, x, *, window: int = 16, z: float = 3.0):
    """Time-series anomaly flags: |x - rolling_mean| > z * rolling_std."""
    mu = _window_agg(xp, x, window=window, agg="mean")
    sq = _window_agg(xp, x * x, window=window, agg="mean")
    var = xp.maximum(sq - mu * mu, 1e-12)
    flags = (xp.abs(x - mu) > z * xp.sqrt(var)).astype(x.dtype)
    return flags


def _filter_features(xp, x, *, k: int = 4, target_col: int = 0):
    """Filter-based feature selection: top-k |corr with target| columns."""
    y = x[:, target_col]
    xc = x - x.mean(axis=0, keepdims=True)
    yc = y - y.mean()
    cov = (xc * yc[:, None]).mean(axis=0)
    denom = xp.sqrt(xp.maximum(xc.var(axis=0) * yc.var(), 1e-12))
    corr = xp.abs(cov / denom)
    # never re-select the target itself
    corr = corr.at[target_col].set(-1.0) if hasattr(corr, "at") else _set(corr, target_col, -1.0)
    k = min(k, x.shape[1] - 1)
    idx = xp.sort(xp.argsort(-corr)[:k])
    return xp.take(x, idx, axis=1)


def _set(arr, i, v):  # numpy in-place analogue of .at[].set()
    arr = arr.copy()
    arr[i] = v
    return arr


def _pca(xp, x, *, k: int = 2, iters: int = 16):
    """Top-k PCA scores via subspace (orthogonal) iteration — identical
    deterministic algorithm on both backends (no LAPACK divergence)."""
    xc = x - x.mean(axis=0, keepdims=True)
    d = x.shape[1]
    k = min(k, d)
    cov = xc.T @ xc / max(x.shape[0] - 1, 1)
    # deterministic start: identity slab
    q = xp.eye(d, dtype=x.dtype)[:, :k]
    for _ in range(iters):
        z = cov @ q
        q, _r = xp.linalg.qr(z)
    # sign-fix each component for cross-backend determinism
    sgn = xp.sign(q[xp.argmax(xp.abs(q), axis=0), xp.arange(k)])
    q = q * sgn[None, :]
    return xc @ q


def _assign(xp, x, cent):
    """Nearest centroid per row, and the (n, k) squared distances."""
    d2 = ((x[:, None, :] - cent[None, :, :]) ** 2).sum(-1)
    return xp.argmin(d2, axis=1), d2


def _kmeans_step(xp, x, cent):
    assign, _ = _assign(xp, x, cent)
    onehot = (assign[:, None] == xp.arange(cent.shape[0])[None, :]).astype(x.dtype)
    cnt = xp.maximum(onehot.sum(0), 1.0)
    new = (onehot.T @ x) / cnt[:, None]
    # keep empty clusters where they were
    return xp.where((onehot.sum(0) > 0)[:, None], new, cent)


def _kmeans_init(xp, x, k: int):
    """Deterministic spread init: k evenly-spaced rows in stream order.

    Picking by position keeps the init continuous in x: a choice by rank
    (e.g. rows at evenly-spaced ranks of the row norms) jumps to another
    row when two norms swap order, which last-digit differences between
    backends do at scale, and Lloyd's iterations then settle elsewhere."""
    n = x.shape[0]
    return xp.take(x, (xp.arange(k) * max(n // k, 1)) % n, axis=0)


def _lloyd(xp, x, cent, iters: int):
    """``iters`` Lloyd updates from ``cent``; returns (centroids, the
    assignments to them, inertia)."""
    for _ in range(iters):
        cent = _kmeans_step(xp, x, cent)
    assign, d2 = _assign(xp, x, cent)
    inertia = xp.take_along_axis(d2, assign[:, None], axis=1).sum()
    return cent, assign, inertia


def _kmeans(xp, x, *, k: int = 4, iters: int = 10):
    """Lloyd's k-means; returns (centroids, assignments, inertia)."""
    return _lloyd(xp, x, _kmeans_init(xp, x, k), iters)


def _sweep_clustering(xp, x, *, ks: Tuple[int, ...] = (2, 3, 4, 6),
                      iters: int = 10, penalty: float = 0.05):
    """Parameter sweep over k; pick argmin( inertia/n + penalty·k )."""
    best_score, best_cent, best_assign, best_k = None, None, None, None
    n = x.shape[0]
    for k in ks:
        cent, assign, inertia = _kmeans(xp, x, k=k, iters=iters)
        score = inertia / n + penalty * k * float(x.var())
        # host/device both execute the full sweep; selection is python-side
        score_f = float(score)
        if best_score is None or score_f < best_score:
            best_score, best_cent, best_assign, best_k = score_f, cent, assign, k
    return best_cent, best_assign, best_k


def _train_cluster(xp, x, cent, *, iters: int = 20):
    """Refine a clustering model from given centroids (paper's
    'train clustering model' node consuming kmeans/sweep output)."""
    return _lloyd(xp, x, cent, iters)


def _linreg(xp, x, *, target_col: int = 0, ridge: float = 1e-6):
    """Ridge least-squares of target_col on the remaining columns.

    Returns (w, b) with deterministic normal-equations solve.
    """
    n, d = x.shape
    y = x[:, target_col]
    mask = xp.arange(d) != target_col
    feats = xp.take(x, xp.nonzero(mask, size=d - 1)[0], axis=1) if hasattr(xp, "nonzero") and xp is not np else x[:, np.arange(d)[mask]]
    xm = feats.mean(axis=0, keepdims=True)
    ym = y.mean()
    xc = feats - xm
    yc = y - ym
    gram = xc.T @ xc + ridge * xp.eye(d - 1, dtype=x.dtype)
    w = xp.linalg.solve(gram, xc.T @ yc)
    b = ym - (xm[0] * w).sum()
    return w, b


def _score(xp, x, w, b, *, target_col: int = 0):
    """Apply a linreg model; return (pred, mse, r2)."""
    d = x.shape[1]
    if xp is np:
        feats = x[:, np.arange(d)[np.arange(d) != target_col]]
    else:
        idx = xp.nonzero(xp.arange(d) != target_col, size=d - 1)[0]
        feats = xp.take(x, idx, axis=1)
    y = x[:, target_col]
    pred = feats @ w + b
    err = pred - y
    mse = (err * err).mean()
    denom = xp.maximum(((y - y.mean()) ** 2).mean(), 1e-12)
    r2 = 1.0 - mse / denom
    return pred, mse, r2


def _join(xp, *parts):
    """Concatenate result tables column-wise after row-broadcasting."""
    parts = [xp.asarray(p, dtype=xp.float32) for p in parts]
    parts = [p[:, None] if p.ndim == 1 else p for p in parts]
    n = max(p.shape[0] for p in parts)
    out = []
    for p in parts:
        if p.shape[0] != n:  # tile summaries up to the longest table
            p = xp.take(p, xp.arange(n) % p.shape[0], axis=0)
        out.append(p)
    return xp.concatenate(out, axis=1)


def _export(xp, x):
    """Terminal digest: (count, mean, l2) — cheap, deterministic."""
    return xp.stack([xp.asarray(x.size, dtype=xp.float32),
                     x.mean().astype(xp.float32),
                     xp.sqrt((x * x).sum()).astype(xp.float32)])


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

_GENERIC: Dict[str, Callable] = {
    "ingest": _ingest,
    "sql_transform": _sql_transform,
    "clean_missing": _clean_missing,
    "select_columns": _select_columns,
    "summarize": _summarize,
    "window_agg": _window_agg,
    "anomaly": _anomaly,
    "filter_features": _filter_features,
    "pca": _pca,
    "kmeans": _kmeans,
    "sweep_clustering": _sweep_clustering,
    "train_cluster": _train_cluster,
    "linreg": _linreg,
    "score": _score,
    "join": _join,
    "export": _export,
}


def host_backend(op: str) -> Callable:
    """Host (numpy) implementation of ``op``, inside a ``jita.host.<op>``
    span."""
    fn = _GENERIC[op]

    def run(*args, **kwargs):
        with span(f"host.{op}", op=op):
            return fn(np, *args, **kwargs)

    return run


def device_backend(op: str) -> Callable:
    """Device (jax.numpy) implementation of ``op``.

    Every device backend is the generic operator over ``jax.numpy``,
    dispatched op by op; none calls a Pallas kernel yet
    (repro.kernels holds kernels for window_agg and the kmeans
    assignment step, checked against these operators by the tests).
    Matmuls run at full float32 precision: a TPU's default is a single
    bfloat16 pass, which would break parity with the host backend. The
    call sits in a ``jita.device.<op>`` span: the host's time issuing the
    operator, with any copy of a host input and any wait on a result
    inside it.
    """
    fn = _GENERIC[op]

    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"), span(f"device.{op}", op=op):
            return fn(jnp, *args, **kwargs)

    return run


def backends(op: str) -> Dict[str, Callable]:
    """Both backends for a Task's ``backends`` field (the flexible binary)."""
    return {"host": host_backend(op), "device": device_backend(op)}


OPERATORS = tuple(_GENERIC)

"""Plain reference of the 16-task DS workload (JITA-4DS, Fig. 5).

Written from the operators' stated semantics, not from the program: each
operator is a few lines of array arithmetic over an array namespace ``xp``
(numpy in float64 for the check; ``jax.numpy`` in float32 with a chosen
matmul precision for the control), and every matrix product goes through
the ``mm`` argument so that its precision is the caller's choice.

The wiring (which output feeds which task) and the operator parameters come
from the configuration file, under ``operators``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict


def _causal_mean(xp, x, w: int):
    """Mean of rows ``[t-w+1, t]`` (clamped at row 0), as a plain sum of
    ``w`` shifted copies."""
    n = x.shape[0]
    acc = x
    for s in range(1, w):
        pad = xp.zeros((s,) + x.shape[1:], x.dtype)
        acc = acc + xp.concatenate([pad, x[: n - s]], axis=0)
    cnt = xp.minimum(xp.arange(1, n + 1), w).astype(x.dtype)
    return acc / cnt[:, None]


def _top_columns(xp, score, k: int):
    """Indices of the ``k`` largest scores, in ascending index order."""
    order = xp.argsort(-score, kind="stable") if xp.__name__ == "numpy" \
        else xp.argsort(-score, stable=True)
    return xp.sort(order[:k])


def _d2(xp, x, c):
    return ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)


def _lloyd(xp, mm, x, cent, iters: int):
    k = cent.shape[0]
    for _ in range(iters):
        a = xp.argmin(_d2(xp, x, cent), axis=1)
        onehot = (a[:, None] == xp.arange(k)[None, :]).astype(x.dtype)
        cnt = onehot.sum(0)
        new = mm(onehot.T, x) / xp.maximum(cnt, 1.0)[:, None]
        cent = xp.where((cnt > 0)[:, None], new, cent)
    d2 = _d2(xp, x, cent)
    a = xp.argmin(d2, axis=1)
    return cent, a, d2.min(axis=1).sum()


def _kmeans(xp, mm, x, k: int, iters: int):
    n = x.shape[0]
    start = x[(xp.arange(k) * max(n // k, 1)) % n]
    return _lloyd(xp, mm, x, start, iters)


def _orthonormal(xp, z):
    """Gram-Schmidt columns of ``z`` (positive diagonal of R)."""
    cols = []
    for j in range(z.shape[1]):
        v = z[:, j]
        for u in cols:
            v = v - (u * v).sum() * u
        cols.append(v / xp.sqrt((v * v).sum()))
    return xp.stack(cols, axis=1)


def pipeline(raw, ops: Dict[str, Any], xp, dtype,
             mm: Callable) -> Dict[str, Any]:
    """Every task's output, keyed by task name, in the program's output
    structure."""
    o: Dict[str, Any] = {}
    x = xp.asarray(raw).astype(dtype)
    o["ingest"] = x
    sql = ops["sql_transform"]
    x = xp.clip(x * sql["scale"] + sql["shift"], sql["clip_lo"], sql["clip_hi"])
    o["sql_transform"] = x
    ok = xp.isfinite(x)
    mean = xp.where(ok, x, 0).sum(0) / xp.maximum(ok.sum(0), 1)
    x = xp.where(ok, x, mean[None, :])
    o["clean_missing"] = x
    x = x[:, _top_columns(xp, x.var(axis=0), ops["select_columns"]["k"])]
    o["select_columns"] = x
    o["summarize"] = xp.stack([x.mean(0), x.std(0), x.min(0), x.max(0),
                               xp.median(x, axis=0)])
    wa = _causal_mean(xp, x, ops["window_agg"]["window"])
    o["window_agg"] = wa
    an = ops["anomaly"]
    mu = _causal_mean(xp, wa, an["window"])
    var = xp.maximum(_causal_mean(xp, wa * wa, an["window"]) - mu * mu, 1e-12)
    o["anomaly"] = (xp.abs(wa - mu) > an["z"] * xp.sqrt(var)).astype(dtype)

    ff = ops["filter_features"]
    t = ff["target_col"]
    xc = x - x.mean(0)
    yc = xc[:, t]
    corr = xp.abs((xc * yc[:, None]).mean(0)
                  / xp.sqrt(xp.maximum(x.var(0) * x[:, t].var(), 1e-12)))
    corr = xp.where(xp.arange(x.shape[1]) == t, -1.0, corr)
    f = x[:, _top_columns(xp, corr, ff["k"])]
    o["filter_features"] = {"x": f}

    pc = ops["pca"]
    fc = f - f.mean(0)
    cov = mm(fc.T, fc) / (f.shape[0] - 1)
    q = xp.eye(f.shape[1], dtype=dtype)[:, : pc["k"]]
    for _ in range(pc["iters"]):
        q = _orthonormal(xp, mm(cov, q))
    lead = xp.argmax(xp.abs(q), axis=0)
    q = q * xp.sign(q[lead, xp.arange(pc["k"])])[None, :]
    p = mm(fc, q)
    o["pca"] = {"x": p}

    km = ops["kmeans"]
    o["kmeans"] = {"x": f, "fit": _kmeans(xp, mm, f, km["k"], km["iters"])}
    sw = ops["sweep_clustering"]
    best = None
    for k in sw["ks"]:
        cent, a, inertia = _kmeans(xp, mm, p, k, sw["iters"])
        s = float(inertia / p.shape[0] + sw["penalty"] * k * p.var())
        if best is None or s < best[0]:
            best = (s, cent, a, k)
    o["sweep_clustering"] = {"x": p, "fit": best[1:]}
    o["train_cluster"] = {"x": f, "fit": _lloyd(
        xp, mm, f, o["kmeans"]["fit"][0], ops["train_cluster"]["iters"])}

    lr = ops["linreg"]
    t = lr["target_col"]
    keep = [j for j in range(p.shape[1]) if j != t]
    feats, y = p[:, keep], p[:, t]
    fm, ym = feats.mean(0), y.mean()
    gram = mm((feats - fm).T, feats - fm) + lr["ridge"] * xp.eye(len(keep),
                                                                 dtype=dtype)
    w = xp.linalg.solve(gram, mm((feats - fm).T, (y - ym)[:, None]))[:, 0]
    b = ym - (fm * w).sum()
    o["linreg"] = {"x": p, "model": (w, b)}
    pred = mm(feats, w[:, None])[:, 0] + b
    mse = ((pred - y) ** 2).mean()
    o["score"] = (pred, mse, 1.0 - mse / xp.maximum(y.var(), 1e-12))

    s5 = o["summarize"]
    n = x.shape[0]
    tiled = xp.concatenate([s5] * -(-n // s5.shape[0]), axis=0)[:n]
    j = xp.concatenate([tiled, o["anomaly"], pred[:, None]], axis=1)
    o["join"] = j
    o["export"] = xp.stack([xp.asarray(float(j.size), dtype), j.mean(),
                            xp.sqrt((j * j).sum())])
    return o

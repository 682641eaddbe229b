"""DS operators: host/device parity + window properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.pipeline import operators as ops
from repro.pipeline import windows as W


@pytest.fixture(scope="module")
def x():
    rng = np.random.default_rng(0)
    a = rng.normal(0, 1, (96, 6)).astype(np.float32)
    a[5, 3] = np.nan
    return a


def _pairs(res):
    return res if isinstance(res, tuple) else (res,)


@pytest.mark.parametrize("op", ops.OPERATORS)
def test_host_device_parity(op, x):
    clean = np.nan_to_num(x)
    h, d = ops.host_backend(op), ops.device_backend(op)
    if op == "ingest":
        args = (x,)
    elif op == "train_cluster":
        args = (clean, clean[:4])
    elif op == "score":
        w, b = ops.host_backend("linreg")(clean)
        args = (clean, w, b)
    elif op == "join":
        args = (x[:8], x[:4, :2])
    elif op == "clean_missing":
        args = (x,)
    else:
        args = (clean,)
    for a, b in zip(_pairs(h(*args)), _pairs(d(*args)), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_clean_missing_fills_nan(x):
    out = ops.host_backend("clean_missing")(x)
    assert np.isfinite(out).all()
    # untouched entries preserved
    mask = np.isfinite(x)
    np.testing.assert_array_equal(out[mask], x[mask])


def test_kmeans_assignments_valid(x):
    cent, assign, inertia = ops.host_backend("kmeans")(np.nan_to_num(x), k=4)
    assert cent.shape == (4, x.shape[1])
    assert set(np.unique(assign)) <= set(range(4))
    assert inertia >= 0


def test_window_agg_matches_bruteforce():
    rng = np.random.default_rng(1)
    v = rng.normal(0, 1, (40, 3)).astype(np.float32)
    out = ops.host_backend("window_agg")(v, window=5, agg="mean")
    for t in range(40):
        lo = max(t - 4, 0)
        np.testing.assert_allclose(out[t], v[lo:t + 1].mean(0), rtol=1e-5)


def _join_by_copies(xp, s, table, col):
    """The join of a summary, a table and a column, with the summary
    broadcast as a list of whole copies: the oracle."""
    s = s[:, None] if s.ndim == 1 else s
    n = table.shape[0]
    reps = -(-n // s.shape[0])
    return xp.concatenate([xp.concatenate([s] * reps, axis=0)[:n], table,
                           col[:, None]], axis=1)


@pytest.mark.parametrize("summary", [(4,), (4, 3), (5,), (5, 4), "full"],
                         ids=["4", "4x3", "5", "5x4", "full"])
@pytest.mark.parametrize("n", [8, 4096, 4099])
@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_join_broadcast_matches_copies(xp, n, summary):
    rng = np.random.default_rng(n)
    shape = (n, 2) if summary == "full" else summary
    s = rng.normal(0, 1, shape).astype(np.float32)
    table = rng.normal(0, 1, (n, 4)).astype(np.float32)
    col = rng.normal(0, 1, n).astype(np.float32)
    got = ops._join(xp, s, table, col)
    want = _join_by_copies(xp, s, table, col)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_join_program_size_independent_of_rows():
    """The device join's program does not grow with the rows it fills."""
    def eqns(n):
        args = (jnp.zeros((5, 4), jnp.float32), jnp.zeros((n, 4), jnp.float32),
                jnp.zeros((n,), jnp.float32))
        return len(jax.make_jaxpr(lambda *a: ops._join(jnp, *a))(*args).eqns)
    assert eqns(4096) == eqns(65536)


# -- windows ---------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(n=st.integers(2, 200), size=st.floats(0.5, 20))
def test_sliding_step_eq_size_is_tumbling(n, size):
    rng = np.random.default_rng(n)
    ts = np.sort(rng.uniform(0, 50, n))
    tb = W.tumbling(ts, size)
    sl = W.sliding(ts, size, size)
    assert [(b.lo, b.hi) for b in tb] == [(b.lo, b.hi) for b in sl]


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 150))
def test_tumbling_partitions_rows(n):
    rng = np.random.default_rng(n)
    ts = np.sort(rng.uniform(0, 30, n))
    bounds = W.tumbling(ts, 3.0)
    covered = sorted(i for b in bounds for i in range(b.lo, b.hi))
    assert covered == list(range(n))   # every row exactly once


def test_landmark_grows_monotonically():
    ts = np.linspace(0, 100, 101)
    bounds = W.landmark(ts, 0.0, 10.0)
    sizes = [b.n_rows for b in bounds]
    assert sizes == sorted(sizes)
    assert bounds[-1].hi == len(ts)


def test_combine_history_prefers_live():
    hist = np.arange(10, dtype=np.float64)
    live = np.arange(5, 8, dtype=np.float64)
    hv = np.ones((10, 1), np.float32)
    lv = np.zeros((3, 1), np.float32)
    ts, vals = W.combine_history_and_live(hist, hv, live, lv)
    assert len(ts) == 5 + 3            # hist[:5] + live
    assert (vals[-3:] == 0).all()

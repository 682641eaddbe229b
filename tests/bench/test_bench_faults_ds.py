"""A DS pipeline run with the timed path broken underneath must come out
not correct: one case per fault the cell can have. The run is the whole
harness on the CPU at a small size, past the look for a chip."""

import pytest
import run
from repro.pipeline import operators

ORIG = dict(operators._GENERIC)


def _altered(xp, x, **kw):
    """An answer altered where it is produced."""
    return ORIG["window_agg"](xp, x, **kw) * 1.001


def _unchanged(xp, x, cent, **kw):
    """A refinement step that returns its state unchanged."""
    return ORIG["train_cluster"](xp, x, cent, iters=0)


def _half(xp, x):
    """Half of the batch left out, the statistics taken over the rest."""
    return ORIG["summarize"](xp, x[: x.shape[0] // 2])


@pytest.mark.parametrize("op,fault", [("window_agg", _altered),
                                      ("train_cluster", _unchanged),
                                      ("summarize", _half)])
def test_fault_is_not_correct(checkout, monkeypatch, op, fault):
    monkeypatch.setitem(operators._GENERIC, op, fault)
    res = run.execute("ds16-paper.closed", 2**31 + 11, 1.0, False,
                      root=checkout, bench=checkout / "bench", platform="cpu")
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_sound_run_is_correct(checkout):
    res = run.execute("ds16-paper.closed", 2**31 + 11, 1.0, False,
                      root=checkout, bench=checkout / "bench", platform="cpu")
    assert res["correct"] is True

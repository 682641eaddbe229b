"""A serving run with the timed path broken underneath must come out not
correct: one case per fault the cell can have. The run is the whole harness
on the CPU at a small size, past the look for a chip."""

import pytest
import run
from repro.serve import engine, serve_step

ORIG = serve_step.build_decode_step


def _altered(cfg, *a, **kw):
    """A token altered where it is produced."""
    step = ORIG(cfg, *a, **kw)

    def fault(*args, **kwargs):
        nxt, logits, caches = step(*args, **kwargs)
        return (nxt + 1) % cfg.vocab_size, logits, caches

    return fault


def _unchanged(cfg, *a, **kw):
    """A decode step that returns its state (the cache) unchanged."""
    step = ORIG(cfg, *a, **kw)

    def fault(params, token, pos, caches, **kwargs):
        nxt, logits, _new = step(params, token, pos, caches, **kwargs)
        return nxt, logits, caches

    return fault


def _half(cfg, *a, **kw):
    """Half of the batch left out: the second half of the slots gets the
    first slot's token."""
    step = ORIG(cfg, *a, **kw)

    def fault(*args, **kwargs):
        nxt, logits, caches = step(*args, **kwargs)
        return nxt.at[nxt.shape[0] // 2:].set(nxt[0]), logits, caches

    return fault


@pytest.mark.parametrize("fault", [_altered, _unchanged, _half])
def test_fault_is_not_correct(checkout, monkeypatch, fault):
    monkeypatch.setattr(engine, "build_decode_step", fault)
    res = run.execute("stablelm-1.6b.chat", 2**31 + 13, 2.0, False,
                      root=checkout, bench=checkout / "bench", platform="cpu")
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_sound_run_is_correct(checkout):
    res = run.execute("stablelm-1.6b.chat", 2**31 + 13, 2.0, False,
                      root=checkout, bench=checkout / "bench", platform="cpu")
    assert res["correct"] is True

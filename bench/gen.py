"""Traffic and input generation, from the seed alone.

One general generator per loop kind reads a traffic file
(``bench/traffic/<mix>.json``):

* ``open``: requests due on a schedule, whether or not earlier ones have
  finished. ``arrivals`` is ``poisson`` (a fixed multiset of exponential
  gaps, shuffled) or ``bursty`` (Zipf bursts after Pareto gaps, modulated by
  a diurnal rate). Sizes and tiers come from weighted buckets.
* ``closed``: ``in_flight`` sources that each submit the next input as soon
  as the previous one completes, cycling ``distinct_inputs`` inputs.

Every seed gets the same multiset of sizes, tiers and (for ``poisson``) gaps:
the seed changes their order and the token contents, not the amount of work.
A mix with ``order_seed`` fixes the order too (sizes, tiers and gaps drawn
from it, tokens and weights still from the run's seed): in a queue near
capacity the order of long requests and short gaps moves a latency tail by
far more than anything a program change would, so the run's seed must not
choose it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream) pair; any non-negative int
    seed works, however large."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


def apportion(n: int, weights: Sequence[float]) -> List[int]:
    """Split ``n`` into counts proportional to ``weights`` (largest
    remainder), the same split for every seed."""
    w = np.asarray(weights, dtype=float)
    w = w / w.sum()
    raw = n * w
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _draw(n: int, buckets: Sequence[Sequence[float]],
          rng: np.random.Generator) -> np.ndarray:
    """``n`` values from ``[[value, weight], ...]`` in exact proportion,
    shuffled."""
    values = [b[0] for b in buckets]
    counts = apportion(n, [b[1] for b in buckets])
    out = np.repeat(np.asarray(values), counts)
    rng.shuffle(out)
    return out


def bursty_arrivals(n: int, seed: int, mean_gap: float, alpha: float = 1.5,
                    max_burst: int = 64, day_s: float = 86400.0,
                    diurnal_depth: float = 0.0) -> List[float]:
    """Heavy-tailed bursty trace: Zipf(2) burst sizes of coincident
    arrivals after Pareto(``alpha``) gaps, the gap rate modulated by a
    sinusoidal day. Deterministic per seed."""
    rng = rng_for(seed, 1)
    ts: List[float] = []
    t = 0.0
    while len(ts) < n:
        burst = int(min(rng.zipf(2.0), max_burst))
        rate = 1.0 + diurnal_depth * math.sin(2.0 * math.pi * t / day_s)
        t += mean_gap * (rng.pareto(alpha) + 0.1) / max(rate, 1e-9)
        ts.extend([t] * burst)
    return ts[:n]


def poisson_arrivals(n: int, seconds: float, seed: int) -> List[float]:
    """``n`` due times over ``[0, seconds)``: the gaps are the ``n``
    mid-quantiles of an exponential distribution, scaled to fill the window
    and shuffled by the seed."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    rng_for(seed, 1).shuffle(gaps)
    return (np.cumsum(gaps) - gaps).tolist()


@dataclasses.dataclass
class Request:
    rid: int
    due: float
    prompt_len: int
    output_len: int
    tier: str


def open_loop(traffic: dict, seed: int, seconds: float) -> List[Request]:
    """The requests due in a window of ``seconds`` at the mix's rate."""
    n = int(round(traffic["rate_per_s"] * seconds))
    seed = traffic.get("order_seed", seed)
    if traffic["arrivals"] == "poisson":
        due = poisson_arrivals(n, seconds, seed)
    elif traffic["arrivals"] == "bursty":
        b = traffic.get("bursty", {})
        due = bursty_arrivals(n, seed, mean_gap=b.get("mean_gap_s", 1.0),
                              alpha=b.get("alpha", 1.5),
                              max_burst=b.get("max_burst", 64),
                              diurnal_depth=b.get("diurnal_depth", 0.0))
        due = [t for t in due if t < seconds]
        n = len(due)
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    rng = rng_for(seed, 2)
    prompts = _draw(n, traffic["prompt_buckets"], rng)
    outputs = _draw(n, traffic["output_buckets"], rng)
    tiers = _draw(n, traffic["tiers"], rng)
    return [Request(i, float(due[i]), int(prompts[i]), int(outputs[i]),
                    str(tiers[i])) for i in range(n)]


def prompt_tokens(req: Request, seed: int, vocab: int) -> np.ndarray:
    """The request's prompt: uniform token ids, fixed by seed and rid."""
    return rng_for(seed, 3, req.rid).integers(
        2, vocab, size=req.prompt_len).astype(np.int32)


def neubot_batches(data: dict, rows: int, cols: int, n: int,
                   seed: int) -> List[np.ndarray]:
    """``n`` raw batches of speed-test tuples, ``rows`` x ``cols`` float32.

    Each row is one measurement from one of ``data["classes"]`` access-
    network classes (well separated cluster centres, unit noise), on a slow
    per-column trend with rare spikes, and with a share of entries missing
    (NaN) as a collector drops them."""
    out = []
    for b in range(n):
        rng = rng_for(seed, 4, b)
        k = data["classes"]
        centres = rng.normal(0.0, data["separation"], size=(k, cols))
        cls = rng.integers(0, k, size=rows)
        x = centres[cls] + rng.standard_normal((rows, cols))
        t = np.linspace(0.0, 1.0, rows)[:, None]
        x += data["trend"] * np.sin(2 * np.pi * (t * rng.uniform(1, 4, cols)))
        spikes = rng.random((rows, cols)) < data["spike_rate"]
        x[spikes] += data["spike_size"] * rng.choice([-1.0, 1.0], spikes.sum())
        x[rng.random((rows, cols)) < data["missing_rate"]] = np.nan
        out.append(x.astype(np.float32))
    return out

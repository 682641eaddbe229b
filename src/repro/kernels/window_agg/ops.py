"""Jit'd wrapper for the sliding-window aggregation kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.window_agg.window_agg import window_agg_kernel


@functools.partial(jax.jit, static_argnames=("window", "agg", "block_s"))
def window_agg(x: jax.Array, *, window: int, agg: str = "mean",
               block_s: int = 2048) -> jax.Array:
    """x: (S, C) → (S, C): causal sliding-window aggregate, kernel-tiled.

    The kernel wants time on the lanes: the stream is transposed to
    ``(C, S)``, channels padded to 8 sublanes and time to whole blocks
    of a 128-lane multiple that is at least the window."""
    if agg not in ("sum", "mean", "max"):
        raise ValueError(f"unknown agg {agg!r}")
    S, C = x.shape
    w = max(1, min(window, S))
    bs = max(min(block_s, S), w)
    bs = -(-bs // 128) * 128
    xt = jnp.pad(x.T, [(0, (-C) % 8), (0, (-S) % bs)])
    out = window_agg_kernel(xt, window=w, agg=agg, block_s=bs)
    return out[:C, :S].T

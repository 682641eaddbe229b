"""Sliding-window aggregation — Pallas TPU kernel.

The hot loop of the paper's streaming services (window_agg / anomaly /
summarize over tuple streams, §3.1). Memory-bound, so the layout is
lane-dense: the wrapper hands the kernel the stream transposed,
``(channels, time)``, with time on the 128 lanes and the handful of
channels of the paper's tuple model on the sublanes.

Per grid step the kernel holds one block of time and the block before it
(a second view of the same operand through an overlapping ``BlockSpec``),
and reduces each window by **binary doubling** along the lanes:
``P_{2k}[t] = op(P_k[t], P_k[t-k])`` with ``pltpu.roll`` shifts, combined
over the set bits of ``w``. That is O(log w) vector ops per element for
sum, mean and max alike, with no prefix sum (Mosaic has no ``cumsum``)
and no gather.

Grid: one step per time block; ``window ≤ block_s`` (the wrapper makes
the block at least as long as the window).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform


def _window_reduce(both: jax.Array, window: int, op) -> jax.Array:
    """``out[:, t] = op(both[:, t-window+1 .. t])`` along the lanes; exact
    for every ``t >= window - 1`` (lower lanes hold wrapped values)."""
    acc = None
    p, k, off = both, 1, 0
    while True:
        if window & k:
            term = p if off == 0 else pltpu.roll(p, off, 1)
            acc = term if acc is None else op(acc, term)
            off += k
        if 2 * k > window:
            return acc
        p = op(p, pltpu.roll(p, k, 1))
        k *= 2


def _kernel(prev_ref, cur_ref, o_ref, *, window: int, agg: str,
            block_s: int):
    i = pl.program_id(0)
    fill = -jnp.inf if agg == "max" else 0.0
    prev = prev_ref[...].astype(jnp.float32)     # (C, bs) block i-1
    cur = cur_ref[...].astype(jnp.float32)       # (C, bs) block i
    prev = jnp.where(i > 0, prev, fill)          # nothing before t = 0
    both = jnp.concatenate([prev, cur], axis=1)  # (C, 2bs)
    op = jnp.maximum if agg == "max" else jnp.add
    out = _window_reduce(both, window, op)[:, block_s:]
    if agg == "mean":
        t = i * block_s + jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        out = out / jnp.minimum(t + 1, window).astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def window_agg_kernel(xt: jax.Array, *, window: int, agg: str = "mean",
                      block_s: int = 2048) -> jax.Array:
    """xt: (C_pad, S_pad) time-major-on-lanes, C_pad % 8 == 0,
    S_pad % block_s == 0, block_s % 128 == 0, window ≤ block_s."""
    C, S = xt.shape
    if window > block_s:
        raise ValueError("window must be ≤ block_s")
    kernel = functools.partial(_kernel, window=window, agg=agg,
                               block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid=(S // block_s,),
        in_specs=[
            # previous block (index clamped at 0; masked inside the kernel)
            pl.BlockSpec((C, block_s), lambda i: (0, jnp.maximum(i - 1, 0))),
            pl.BlockSpec((C, block_s), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((C, block_s), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, S), xt.dtype),
        interpret=platform.interpret(),
    )(xt, xt)

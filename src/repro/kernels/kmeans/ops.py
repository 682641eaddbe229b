"""Jit'd wrapper for the k-means assignment kernel (layout + padding)."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.kmeans.kmeans import kmeans_assign_kernel


@functools.partial(jax.jit, static_argnames=("block_n",))
def kmeans_assign(x: jax.Array, cent: jax.Array, *, block_n: int = 8192
                  ) -> Tuple[jax.Array, jax.Array]:
    """x: (N, D) · cent: (K, D) → (assign (N,) int32, min_d2 (N,) f32).

    Points go to the lanes: x is transposed to ``(D, N)``, features
    padded to 8 sublanes (zeros add nothing to a distance) and points to
    whole blocks of a 128-lane multiple."""
    N, D = x.shape
    K = cent.shape[0]
    bn = -(-min(block_n, N) // 128) * 128
    pad_d = (-D) % 8
    xt = jnp.pad(x.T, [(0, pad_d), (0, (-N) % bn)])
    ct = jnp.pad(cent.T, [(0, pad_d), (0, (-K) % 128)])
    assign, d2 = kmeans_assign_kernel(xt, ct, k_real=K, block_n=bn)
    return assign[0, :N], d2[0, :N]

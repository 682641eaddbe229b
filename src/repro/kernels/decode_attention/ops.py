"""Jit'd wrapper for the decode-attention kernel (layout + padding)."""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_kernel)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "block_c"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: Optional[jax.Array] = None, *,
                     softcap: float = 0.0, scale: Optional[float] = None,
                     block_c: int = 512) -> jax.Array:
    """q: (B, Hq, D) · k,v: (B, C, Hkv, D) · valid: (B, C) bool →
    (B, Hq, D). Never expands KV to query heads (bandwidth-optimal).

    The cache is read in place when C is a multiple of the block and D
    of 128 lanes; otherwise it is padded (a copy) to get there."""
    B, Hq, D = q.shape
    C, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if valid is None:
        valid = jnp.ones((B, C), bool)

    bc = min(block_c, max(8, C))
    pad_c = (-C) % bc
    if pad_c:
        k = jnp.pad(k, [(0, 0), (0, pad_c), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pad_c), (0, 0), (0, 0)])
        valid = jnp.pad(valid, [(0, 0), (0, pad_c)])
    pad_d = (-D) % 128
    qg = q.reshape(B, Hkv, G, D)
    if pad_d:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, 0), (0, pad_d)])
        k = jnp.pad(k, [(0, 0), (0, 0), (0, 0), (0, pad_d)])
        v = jnp.pad(v, [(0, 0), (0, 0), (0, 0), (0, pad_d)])

    out = decode_attention_kernel(qg, k, v, valid.astype(jnp.int32),
                                  softcap=softcap, scale=scale, block_c=bc)
    return out[..., :D].reshape(B, Hq, D)

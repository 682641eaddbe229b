"""Jit'd wrapper: shape normalisation + GQA around the flash kernel.

Handles what the kernel leaves to the caller:
  * (B, S, Hq, D) model layout → (B·H, S, D) kernel layout;
  * GQA — kv heads are broadcast to the query-head count (the kernel
    streams k/v per *query* head; per-kv-head grouping is the
    decode_attention kernel's job where bandwidth actually dominates);
  * padding S to the block size and D to the 128-lane multiple, with true
    ``seq_len`` masking inside the kernel;
  * where it runs: compiled on a TPU, interpreted on the CPU
    (:func:`repro.kernels.platform.interpret`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_kernel


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: (B, S, Hq, D) · k,v: (B, S, Hkv, D) → (B, S, Hq, D)."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    if Hq % Hkv:
        raise ValueError("Hq must be a multiple of Hkv")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if Hkv != Hq:
        reps = Hq // Hkv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)

    # (B, S, H, D) → (B*H, S, D)
    def to_kernel(x):
        return x.transpose(0, 2, 1, 3).reshape(B * Hq, S, x.shape[3])

    qk, kk, vk = to_kernel(q), to_kernel(k), to_kernel(v)
    bq = min(block_q, max(8, 1 << (S - 1).bit_length()))
    bk = min(block_k, bq)
    qk = _pad_to(_pad_to(qk, 1, bq), 2, 128)
    kk = _pad_to(_pad_to(kk, 1, bk), 2, 128)
    vk = _pad_to(_pad_to(vk, 1, bk), 2, 128)

    out = flash_attention_kernel(
        qk, kk, vk, causal=causal, window=window, softcap=softcap,
        scale=scale, seq_len=S, block_q=min(bq, qk.shape[1]),
        block_k=min(bk, kk.shape[1]))
    out = out[:, :S, :D].reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
    return out

"""Collective schedules: hierarchical + int8-compressed gradient reduction.

The paper's JITA rule — *keep traffic near the data when links are slow* —
applied to gradients (DESIGN.md §5). Two shard_map-level schedules:

  * :func:`hierarchical_psum` — reduce-scatter over the fast intra-pod ICI
    axis, all-reduce only the 1/N-sized shard over the slow inter-pod DCN
    axis, all-gather back over ICI. DCN bytes drop from 2·T to 2·T/N per
    chip (N = intra-pod degree) vs a flat all-reduce over both axes.
  * :func:`int8_allreduce` — error-feedback int8 compression: quantize
    (per-256-block absmax scales), reduce via all-to-all in int8 (wire
    bytes ÷4 vs f32), locally sum dequantized segments, re-quantize, and
    all-gather int8. The quantization residual is *returned* and fed back
    into the next step's gradient (error feedback), which keeps SGD
    convergence (Karimireddy et al.-style).

Both are pure functions meant to run **inside shard_map** with the named
axes bound; tests drive them on a host-platform device mesh. The SPMD
train step uses XLA's own all-reduce by default — these are the opt-in
"beyond-paper" schedules benchmarked in §Perf.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


_QBLOCK = 256


def hierarchical_psum(x: jax.Array, *, inner_axis: str = "data",
                      outer_axis: str = "pod") -> jax.Array:
    """All-reduce over (inner × outer) as RS(inner) → AR(outer) → AG(inner).

    Mathematically identical to psum over both axes; on hardware the outer
    (DCN) axis carries only the scattered shard.
    """
    n_inner = jax.lax.axis_size(inner_axis)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n_inner
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # reduce-scatter over the fast axis: each inner rank owns one segment
    seg = jax.lax.psum_scatter(flat.reshape(n_inner, -1), inner_axis,
                               scatter_dimension=0, tiled=False)
    # cross-pod all-reduce of the 1/n_inner-sized shard
    seg = jax.lax.psum(seg, outer_axis)
    # all-gather the segments back over the fast axis
    full = jax.lax.all_gather(seg, inner_axis, axis=0, tiled=False)
    full = full.reshape(-1)[: x.size]
    return full.reshape(x.shape)


# ---------------------------------------------------------------------------
# int8 error-feedback compressed all-reduce
# ---------------------------------------------------------------------------

def _quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    n = x.shape[0]
    nb = -(-n // _QBLOCK)
    padded = jnp.pad(x, (0, nb * _QBLOCK - n)).reshape(nb, _QBLOCK)
    scale = jnp.max(jnp.abs(padded), axis=1, keepdims=True) / 127.0
    q = jnp.round(padded / jnp.maximum(scale, 1e-12))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array, n: int) -> jax.Array:
    return (q.astype(jnp.float32) * scale).reshape(-1)[:n]


def int8_allreduce(x: jax.Array, *, axis: str = "data",
                   error: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Mean-all-reduce with int8 wire format + error feedback.

    Returns (reduced, new_error). ``error`` is the previous step's
    quantization residual (same shape as x, f32), added before quantizing.
    Wire bytes per chip ≈ 2 × size × 1 B (vs 8 B for f32 ring) + scales.
    """
    n_dev = jax.lax.axis_size(axis)
    flat = x.astype(jnp.float32).reshape(-1)
    if error is not None:
        flat = flat + error.reshape(-1)
    n = flat.shape[0]

    # pad so each device owns an equal segment of whole quant blocks
    seg_len = -(-n // n_dev)
    seg_len = -(-seg_len // _QBLOCK) * _QBLOCK
    padded = jnp.pad(flat, (0, seg_len * n_dev - n))

    q, scale = _quantize(padded)                      # (nb, 256), (nb, 1)
    residual = padded - _dequantize(q, scale, padded.shape[0])

    # scatter: each device receives every peer's copy of its own segment
    blocks_per_seg = seg_len // _QBLOCK
    q_segs = q.reshape(n_dev, blocks_per_seg, _QBLOCK)
    s_segs = scale.reshape(n_dev, blocks_per_seg, 1)
    q_recv = jax.lax.all_to_all(q_segs, axis, split_axis=0,
                                concat_axis=0, tiled=False)  # (n_dev, b, 256)
    s_recv = jax.lax.all_to_all(s_segs, axis, split_axis=0,
                                concat_axis=0, tiled=False)
    # local mean of dequantized peer contributions for the owned segment
    seg_sum = (q_recv.astype(jnp.float32) * s_recv).sum(axis=0) / n_dev

    # re-quantize the reduced segment, all-gather in int8
    q2, s2 = _quantize(seg_sum.reshape(-1))
    q_all = jax.lax.all_gather(q2, axis, axis=0, tiled=True)
    s_all = jax.lax.all_gather(s2, axis, axis=0, tiled=True)
    out = _dequantize(q_all, s_all, seg_len * n_dev)[:n]
    return out.reshape(x.shape).astype(x.dtype), residual[:n].reshape(x.shape)

"""Single-token KV-cache attention — Pallas TPU kernel.

Decode attention is **HBM-bandwidth-bound**: the whole KV cache streams
through once per generated token while compute is a rank-1-ish matmul.
The kernel therefore (a) keeps the per-kv-head query groups (Hkv, G, D)
resident in VMEM, (b) streams K/V cache blocks HBM→VMEM along the
sequential innermost grid axis, and (c) never materialises the
GQA-expanded KV (unlike the prefill kernel, where compute dominates) —
per-kv-head grouping reads each cache byte exactly once, the roofline
optimum.

Blocking follows the cache's own ``(B, C, Hkv, D)`` layout, with no
copy: a K/V block is ``(1, block_c, Hkv, D)`` — all kv heads, so its
last two dims are whole array dims as Mosaic's (8, 128) rule asks — and
each kv head is a strided ``(block_c, D)`` read of it inside the kernel.
The validity mask ``(B, C)`` is read as a lane-dense ``(B, block_c)``
block (all rows; the kernel picks its own).

Grid: (B, n_cache_blocks); online-softmax scratch (m, l, acc) per kv head
carries across cache blocks. Invalid (unwritten ring) slots are masked
via ``valid`` so one kernel serves dense, ring (SWA), and partially
filled caches.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, softcap: float, n_c: int, n_kv: int):
    b = pl.program_id(0)
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mask = valid_ref[pl.ds(b, 1), :] > 0                 # (1, bc)
    for h in range(n_kv):
        q = q_ref[0, h].astype(jnp.float32) * scale       # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)         # (bc, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)         # (bc, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bc)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[h]                                 # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
        m_ref[h] = m_new
        acc_ref[h] = (acc_ref[h] * alpha
                      + jax.lax.dot_general(
                          p, v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))

    @pl.when(ic == n_c - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                            valid: jax.Array, *, softcap: float = 0.0,
                            scale=None, block_c: int = 512) -> jax.Array:
    """q: (B, Hkv, G, D) · k,v: (B, C, Hkv, D) · valid: (B, C) int32
    → (B, Hkv, G, D).  C % block_c == 0 (wrapper pads + marks invalid)."""
    B, Hkv, G, D = q.shape
    C = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    n_c = C // block_c

    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               n_c=n_c, n_kv=Hkv)
    kv_spec = pl.BlockSpec((1, block_c, Hkv, D), lambda b, ic: (b, ic, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, n_c),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, ic: (b, 0, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((B, block_c), lambda b, ic: (0, ic)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b, ic: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
        interpret=platform.interpret(),
    )(q, k, v, valid)

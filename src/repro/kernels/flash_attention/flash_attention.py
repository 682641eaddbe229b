"""Blockwise online-softmax attention — Pallas TPU kernel.

TPU adaptation of FlashAttention (DESIGN.md §2): instead of CUDA shared
memory + warp tiling, blocks of Q stay resident in **VMEM scratch** while
the kernel streams K/V blocks HBM→VMEM along the innermost (sequential)
grid dimension; the MXU consumes (block_q × D)·(D × block_k) matmuls.
Running max / denominator / accumulator live in VMEM scratch across the
K-block sweep — the classic online-softmax recurrence, tiled to hardware:
block sizes default to 128 (MXU-native), D is padded to a lane multiple by
the ops.py wrapper.

Grid: (B·H, n_q_blocks, n_k_blocks), K innermost ("arbitrary" semantics —
sequential on TPU, so scratch carries across K blocks). Causal/windowed
blocks that are fully masked are skipped cheaply via @pl.when.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, softcap: float,
            block_q: int, block_k: int, n_k: int, seq_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # Whole-block skip test (static shapes, cheap scalar predicate):
    # causal  → skip if the earliest q cannot see the latest valid k
    # window  → skip if the latest q is beyond the window from latest k
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window > 0:
        run = jnp.logical_and(
            run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_prev * alpha + p.sum(axis=1)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(ik == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           softcap: float = 0.0, scale=None,
                           seq_len=None,
                           block_q: int = 128, block_k: int = 128
                           ) -> jax.Array:
    """q,k,v: (BH, S_pad, D_pad), S_pad % block == 0. ``seq_len`` is the
    true (pre-padding) length — padded keys are masked out; padded q rows
    produce garbage the ops.py wrapper slices off."""
    BH, S, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    n_q = S // block_q
    n_k = S // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, n_k=n_k,
        seq_len=int(seq_len if seq_len is not None else S))

    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, iq, ik: (h, ik, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, iq, ik: (h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max
            pltpu.VMEM((block_q,), jnp.float32),      # running denom
            pltpu.VMEM((block_q, D), jnp.float32),    # output accumulator
        ],
        interpret=platform.interpret(),
    )(q, k, v)

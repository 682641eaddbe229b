"""k-means assignment — Pallas TPU kernel (lane-dense VPU formulation).

The hot loop of the paper's k-means / sweep-clustering / train-cluster DS
operators (the dominant ``ml``-family tasks of the Fig. 5 workload). The
points are few-featured (3 columns after the pipeline's feature filter),
so an MXU matmul over a 128-padded feature dim would be almost all
padding. Instead the wrapper hands the kernel the points transposed,
``(D, N)``: points on the 128 lanes, features on the sublanes. Per grid
step a ``(D, block_n)`` slab is resident in VMEM and, for each centroid,

    d2_k = Σ_d (x_d − c_kd)²

is a sublane reduction to one lane-dense ``(1, block_n)`` row — the same
subtract-square-sum formula as the oracle, so distances agree to
rounding. A running strict ``<`` keeps the first minimum (``argmin``
semantics). Outputs are ``(1, N)`` rows: lane-dense, so Mosaic's layout
matches XLA's at any N.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import platform


def _kernel(xt_ref, ct_ref, a_ref, d_ref, *, k_real: int):
    xt = xt_ref[...].astype(jnp.float32)                # (D, bn)
    ct = ct_ref[...].astype(jnp.float32)                # (D, K)
    best_d = jnp.full((1, xt.shape[1]), jnp.inf, jnp.float32)
    best_k = jnp.zeros((1, xt.shape[1]), jnp.int32)
    for k in range(k_real):                             # static: K is small
        diff = xt - ct[:, k:k + 1]
        d2 = jnp.sum(diff * diff, axis=0, keepdims=True)  # (1, bn)
        better = d2 < best_d
        best_k = jnp.where(better, k, best_k)
        best_d = jnp.where(better, d2, best_d)
    a_ref[...] = best_k
    d_ref[...] = best_d


def kmeans_assign_kernel(xt: jax.Array, ct: jax.Array, *, k_real: int,
                         block_n: int = 8192):
    """xt: (D_pad, N_pad) · ct: (D_pad, K_pad) → ((1, N_pad) int32,
    (1, N_pad) f32); D_pad % 8 == 0, N_pad % block_n == 0,
    block_n % 128 == 0."""
    D, N = xt.shape
    K = ct.shape[1]
    kernel = functools.partial(_kernel, k_real=k_real)
    return pl.pallas_call(
        kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((D, block_n), lambda i: (0, i)),
            pl.BlockSpec((D, K), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, N), jnp.int32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=platform.interpret(),
    )(xt, ct)

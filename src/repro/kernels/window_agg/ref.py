"""Pure-jnp oracle for the sliding-window aggregation kernel.

Same semantics as repro.pipeline.operators._window_agg: causal window of
``window`` rows (clamped at the start), same-length output — computed the
plainest way, independent of both the kernel and the operator.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def window_agg_ref(x: jax.Array, *, window: int, agg: str = "mean"
                   ) -> jax.Array:
    """x: (S, C) → (S, C); causal window [t-w+1, t] clamped at 0: the w
    shifted copies of x, stacked and reduced."""
    n = x.shape[0]
    w = max(1, min(window, n))
    xf = x.astype(jnp.float32)
    fill = -jnp.inf if agg == "max" else 0.0
    xpad = jnp.pad(xf, [(w - 1, 0)] + [(0, 0)] * (x.ndim - 1),
                   constant_values=fill)
    stk = jnp.stack([xpad[i:i + n] for i in range(w)])
    if agg == "max":
        out = stk.max(axis=0)
    elif agg in ("mean", "sum"):
        out = stk.sum(axis=0)
        if agg == "mean":
            cnt = jnp.minimum(jnp.arange(1, n + 1), w).astype(jnp.float32)
            out = out / cnt[:, None]
    else:
        raise ValueError(agg)
    return out.astype(x.dtype)

"""Plain float32 reference of StableLM-2 (stabilityai/stablelm-2-1_6b).

Written from the published architecture, not from the program: a
pre-LayerNorm decoder with biased q/k/v projections and an unbiased output
projection, rotary embedding on the first ``rotary_pct`` of each head
(rotate-half convention, base ``rope_theta``), causal softmax attention with
as many key/value heads as query heads, a SwiGLU feed-forward
``down(silu(gate(x)) * up(x))``, a final LayerNorm and an untied LM head.

Everything runs in float32 with every matrix product at ``highest``
precision. Layers run one at a time under ``lax.scan``, casting that
layer's weights to float32 inside the step, so only one layer is ever held
in float32. With ``quant`` (``"int8"`` or ``"fp8"``) every matrix weight is
first rounded to that type with one scale per output channel: the control.

Weights come as a dict: ``embed`` (V, d), ``lm_head`` (d, V), ``final_ln``
(scale, bias) and ``layers``, a dict of arrays stacked over layers:
``ln1_s ln1_b wq bq wk bk wv bv wo ln2_s ln2_b w_gate w_up w_down``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _ln(x, s, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * s + b


def quantised(w, quant: str):
    """Round a (in, out) weight to ``int8`` or ``fp8`` (e4m3) with one
    scale per output column, and return it dequantised in float32."""
    w = w.astype(jnp.float32)
    top = 127.0 if quant == "int8" else 448.0
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if quant == "int8":
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown quantisation {quant!r}")


def _rope(x, pos, rot: int, theta: float):
    """x (S, H, D): rotate the first ``rot`` dims of each head."""
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (S, half)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def logits(w: Dict[str, Any], tokens, m, quant: Optional[str] = None):
    """tokens (S,) int32 -> logits (S, V) float32. ``m`` is a hashable
    tuple of the model section's items."""
    m = dict(m)
    S = tokens.shape[0]
    H, D = m["n_heads"], m["head_dim"]
    eps = m["norm_eps"]
    rot = int(D * m["rotary_pct"]) // 2 * 2
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]

    def mat(a):
        return quantised(a, quant) if quant else a.astype(jnp.float32)

    def layer(x, p):
        f = {k: v.astype(jnp.float32) for k, v in p.items()}
        h = _ln(x, f["ln1_s"], f["ln1_b"], eps)
        q = (_mm(h, mat(p["wq"])) + f["bq"]).reshape(S, H, D)
        k = (_mm(h, mat(p["wk"])) + f["bk"]).reshape(S, H, D)
        v = (_mm(h, mat(p["wv"])) + f["bv"]).reshape(S, H, D)
        q = _rope(q, pos, rot, m["rope_theta"])
        k = _rope(k, pos, rot, m["rope_theta"])
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(float(D))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v, precision=HI).reshape(S, H * D)
        x = x + _mm(o, mat(p["wo"]))
        h = _ln(x, f["ln2_s"], f["ln2_b"], eps)
        g = _mm(h, mat(p["w_gate"]))
        u = _mm(h, mat(p["w_up"]))
        x = x + _mm(jax.nn.silu(g) * u, mat(p["w_down"]))
        return x, None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _ln(x, w["final_ln"][0].astype(jnp.float32),
            w["final_ln"][1].astype(jnp.float32), eps)
    return _mm(x, mat(w["lm_head"]))

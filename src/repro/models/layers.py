"""Core decoder layers: norms, RoPE, GQA attention, MLP.

Attention is implemented as **blockwise online-softmax over KV chunks**
(`jax.lax.scan` carrying running max / denominator / accumulator) — the
same algorithm the Pallas flash kernel (repro.kernels.flash_attention)
implements with explicit VMEM tiling. The pure-jnp path here is what the
multi-pod dry-run lowers (Pallas lowering needs real TPUs); its memory
footprint is O(Sq × chunk), which is what makes the 32k-prefill cells fit.

Supported attention features (per assigned arch, DESIGN.md §4):
GQA (kv-head grouping), causal + sliding-window masks, logit softcap
(gemma2), qk-norm (qwen3), partial rotary (stablelm2), cross-attention
(llama-3.2-vision), attention sinks over a KV cache (decode path).

Everything is a pure function over an explicit param pytree; params are
created by ``init_*`` functions taking a PRNG key.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, key, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), dtype=cfg.param_dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype=cfg.param_dtype)
    return p


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = (x * x).mean(-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(dt)


def rms_head_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Per-head RMSNorm over head_dim (qwen3 qk-norm)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig) -> jax.Array:
    rot = int(cfg.head_dim * cfg.rotary_pct) // 2 * 2
    return 1.0 / (cfg.rope_theta
                  ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32. Rotates the first
    ``rotary_pct`` fraction of D (pairwise halves convention)."""
    rot = int(cfg.head_dim * cfg.rotary_pct) // 2 * 2
    if rot == 0:
        return x
    inv = rope_freqs(cfg)                                     # (rot/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv      # (B,S,rot/2)
    cos = jnp.cos(ang)[:, :, None, :]                         # (B,S,1,rot/2)
    sin = jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1.astype(x.dtype), y2.astype(x.dtype), xp], -1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    pd = cfg.param_dtype
    p: Params = {
        "wq": jax.random.normal(ks[0], (d, q_dim), pd) * std,
        "wk": jax.random.normal(ks[1], (d, kv_dim), pd) * std,
        "wv": jax.random.normal(ks[2], (d, kv_dim), pd) * std,
        "wo": jax.random.normal(ks[3], (q_dim, d), pd) * (std / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((q_dim,), pd)
        p["bk"] = jnp.zeros((kv_dim,), pd)
        p["bv"] = jnp.zeros((kv_dim,), pd)
        p["bo"] = jnp.zeros((d,), pd)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), pd)
        p["k_norm"] = jnp.ones((hd,), pd)
    return p


def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array,
                 kv_x: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """→ q: (B,Sq,Hq,D), k/v: (B,Skv,Hkv,D). ``kv_x`` for cross-attention."""
    kv_src = x if kv_x is None else kv_x
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = kv_src @ p["wk"].astype(dt)
    v = kv_src @ p["wv"].astype(dt)
    if cfg.use_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    B, Sq = q.shape[:2]
    Skv = k.shape[1]
    q = q.reshape(B, Sq, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_head_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      q_positions: jax.Array, kv_positions: jax.Array,
                      kv_valid: Optional[jax.Array] = None,
                      causal: bool = True, window: int = 0,
                      softcap: float = 0.0, chunk: int = 1024,
                      scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention over KV chunks (flash-style, pure jnp).

    q: (B,Sq,Hq,D) · k,v: (B,Skv,Hkv,D) · positions: (B,S) absolute token
    indices (drive causal/window masks — decode passes offsets here).
    kv_valid: (B,Skv) bool for ring-buffer caches with unwritten slots.
    Grouped-query: Hq % Hkv == 0; scores computed in f32, output in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    nchunk = -(-Skv // chunk)
    pad = nchunk * chunk - Skv
    if pad:
        padc = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k = jnp.pad(k, padc)
        v = jnp.pad(v, padc)
        kv_positions = jnp.pad(kv_positions, [(0, 0), (0, pad)])
        valid = jnp.pad(kv_valid if kv_valid is not None
                        else jnp.ones((B, Skv), bool), [(0, 0), (0, pad)])
    else:
        valid = (kv_valid if kv_valid is not None
                 else jnp.ones((B, Skv), bool))

    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, D)
    kc = k.reshape(B, nchunk, chunk, Hkv, D)
    vc = v.reshape(B, nchunk, chunk, Hkv, D)
    pc = kv_positions.reshape(B, nchunk, chunk)
    mc = valid.reshape(B, nchunk, chunk)
    qpos = q_positions.astype(jnp.int32)

    # checkpointed: the backward pass recomputes the (B,Sq,H,G,chunk) f32
    # score tensors instead of saving one per chunk — at 32k/4k train
    # shapes those stacks dominated temp memory (§Perf, measured)
    @jax.checkpoint
    def body(carry, xs):
        m, lsum, acc = carry
        kb, vb, pb, vb_mask = xs                     # (B,chunk,Hkv,D) ...
        s = jnp.einsum("bqhgd,bchd->bqhgc", qf, kb.astype(jnp.float32))
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = vb_mask[:, None, :]                                   # (B,1,c)
        if causal:
            mask = mask & (pb[:, None, :] <= qpos[:, :, None])
        if window > 0:
            mask = mask & (pb[:, None, :] > qpos[:, :, None] - window)
        s = jnp.where(mask[:, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p_ = jnp.exp(s - m_new[..., None])
        lsum_new = lsum * alpha + p_.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bqhgc,bchd->bqhgd", p_, vb.astype(jnp.float32))
        return (m_new, lsum_new, acc_new), None

    init = (jnp.full((B, Sq, Hkv, G), -1e30, jnp.float32),
            jnp.zeros((B, Sq, Hkv, G), jnp.float32),
            jnp.zeros((B, Sq, Hkv, G, D), jnp.float32))
    xs = (kc.swapaxes(0, 1), vc.swapaxes(0, 1),
          pc.swapaxes(0, 1), mc.swapaxes(0, 1))
    (m, lsum, acc), _ = jax.lax.scan(body, init, xs)
    out = acc / jnp.maximum(lsum, 1e-30)[..., None]
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def sharded_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             q_positions: jax.Array, kv_positions: jax.Array,
                             kv_valid: jax.Array, window: int,
                             softcap: float, rules,
                             scale: Optional[float] = None) -> jax.Array:
    """Flash-decode over a CAPACITY-sharded cache (§Perf path).

    Each model shard computes online-softmax stats (m, l, acc) over its
    local cache slice; stats merge with one tiny pmax/psum — wire bytes
    are O(B·H·D) per layer instead of re-gathering the cache per chunk
    (measured 28.6 GB → ~MB on qwen3 decode_32k; EXPERIMENTS.md §Perf).

    q: (B, 1, Hq, D) replicated over "model"; k/v: (B, C, Hkv, D) with C
    sharded over "model"; positions/valid sharded alike.
    """
    from jax.sharding import PartitionSpec as P_

    mesh = rules.mesh
    tp_axis = "model"
    B, _, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale_ = scale if scale is not None else 1.0 / math.sqrt(D)
    b_rule = rules.dim_rule("batch", B)
    cap_rule = rules.dim_rule("cache_cap", k.shape[1])

    def body(q_l, k_l, v_l, pos_l, valid_l, qpos_l):
        qf = (q_l.astype(jnp.float32) * scale_).reshape(
            q_l.shape[0], Hkv, G, D)                       # (B,Hkv,G,D)
        s = jnp.einsum("bhgd,bchd->bhgc", qf, k_l.astype(jnp.float32))
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = valid_l[:, None, None, :] & \
            (pos_l[:, None, None, :] <= qpos_l[:, None, None, None])
        if window > 0:
            mask = mask & (pos_l[:, None, None, :]
                           > qpos_l[:, None, None, None] - window)
        s = jnp.where(mask, s, -1e30)
        m = s.max(-1)                                       # (B,Hkv,G)
        p_ = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
        lsum = p_.sum(-1)
        acc = jnp.einsum("bhgc,bchd->bhgd", p_, v_l.astype(jnp.float32))
        # merge partial softmax stats across capacity shards
        m_g = jax.lax.pmax(m, tp_axis)
        corr = jnp.exp(m - m_g)
        lsum_g = jax.lax.psum(lsum * corr, tp_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], tp_axis)
        out = acc_g / jnp.maximum(lsum_g, 1e-30)[..., None]
        return out.reshape(q_l.shape[0], 1, Hq, D).astype(q_l.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P_(b_rule, None, None, None),
                  P_(b_rule, cap_rule, None, None),
                  P_(b_rule, cap_rule, None, None),
                  P_(b_rule, cap_rule), P_(b_rule, cap_rule),
                  P_(b_rule)),
        out_specs=P_(b_rule, None, None, None),
        check_vma=False,
    )(q, k, v, kv_positions, kv_valid, q_positions[:, 0])


def attention_block(cfg: ModelConfig, p: Params, x: jax.Array, *,
                    positions: jax.Array, local: bool,
                    kv_x: Optional[jax.Array] = None,
                    kv_positions: Optional[jax.Array] = None,
                    cache: Optional[Dict[str, jax.Array]] = None
                    ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full attention sub-block: project → rope → (cache update) → attend →
    output projection. Returns (output, updated_cache)."""
    q, k, v = _project_qkv(cfg, p, x, kv_x)
    cross = kv_x is not None
    if not cross:
        q = apply_rope(cfg, q, positions)
        k = apply_rope(cfg, k, positions if kv_positions is None
                       else kv_positions)
    kv_valid = None
    if cache is not None and not cross:
        from repro.models.kvcache import update_cache
        from repro.distributed.sharding import current_rules
        cache, k_all, v_all, pos_all, valid_all = update_cache(
            cache, k, v, positions)
        if q.shape[1] == 1:
            rules = current_rules()
            if (rules is not None
                    and rules.options.get("decode_flash_shard")):
                out = sharded_decode_attention(
                    q, k_all, v_all, q_positions=positions,
                    kv_positions=pos_all, kv_valid=valid_all,
                    window=cfg.sliding_window if local else 0,
                    softcap=cfg.attn_logit_softcap, rules=rules)
                B_, S_ = out.shape[:2]
                out = out.reshape(B_, S_, cfg.n_heads * cfg.head_dim)
                y = out @ p["wo"].astype(out.dtype)
                if cfg.use_bias:
                    y = y + p["bo"].astype(out.dtype)
                return y, cache
            # decode: attend over the cache view (ring wraparound handled
            # by absolute positions + validity mask)
            k, v, kv_pos, kv_valid = k_all, v_all, pos_all, valid_all
        else:
            # prefill from empty cache: attend in-segment (the ring may be
            # smaller than the segment), cache updated above for decode
            kv_pos = positions
    else:
        kv_pos = positions if kv_positions is None else kv_positions
        if cross:
            kv_pos = jnp.broadcast_to(
                jnp.arange(k.shape[1], dtype=jnp.int32)[None], k.shape[:2])
    out = chunked_attention(
        q, k, v, q_positions=positions, kv_positions=kv_pos,
        kv_valid=kv_valid, causal=not cross,
        window=cfg.sliding_window if local else 0,
        softcap=cfg.attn_logit_softcap, chunk=cfg.attn_chunk)
    B, S = out.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    y = out @ p["wo"].astype(out.dtype)
    if cfg.use_bias:
        y = y + p["bo"].astype(out.dtype)
    return y, cache


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    std = 1.0 / math.sqrt(d)
    pd = cfg.param_dtype
    return {
        "wi": jax.random.normal(ks[0], (d, f), pd) * std,
        "wg": jax.random.normal(ks[1], (d, f), pd) * std,
        "wo": jax.random.normal(ks[2], (f, d), pd) * (std / math.sqrt(2 * cfg.n_layers)),
    }


def apply_mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(x @ p["wg"].astype(dt)) * (x @ p["wi"].astype(dt))
    return h @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embed(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 2)
    pd = cfg.param_dtype
    p = {"embedding": jax.random.normal(
        ks[0], (cfg.vocab_size, cfg.d_model), pd) * 0.02}
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            ks[1], (cfg.d_model, cfg.vocab_size), pd) * 0.02
    return p


def embed_tokens(cfg: ModelConfig, p: Params, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["embedding"], tokens, axis=0).astype(cfg.dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def lm_logits(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    w = (p["embedding"].T if cfg.tie_embeddings else p["lm_head"])
    logits = x @ w.astype(x.dtype)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits

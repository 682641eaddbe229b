"""Mixture-of-Experts feed-forward (mixtral / kimi-k2 / jamba).

Token-choice top-k routing with capacity-bounded scatter dispatch:

  1. router logits → top-k experts per token (+ renormalised weights);
  2. each (token, choice) gets a slot inside its expert's capacity via a
     cumulative-sum position (tokens beyond capacity are dropped — the
     standard GShard/Switch discipline, capacity_factor-controlled);
  3. tokens are *scattered* into a dense (E, cap, d) buffer, experts run as
     one batched einsum, results gather back.

The scatter formulation keeps memory at O(T·E) ints + O(E·cap·d)
activations — unlike the classic one-hot (T, E, cap) dispatch einsum this
stays tractable at kimi-k2 scale (E=384, T=1M) and shards cleanly: E over
the EP axis, cap over the data axis (see repro.distributed.sharding; the
``constrain`` hooks below are no-ops outside a mesh context).

Aux losses: switch load-balancing loss and router z-loss, returned for the
trainer to weigh in.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.models.config import ModelConfig
from repro.models.layers import apply_mlp, init_mlp

Params = Dict[str, Any]


def init_moe(cfg: ModelConfig, key) -> Params:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    ks = jax.random.split(key, 5)
    std = 1.0 / math.sqrt(d)
    pd = cfg.param_dtype
    p: Params = {
        "router": jax.random.normal(ks[0], (d, E), pd) * std,
        "wi": jax.random.normal(ks[1], (E, d, f), pd) * std,
        "wg": jax.random.normal(ks[2], (E, d, f), pd) * std,
        "wo": jax.random.normal(ks[3], (E, f, d), pd)
              * (std / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(cfg, ks[4],
                               d_ff=cfg.n_shared_experts * f)
    return p


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    cap = int(n_tokens * cfg.n_experts_per_tok / cfg.n_experts
              * cfg.capacity_factor)
    return max(8, -(-cap // 8) * 8)  # round up to a lane-friendly multiple


def apply_moe(cfg: ModelConfig, p: Params, x: jax.Array
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dispatch to the shard_map-local implementation when sharding rules
    are active and request it (beyond-paper §Perf path), else the plain
    SPMD formulation."""
    from repro.distributed.sharding import current_rules
    rules = current_rules()
    if rules is not None and rules.options.get("moe_shard_map"):
        return apply_moe_shard_map(cfg, p, x, rules)
    return apply_moe_spmd(cfg, p, x)


def apply_moe_spmd(cfg: ModelConfig, p: Params, x: jax.Array
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) → (y, aux). aux: {"aux_loss", "z_loss", "dropped_frac"}."""
    from repro.distributed.sharding import constrain

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    T = B * S
    cap = _capacity(cfg, T)
    dt = x.dtype
    xf = x.reshape(T, d)

    # -- routing (f32 for numerics) ------------------------------------------------
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    weights, ids = jax.lax.top_k(probs, k)                       # (T, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # -- aux losses ----------------------------------------------------------------
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)           # (T, k, E)
    tokens_per_expert = onehot.sum((0, 1)) / T                   # f_e
    mean_prob = probs.mean(0)                                    # P_e
    aux_loss = E * jnp.sum(tokens_per_expert * mean_prob)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # -- slot assignment (token-major priority, GShard discipline) -------------------
    ohf = onehot.reshape(T * k, E)
    slot = (jnp.cumsum(ohf, axis=0) * ohf).sum(-1).astype(jnp.int32) - 1
    expert = ids.reshape(T * k)
    keep = (slot >= 0) & (slot < cap)
    slot_c = jnp.clip(slot, 0, cap - 1)
    dropped = 1.0 - keep.mean(dtype=jnp.float32)

    # -- scatter → expert einsums → gather -----------------------------------------
    x_rep = jnp.repeat(xf, k, axis=0)                            # (T*k, d)
    contrib = x_rep * keep[:, None].astype(dt)
    buf = jnp.zeros((E, cap, d), dtype=dt)
    buf = buf.at[expert, slot_c].add(contrib, mode="drop")
    buf = constrain(buf, "expert", "moe_cap", None)

    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    wg = p["wg"].astype(dt)
    wi = p["wi"].astype(dt)
    wo = p["wo"].astype(dt)
    h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) \
        * jnp.einsum("ecd,edf->ecf", buf, wi)
    y_buf = jnp.einsum("ecf,efd->ecd", h, wo)
    y_buf = constrain(y_buf, "expert", "moe_cap", None)

    y_tok = y_buf[expert, slot_c] * keep[:, None].astype(dt)     # (T*k, d)
    w_flat = weights.reshape(T * k).astype(dt)
    y = (y_tok * w_flat[:, None]).reshape(T, k, d).sum(1)

    if cfg.n_shared_experts:
        y = y + apply_mlp(cfg, p["shared"], xf)

    aux = {"aux_loss": aux_loss.astype(jnp.float32),
           "z_loss": z_loss.astype(jnp.float32),
           "dropped_frac": dropped}
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# shard_map-local dispatch (beyond-paper §Perf path)
# ---------------------------------------------------------------------------

def apply_moe_shard_map(cfg: ModelConfig, p: Params, x: jax.Array, rules
                        ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Shard-local MoE: route/scatter/compute per data shard; combine
    expert-parallel partial outputs with ONE psum over the model axis.

    Under plain SPMD the capacity-scatter reshards the global token buffer
    every layer (measured: ~166 TB all-reduce per step on kimi-k2 train_4k
    — EXPERIMENTS.md §Perf). Here every data shard routes only ITS tokens
    into a buffer for the experts its model shard owns (EP) or for an
    expert-FF slice (TP fallback); either way the only inter-chip traffic
    is the activation-sized psum of partial outputs over "model" — the
    same wire cost as a dense TP MLP — plus the FSDP weight gathers at the
    shard_map boundary.

    Capacity becomes per-data-shard (T_local-based), which is the standard
    per-device-capacity discipline at scale.
    """
    mesh = rules.mesh
    names = mesh.axis_names
    tp_axis = "model" if "model" in names else None
    B, S, d = x.shape
    E = cfg.n_experts
    P_ = PartitionSpec

    x_spec = rules.spec(("batch", None, None), x.shape)
    b_rule = rules.dim_rule("batch", B)
    dp_axes: Tuple[str, ...] = ((b_rule,) if isinstance(b_rule, str)
                                else tuple(b_rule or ()))
    ep = (rules.rules.get("expert") == tp_axis and tp_axis is not None)
    ff_tp = (not ep and tp_axis is not None
             and cfg.expert_d_ff % rules.axis_size.get(tp_axis, 1) == 0)
    # weight in_specs: EP slices experts; TP fallback slices expert-ff.
    if ep:
        wi_spec = P_(tp_axis, None, None)
        wo_spec = P_(tp_axis, None, None)
    elif ff_tp:
        wi_spec = P_(None, None, tp_axis)
        wo_spec = P_(None, tp_axis, None)
    else:
        wi_spec = wo_spec = P_()
    shared_specs = (jax.tree_util.tree_map(lambda _: P_(), p["shared"])
                    if "shared" in p else None)

    def body(x_l, router, wi, wg, wo, shared):
        Bl, Sl, _ = x_l.shape
        T = Bl * Sl
        xf = x_l.reshape(T, d)
        dt = x_l.dtype
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, cfg.n_experts_per_tok)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

        onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)
        tokens_per_expert = onehot.sum((0, 1)) / T
        mean_prob = probs.mean(0)
        aux_loss = E * jnp.sum(tokens_per_expert * mean_prob)
        z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

        k = cfg.n_experts_per_tok
        cap = _capacity(cfg, T)
        ohf = onehot.reshape(T * k, E)
        slot = (jnp.cumsum(ohf, axis=0) * ohf).sum(-1).astype(jnp.int32) - 1
        expert = ids.reshape(T * k)
        keep = (slot >= 0) & (slot < cap)
        dropped = 1.0 - keep.mean(dtype=jnp.float32)

        E_loc = wi.shape[0]
        if ep:
            e_start = jax.lax.axis_index(tp_axis) * E_loc
            local = (expert >= e_start) & (expert < e_start + E_loc)
            keep_l = keep & local
            expert_l = jnp.clip(expert - e_start, 0, E_loc - 1)
        else:
            keep_l = keep
            expert_l = expert
        slot_c = jnp.clip(slot, 0, cap - 1)
        x_rep = jnp.repeat(xf, k, axis=0)
        contrib = x_rep * keep_l[:, None].astype(dt)
        buf = jnp.zeros((E_loc, cap, d), dtype=dt)
        buf = buf.at[expert_l, slot_c].add(contrib, mode="drop")

        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))) \
            * jnp.einsum("ecd,edf->ecf", buf, wi.astype(dt))
        y_buf = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))
        y_tok = y_buf[expert_l, slot_c] * keep_l[:, None].astype(dt)
        w_flat = weights.reshape(T * k).astype(dt)
        y = (y_tok * w_flat[:, None]).reshape(T, k, d).sum(1)
        if tp_axis is not None:
            y = jax.lax.psum(y, tp_axis)        # combine EP / ff-TP partials
        if shared is not None:
            y = y + apply_mlp(cfg, shared, xf)
        aux = {"aux_loss": aux_loss.astype(jnp.float32),
               "z_loss": z_loss.astype(jnp.float32),
               "dropped_frac": dropped}
        if dp_axes:
            # router stats are token-local → average across data shards so
            # the aux losses equal the global-batch SPMD formulation
            aux = {k: jax.lax.pmean(v, dp_axes) for k, v in sorted(aux.items())}
        return y.reshape(Bl, Sl, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P_(), wi_spec, wi_spec, wo_spec, shared_specs),
        out_specs=(x_spec, {k: P_() for k in
                            ("aux_loss", "z_loss", "dropped_frac")}),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"], p.get("shared"))
    return y, aux

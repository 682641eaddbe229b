"""Driver for configurations of kind ``serve``: LM requests behind the
SLO-tier gateway.

The system under test: ``ServingGateway`` (the online planner, ``vos``
policy) admits and orders the requests, and ``ServeEngine`` (``fcfs``)
serves them with continuous batching, one prefill and one batched decode
step per tick, through ``models/``.

Traffic ``open``: requests are offered to the gateway when they fall due,
whether or not earlier ones have finished. Before every engine tick the
harness closes the gateway's window (``sync``), so a request is planned at
the first tick after it is due, and hands the engine the planned requests in
plan order, one whenever a slot is free (the engine admits one a tick). The
window offers the requests due in ``--seconds``; afterwards the engine runs
on until every offered request has finished, at most ``drain_s`` longer, so
that each due request's latency is measured.

* ``ttft_p50_ms``: due time to the end of the tick that produced the
  request's first token, median over the requests due in the window;
* ``tpot_p50_ms``: (last token - first token) / (output tokens - 1) per
  request, median.

At the chat cell's rate a window has 16 requests due, too few for a tail:
both are medians.

Correct: after the window, a sample of the finished requests drawn from the
seed, with the longest among them, is run through the float32 reference
(``bench/reference/<config>.py``) over prompt + served tokens; the widest
gap by which a served token's reference logit lies below the reference's
best at that position is compared with the configuration's limit.

The reference owns its layout. Each reference of kind ``serve`` exports

* ``MODEL_KEYS``: the settings of the configuration's ``model`` section
  that its ``logits`` reads (handed to it as a tuple of items);
* ``from_program(params, model)``: the harness's weights, made in the
  program's parameter layout, rearranged into the reference's, as the same
  arrays, uncast;
* ``logits(w, tokens, m, quant=None)``: float32 logits of one padded
  sequence.

So the reference receives the program's arrays in their served type
(bfloat16) and casts them to float32 one block at a time, as StableLM's
does per layer under its scan: a configuration whose weights fill the chip
is checked with no more than one block in float32 beside them (for sparse
experts the block is an expert).
"""

from __future__ import annotations

import collections
import gc
import math
import time
import traceback
from typing import Any, Dict, List, Optional

import numpy as np

import gen

#: request ids of the warm-up requests (above any window's)
WARM_RID = 10**9


# ---------------------------------------------------------------------------
# weights: made by the harness from the seed, on the device, in one call
# ---------------------------------------------------------------------------


def make_weights(model_mod, cfg, init: Dict[str, float], seed: int):
    """Random weights in the program's parameter layout (taken from the
    shapes of its ``init``), in the configuration's dtype, by leaf name:

    * norm scales 1; q/k/v biases ``N(0, bias_std)``; every other bias 0;
    * a Mamba mixer's per-channel leaves as Mamba publishes them (Gu & Dao,
      S4D-real), so that its state decays over many tokens: ``A_log`` =
      log(1..d_state) along its last axis, ``D`` 1, ``conv_b`` 0, and
      ``dt_bias`` the inverse softplus of a step drawn log-uniform in
      [1e-3, 1e-1] from the leaf's own key;
    * every other leaf (matrices, embeddings, routers) ``N(0, std)``.

    A rule fills a leaf whole, so it holds in each layer of a leaf stacked
    over the scanned repeats. Every leaf draws from its own key of one
    split, so a tree without a given kind of leaf is made as before. One
    jitted call on the device."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda k: model_mod.init(cfg, k),
                            jax.random.PRNGKey(0))
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    dt = jnp.dtype(cfg.param_dtype)

    def leaf(path, sd, key):
        name = getattr(path[-1], "key", None)
        if name in ("scale", "D"):
            return jnp.ones(sd.shape, dt)
        if name == "A_log":
            n = sd.shape[-1]
            a = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
            return jnp.broadcast_to(a, sd.shape).astype(dt)
        if name == "dt_bias":
            lo, hi = math.log(1e-3), math.log(1e-1)
            step = jnp.exp(jax.random.uniform(key, sd.shape, jnp.float32)
                           * (hi - lo) + lo)
            return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        if name in ("bq", "bk", "bv"):
            std = init["bias_std"]
        elif name in ("bias", "bo", "conv_b"):
            return jnp.zeros(sd.shape, dt)
        else:
            std = init["std"]
        return (jax.random.normal(key, sd.shape, jnp.float32) * std).astype(dt)

    def make(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            tdef, [leaf(p, sd, k) for (p, sd), k in zip(flat, keys)])

    key = jax.random.PRNGKey(int(gen.rng_for(seed, 6).integers(2**31)))
    return jax.jit(make)(key)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Served:
    """Per-request record of the window."""

    def __init__(self, req: gen.Request) -> None:
        self.req = req
        self.first: Optional[float] = None
        self.last: Optional[float] = None
        self.spec = None


def build(ctx):
    import repro.models.config as mconfig
    import repro.models.model as model_mod
    import repro.serve.engine as engine
    import repro.serve.gateway as gateway

    conf = ctx.cell.config
    m = dict(conf["model"])
    m["layer_pattern"] = tuple(m.get("layer_pattern", ("attn",)))
    cfg = mconfig.ModelConfig(**m)
    params = make_weights(model_mod, cfg, conf["init"], ctx.seed)
    e, g = conf["engine"], conf["gateway"]
    ecfg = engine.EngineConfig(max_batch=e["max_batch"], max_seq=e["max_seq"],
                               policy="fcfs",
                               prefill_cost_per_tok=e["prefill_s_per_token"],
                               decode_cost_per_tok=e["decode_s_per_step"])
    gcfg = gateway.GatewayConfig(policy=g["policy"], slo_unit=g["slo_unit_s"],
                                 window_s=g["window_s"],
                                 shed_backlog_s=g["shed_backlog_s"],
                                 preempt_backlog_s=g["preempt_backlog_s"],
                                 ecfg=ecfg)
    return {"cfg": cfg, "params": params, "ecfg": ecfg, "gcfg": gcfg,
            "engine": engine, "gateway": gateway}


def warm(p, traffic: Dict[str, Any], seed: int, vocab: int):
    """An engine on which every program of the window has run: a prefill of
    each prompt bucket, a slot insert at every slot, and the decode step."""
    eng_mod = p["engine"]
    eng = eng_mod.ServeEngine(p["cfg"], p["params"], p["ecfg"])
    lens = [int(b[0]) for b in traffic["prompt_buckets"]]
    n = max(p["ecfg"].max_batch, len(lens)) + len(lens)
    for i in range(n):
        r = gen.Request(WARM_RID + i, 0.0, lens[i % len(lens)], 3, "batch")
        eng.submit(eng_mod.RequestSpec(
            rid=r.rid, prompt=gen.prompt_tokens(r, seed, vocab),
            max_new_tokens=p["ecfg"].max_batch + 1, arrival=0.0))
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    eng.finished.clear()
    eng.clock, eng.ticks = 0.0, 0
    return eng


def run(ctx):
    conf, tr, rec = ctx.cell.config, ctx.cell.traffic, ctx.rec
    vocab = conf["model"]["vocab_size"]
    p = build(ctx)
    eng_mod = p["engine"]
    reqs = gen.open_loop(tr, ctx.seed, ctx.seconds)
    prompts = {r.rid: gen.prompt_tokens(r, ctx.seed, vocab) for r in reqs}
    eng = warm(p, tr, ctx.seed, vocab)
    gw = p["gateway"].ServingGateway(p["gcfg"])
    served = {r.rid: Served(r) for r in reqs}
    planned: collections.deque = collections.deque()
    handed: set = set()
    drain_s = tr.get("drain_s", 60.0)

    setup_s = ctx.open_window()
    t0 = time.perf_counter()
    ctx.tracer.start()
    nxt = 0
    lateness: List[float] = []
    while True:
        now = time.perf_counter() - t0
        with rec.span("gateway") as g:
            n_off = 0
            while nxt < len(reqs) and reqs[nxt].due <= now:
                r = reqs[nxt]
                spec = eng_mod.RequestSpec(rid=r.rid, prompt=prompts[r.rid],
                                           max_new_tokens=r.output_len - 1,
                                           arrival=r.due, tier=r.tier)
                served[r.rid].spec = spec
                lateness.append(now - r.due)
                gw.offer(spec)
                nxt += 1
                n_off += 1
            if n_off:
                gw.sync()
                for _t, rid in gw.plan_order():
                    if rid not in handed:
                        handed.add(rid)
                        planned.append(rid)
            g["offered"] = n_off
        busy = eng.queue or any(s is not None for s in eng.slots)
        if not busy and not planned:
            if nxt == len(reqs):
                break
            time.sleep(max(0.0, min(reqs[nxt].due - now, 0.05)))
            continue
        if planned and not eng.queue and None in eng.slots:
            spec = served[planned.popleft()].spec
            spec.arrival = eng.clock
            eng.submit(spec)
        kv = [int(eng.slot_pos[b]) + 1 for b, s in enumerate(eng.slots)
              if s is not None]
        admit = eng.queue[0] if eng.queue else None
        if admit is not None:
            kv.append(admit.prompt_len + 1)
        n_before = len(eng.finished)
        with rec.span("tick", admitted=int(admit is not None),
                      prompt=admit.prompt_len if admit else 0) as a:
            eng.step()
        t_tick = time.perf_counter() - t0
        a["kv"] = kv
        if admit is not None:
            served[admit.rid].first = t_tick
        for r in eng.finished[n_before:]:
            served[r.rid].last = t_tick
        ctx.tracer.poll()
        if t_tick > ctx.seconds + drain_s:
            break
    ctx.close_window()

    dropped = set(gw.drv.shed_instances) | set(gw.drv.cancelled_instances)
    done = [s for s in served.values() if s.last is not None]
    failed = len(served) - len(done)
    ttft = [(s.first - s.req.due) * 1e3 for s in done]
    tpot = [(s.last - s.first) * 1e3 / (s.req.output_len - 1) for s in done]
    notes = [f"requests due {len(reqs)}, finished {len(done)}, shed "
             f"{len(dropped)}, generator lateness p50 "
             f"{float(np.percentile(lateness, 50)) if lateness else 0.0!r} s "
             f"max {max(lateness, default=0.0)!r} s, ttft p90 "
             f"{float(np.percentile(ttft, 90)) if ttft else 0.0!r} ms, tpot p90 "
             f"{float(np.percentile(tpot, 90)) if tpot else 0.0!r} ms"]
    inf = float("inf")
    e2e = {"ttft_p50_ms": float(np.percentile(ttft, 50)) if ttft else inf,
           "tpot_p50_ms": float(np.percentile(tpot, 50)) if tpot else inf}

    # free the engine's caches before the reference takes the chip
    del eng, gw
    gc.collect()
    try:
        checks = check(ctx, p, done, prompts)
    except Exception:  # noqa: BLE001 - a check that cannot run fails the run
        traceback.print_exc()
        checks = {"served_gap": [float("inf"), conf["checks"]["served_gap"]]}
    checks["requests_failed"] = [failed, 0]
    return {"setup_s": setup_s, "end_to_end": e2e, "attempted": len(reqs),
            "failed": failed, "checks": checks,
            "layer": {"model": conf["model"]}, "notes": notes}


def sample(done: List[Served], seed: int, k: int) -> List[Served]:
    """``k`` finished requests drawn from the seed, the longest first."""
    if not done:
        return []
    longest = max(done, key=lambda s: (s.req.prompt_len + s.req.output_len,
                                       -s.req.rid))
    rest = [s for s in done if s is not longest]
    rng = gen.rng_for(seed, 7)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(ref, w, m_items, max_seq: int, prompt: np.ndarray,
                out: np.ndarray, quant: Optional[str] = None) -> np.ndarray:
    """Per served token: the reference's best logit minus the reference's
    logit of the served token, at that token's position (prompt + the
    served tokens before it), in units of the standard deviation of the
    reference's logits at that position. With ``quant`` the tokens are
    instead the argmax of the reference run at that precision (the
    control)."""
    import jax.numpy as jnp

    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    n = len(seq)
    pad = np.zeros(max_seq, np.int32)
    pad[:n] = seq
    lg = ref.logits(w, jnp.asarray(pad), m_items)
    lo = len(prompt) - 1
    rows = np.asarray(lg[lo:n], np.float64)
    if quant is not None:
        out = np.asarray(ref.logits(w, jnp.asarray(pad), m_items,
                                    quant=quant)[lo:n].argmax(-1))
    gap = rows.max(-1) - rows[np.arange(len(rows)), out]
    return gap / rows.std(-1)


def check(ctx, p, done: List[Served], prompts) -> Dict[str, List[float]]:
    """The widest served gap over the sampled requests' tokens, and the same
    of each control's first choices."""
    conf = ctx.cell.config
    lim = conf["checks"]
    ref = ctx.reference
    w = ref.from_program(p["params"], conf["model"])
    m_items = tuple((k, conf["model"][k]) for k in ref.MODEL_KEYS)
    picked = sample(done, ctx.seed, lim["sample"])
    out: Dict[str, List[float]] = {}
    for q in (None,) + tuple(ctx.controls):
        gaps = [served_gaps(ref, w, m_items, conf["engine"]["max_seq"],
                            prompts[s.req.rid],
                            np.asarray(s.spec.output, np.int64), quant=q).max()
                for s in picked]
        name = f"control_{q}.served_gap" if q else "served_gap"
        out[name] = [float(max(gaps, default=np.inf)), lim["served_gap"]]
    return out

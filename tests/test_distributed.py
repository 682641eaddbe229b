"""Distributed layer: sharding rules, collectives, sharded e2e step.

These need >1 device, so each case runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count set (the main test
process keeps the single real CPU device, per the dry-run contract).
"""

import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


def test_strategy_and_param_specs_divisibility():
    out = run_sub("""
        import jax, json, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import sharding as sh
        from repro.launch.mesh import make_mesh
        from repro.models import model as M

        mesh = make_mesh((2, 4), ("data", "model"))
        # musicgen: 6 heads % 4 != 0 → attention replicated, d_ff sharded
        cfg = get_config("musicgen-medium", smoke=True)
        rules = sh.strategy_for(cfg, mesh)
        assert rules.rules["heads"] is None, rules.rules
        assert rules.rules["d_ff"] == "model"
        assert "not divisible" in rules.notes

        # qwen3 smoke: 4 heads % 4 == 0 → sharded
        cfg2 = get_config("qwen3-0.6b", smoke=True)
        rules2 = sh.strategy_for(cfg2, mesh)
        assert rules2.rules["heads"] == "model"
        params = jax.eval_shape(lambda: M.init(cfg2, jax.random.PRNGKey(0)))
        with sh.logical_axis_rules(rules2):
            specs = sh.param_specs(params)
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        d = {jax.tree_util.keystr(p): s for p, s in flat}
        assert d["['embed']['embedding']"] == P("model", None)
        wq = [v for k, v in d.items() if "attn']['wq" in k][0]
        assert wq == P("layers", None, "model") or wq == P(None, None, "model"), wq
        # batch-1 fallback: long-context batch of 1 can't shard over data
        spec1 = rules2.spec(("batch", None), (1, 8))
        assert spec1 == P(None, None)
        print("OK")
    """)
    assert "OK" in out


def test_hierarchical_psum_equals_flat():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import hierarchical_psum
        mesh = make_mesh((2, 4), ("pod", "data"))
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 33)),
                        jnp.float32)
        f1 = jax.shard_map(lambda v: jax.lax.psum(v, ("pod", "data")),
                           mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)(x)
        f2 = jax.shard_map(lambda v: hierarchical_psum(v), mesh=mesh,
                           in_specs=P(), out_specs=P(), check_vma=False)(x)
        assert float(jnp.abs(f1 - f2).max()) < 1e-4
        print("OK")
    """)
    assert "OK" in out


def test_int8_allreduce_accuracy_and_error_feedback():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import int8_allreduce
        mesh = make_mesh((8,), ("data",))
        vals = jnp.asarray(np.random.default_rng(1).normal(0, 1, (8, 1000)),
                           jnp.float32)
        ref = jax.shard_map(lambda v: jax.lax.pmean(v, "data"), mesh=mesh,
                            in_specs=P("data"), out_specs=P("data"),
                            check_vma=False)(vals)
        def comp(v, e):
            out, e2 = int8_allreduce(v[0], axis="data", error=e[0])
            return out[None], e2[None]
        out, err = jax.shard_map(comp, mesh=mesh,
                                 in_specs=(P("data"), P("data")),
                                 out_specs=(P("data"), P("data")),
                                 check_vma=False)(vals, jnp.zeros_like(vals))
        rel = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
        assert rel < 0.02, rel
        assert float(jnp.abs(err).max()) > 0      # residual captured
        print("OK")
    """)
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    """The same train step, sharded over an 8-device (4 data × 2 model)
    mesh, must produce the same loss trajectory as unsharded execution."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import sharding as sh
        from repro.train.optimizer import OptConfig
        from repro.train.train_step import build_train_step, init_train_state

        cfg = get_config("qwen3-0.6b", smoke=True)
        oc = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        state = init_train_state(cfg, oc, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 2,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        step = build_train_step(cfg, oc, remat=False)

        # single device
        s1, m1 = jax.jit(step)(state, batch)

        # sharded
        mesh = make_mesh((4, 2), ("data", "model"))
        rules = sh.strategy_for(cfg, mesh)
        with sh.logical_axis_rules(rules):
            st_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), sh.param_specs(state),
                is_leaf=lambda x: isinstance(x, P))
            b_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), sh.batch_specs(batch),
                is_leaf=lambda x: isinstance(x, P))
            def fn(s, b):
                with sh.logical_axis_rules(rules):
                    return step(s, b)
            with jax.set_mesh(mesh):
                s2, m2 = jax.jit(fn, in_shardings=(st_sh, b_sh),
                                 out_shardings=(st_sh, None))(state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, \\
            (float(m1["loss"]), float(m2["loss"]))
        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(np.asarray(a, np.float32)
                                       - np.asarray(b, np.float32)).max()),
            s1["params"], s2["params"])
        assert max(jax.tree_util.tree_leaves(d)) < 1e-4
        print("OK")
    """)
    assert "OK" in out

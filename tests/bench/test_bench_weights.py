"""The serve harness's weights and its check: the chat configuration's
weights and served gaps read exactly as before Mamba leaves had rules of
their own, and those rules hold on a hybrid's shapes."""

import dataclasses
import hashlib
import json
import math
import types

import jax
import numpy as np
import pytest
import run
from conftest import BENCH, tiny_checkout

import gen
import repro.models.config as mconfig
import repro.models.model as model_mod
from repro.configs import jamba_v0_1_52b

SEED = 2**31 + 77
#: the tiny chat configuration's weights at ``SEED`` (sha256 of each leaf's
#: path, dtype, shape and bytes, in tree order)
DIGEST = "761f8c41f63532763cf8901bd6e081b09c8272eb01cfaa9eb926c39e6fdfe032"
#: ``served_gap`` and ``control_fp8.served_gap`` of seven requests whose
#: "served" tokens are drawn from the seed, on those weights
SERVED_GAP = (5.463324391356369, 3.0461485316796026)

serve = run.load_module(BENCH / "drivers" / "serve.py")


@pytest.fixture(scope="module")
def chat(tmp_path_factory):
    root = tiny_checkout(tmp_path_factory.mktemp("chat"))
    conf = json.loads((root / "bench" / "configs" / "stablelm-1.6b.json")
                      .read_text())
    cfg = mconfig.ModelConfig(**conf["model"])
    return conf, cfg, serve.make_weights(model_mod, cfg, conf["init"], SEED)


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_chat_weights_unchanged(chat):
    assert _digest(chat[2]) == DIGEST


def test_chat_served_gap_unchanged(chat):
    conf, cfg, params = chat
    reqs = [gen.Request(i, 0.0, p, o, "batch") for i, (p, o) in enumerate(
        [(8, 8), (24, 16), (24, 8), (8, 16), (24, 16), (8, 8), (24, 8)])]
    prompts = {r.rid: gen.prompt_tokens(r, SEED, cfg.vocab_size) for r in reqs}
    done = []
    for r in reqs:
        s = serve.Served(r)
        out = gen.rng_for(SEED, 9, r.rid).integers(2, cfg.vocab_size,
                                                   r.output_len)
        s.spec = types.SimpleNamespace(output=out.tolist())
        done.append(s)
    ref = run.load_module(BENCH / "reference" / "stablelm-1.6b.py")
    ctx = types.SimpleNamespace(cell=types.SimpleNamespace(config=conf),
                                reference=ref, seed=SEED, controls=("fp8",))
    c = serve.check(ctx, {"params": params}, done, prompts)
    assert (c["served_gap"][0], c["control_fp8.served_gap"][0]) == SERVED_GAP


@pytest.mark.parametrize("n_layers", [8, 16])
def test_mamba_leaves_get_the_published_init(n_layers):
    """On the hybrid's small shapes (one and two repeats of its 8-layer
    period): each rule fills its leaf whole, in every stacked layer."""
    cfg = dataclasses.replace(jamba_v0_1_52b.SMOKE, n_layers=n_layers,
                              param_dtype="float32")
    params = serve.make_weights(model_mod, cfg, {"std": 0.02,
                                                 "bias_std": 0.02}, SEED)
    mambas = [blk["mamba"] for blk in params["scan"] if "mamba" in blk]
    assert len(mambas) == 7
    a = np.arange(1, cfg.ssm_state + 1, dtype=np.float32)
    steps = []
    for p in mambas:
        repeats = n_layers // 8
        assert p["A_log"].shape == (repeats, cfg.d_inner, cfg.ssm_state)
        np.testing.assert_allclose(np.exp(np.asarray(p["A_log"])),
                                   np.broadcast_to(a, p["A_log"].shape),
                                   rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(p["D"]), 1.0)
        np.testing.assert_array_equal(np.asarray(p["conv_b"]), 0.0)
        step = np.asarray(jax.nn.softplus(p["dt_bias"]))
        assert step.shape == (repeats, cfg.d_inner)
        np.testing.assert_allclose(step.min(), 1e-3, rtol=0.5)
        assert step.min() >= 1e-3 * (1 - 1e-5)
        assert step.max() <= 1e-1 * (1 + 1e-5)
        steps.append(step)
    # log-uniform: the median step near the geometric middle, 1e-2
    logs = np.log(np.concatenate([s.ravel() for s in steps]))
    assert abs(np.median(logs) - math.log(1e-2)) < 0.5
    # each leaf, and each stacked layer, draws its own
    flat = [s.reshape(-1, cfg.d_inner) for s in steps]
    rows = np.concatenate(flat)
    assert len({r.tobytes() for r in rows}) == len(rows)
    # the matrices keep N(0, std)
    w = np.asarray(mambas[0]["in_proj"])
    assert abs(w.std() - 0.02) < 0.002

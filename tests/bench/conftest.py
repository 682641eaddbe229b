"""Fixtures of the benchmark's tests: a copy of the harness in a temporary
checkout, with the cells cut to sizes a CPU runs in seconds."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for _p in (BENCH, REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: the repository's cells, cut to CPU size (every width shrunk alike)
TINY_MODEL = {
    "name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 176,
    "vocab_size": 512, "norm": "layernorm", "norm_eps": 1e-05, "act": "silu",
    "use_bias": True, "tie_embeddings": False, "rotary_pct": 0.25,
    "rope_theta": 10000.0, "dtype": "float32", "param_dtype": "float32",
}


def write_json(path: pathlib.Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def tiny_checkout(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout holding the program (``src`` linked), a copy of
    ``bench/`` and the repository's manifest with each cell's
    configuration and traffic cut to CPU size under the same names."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    write_json(root / "BENCHMARK.json", manifest)
    ds = json.loads((BENCH / "configs" / "ds16-paper.json").read_text())
    ds["batch"]["rows"] = 4096
    write_json(root / "bench" / "configs" / "ds16-paper.json", ds)
    lm = json.loads((BENCH / "configs" / "stablelm-1.6b.json").read_text())
    lm["model"] = dict(TINY_MODEL)
    lm["engine"].update(max_batch=4, max_seq=64)
    # larger weights than the published init: at this width they make each
    # token depend on its context as strongly as at full size
    lm["init"].update(std=0.3, bias_std=0.3)
    lm["checks"].update(sample=6)
    write_json(root / "bench" / "configs" / "stablelm-1.6b.json", lm)
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    chat.update(rate_per_s=60.0, prompt_buckets=[[8, 0.5], [24, 0.5]],
                output_buckets=[[8, 0.5], [16, 0.5]], drain_s=20)
    write_json(root / "bench" / "traffic" / "chat.json", chat)
    return root


@pytest.fixture
def checkout(tmp_path):
    return tiny_checkout(tmp_path)

"""The harness: a cell taken from new files alone, and refusal without a
TPU or without the program."""

import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest
import run
from conftest import REPO, write_json

METRIC = '''"""Tasks executed per instance planned in the traced stretch."""


def read(run):
    tasks = [s for s in run.rec.named("task", run.t0, run.t1)
             if s.attrs["round"] >= 0]
    insts = run.rec.named("planner", run.t0, run.t1)
    return len(tasks) / len(insts) if insts else None
'''


def _digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_cell_from_new_files_only(checkout):
    bench = checkout / "bench"
    before = _digest(bench)
    # a configuration, its reference, a traffic mix and a per-layer metric,
    # each a new file; a cell and the metric as new manifest entries
    conf = json.loads((bench / "configs" / "ds16-paper.json").read_text())
    conf.update(name="ds16-small", batch=dict(conf["batch"], rows=2048))
    write_json(bench / "configs" / "ds16-small.json", conf)
    shutil.copy(bench / "reference" / "ds16-paper.py",
                bench / "reference" / "ds16-small.py")
    write_json(bench / "traffic" / "pair.json",
               {"loop": "closed", "in_flight": 2, "distinct_inputs": 2})
    (bench / "metrics" / "executor.tasks_per_instance.py").write_text(METRIC)
    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "ds16-small", "source": "https://arxiv.org/abs/2108.02558",
        "file": "bench/configs/ds16-small.json", "reduced": ["rows"],
        "why": "a smaller batch"})
    manifest["workloads"].append({
        "name": "ds16-small.pair", "config": "ds16-small", "traffic": "pair",
        "chips": 1, "why": "two sources"})
    manifest["per_layer"].append({
        "name": "executor.tasks_per_instance", "unit": "tasks", "better": "lower",
        "source": "host_clock", "layer": "executor", "moves": "pipelines_per_s",
        "workloads": ["ds16-small.pair"]})
    manifest["end_to_end"][0]["workloads"].append("ds16-small.pair")
    write_json(checkout / "BENCHMARK.json", manifest)

    res = run.execute("ds16-small.pair", 2**31 + 3, 1.0, True, root=checkout,
                      bench=bench, platform="cpu")
    assert res["correct"] is True
    assert res["metrics"]["executor.tasks_per_instance"]["value"] == 16
    assert res["attempted"] >= 1 and res["failed"] == 0
    res = run.execute("ds16-small.pair", 5, 1.0, False, root=checkout,
                      bench=bench, platform="cpu")
    assert set(res["metrics"]) == {"pipelines_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def _no_stdout_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ds16-paper.closed",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_stdout_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, 'bench'); import run; "
            "run.execute('ds16-paper.closed', 1, 1.0, False, platform='cpu')")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr


def test_unknown_workload_and_device(checkout):
    with pytest.raises(KeyError):
        run.load_cell("nope.none", checkout, checkout / "bench")
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")
    assert run.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12

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


#: the reference of a dense decoder whose scanned period holds two layers:
#: StableLM-2's, over the layers in their order, repeat by repeat
PAIR_REFERENCE = '''"""Plain float32 reference of a dense decoder of two-layer periods."""

import importlib.util
import pathlib

import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "stablelm_reference", pathlib.Path(__file__).with_name("stablelm-1.6b.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)
MODEL_KEYS = base.MODEL_KEYS
logits = base.logits


def from_program(params, model):
    """Each scanned position's layers in StableLM's layout, interleaved:
    layer ``r * period + j`` is repeat ``r`` of position ``j``."""
    one = [base.from_program(dict(params, scan=[blk]), model)
           for blk in params["scan"]]
    layers = {k: jnp.stack([o["layers"][k] for o in one], axis=1)
              .reshape((-1,) + one[0]["layers"][k].shape[1:])
              for k in one[0]["layers"]}
    return dict(one[0], layers=layers)
'''


def test_serve_cell_from_new_files_only(checkout, monkeypatch):
    """A serve configuration of another layout (two scanned positions, which
    StableLM's reference cannot unpack) enters with a configuration, its
    reference and manifest entries, and nothing else changed; the same cell
    with its decode step's cache left unchanged is not correct."""
    from repro.serve import engine
    from test_bench_faults_serve import _unchanged

    bench = checkout / "bench"
    before = _digest(bench)
    conf = json.loads((bench / "configs" / "stablelm-1.6b.json").read_text())
    conf["name"] = "tiny-pair"
    conf["model"].update(name="tiny-pair", n_layers=4,
                         layer_pattern=["attn", "attn"])
    write_json(bench / "configs" / "tiny-pair.json", conf)
    (bench / "reference" / "tiny-pair.py").write_text(PAIR_REFERENCE)
    manifest = json.loads((checkout / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-pair",
        "source": "https://huggingface.co/stabilityai/stablelm-2-1_6b",
        "file": "bench/configs/tiny-pair.json", "reduced": ["n_layers"],
        "why": "a dense decoder of two-layer periods"})
    manifest["workloads"].append({
        "name": "tiny-pair.chat", "config": "tiny-pair", "traffic": "chat",
        "chips": 1, "why": "the chat mix on a two-position period"})
    for m in manifest["end_to_end"]:
        if CHAT in m.get("workloads", []):
            m["workloads"].append("tiny-pair.chat")
    write_json(checkout / "BENCHMARK.json", manifest)

    def execute():
        return run.execute("tiny-pair.chat", 2**31 + 29, 2.0, False,
                           root=checkout, bench=bench, platform="cpu")

    res = execute()
    assert res["correct"] is True
    assert set(res["metrics"]) == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    monkeypatch.setattr(engine, "build_decode_step", _unchanged)
    res = execute()
    assert res["correct"] is False
    gap = res["checks"]["served_gap"]
    assert gap["value"] > gap["limit"]


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


CHAT = "stablelm-1.6b.chat"
SERVE_READERS = {"gateway.ms_per_request", "engine.prefill_ms",
                 "engine.decode_step_ms", "model.decode_step_roofline",
                 "model.decode_mfu", "device.idle_share.serve"}


def _manifest() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_chat_cell_from_the_manifest_alone():
    """The serving cell is the manifest's own, with no file of queued
    entries beside it, and carries its end-to-end metrics and six
    readers."""
    assert not (REPO / "bench" / "queued.json").exists()
    assert CHAT in {w["name"] for w in _manifest()["workloads"]}
    cell = run.load_cell(CHAT)
    assert cell.chips == 1 and cell.config["kind"] == "serve"
    assert cell.config["name"] == "stablelm-1.6b"
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == SERVE_READERS
    for name in SERVE_READERS:
        assert (REPO / "bench" / "metrics" / f"{name}.py").is_file()


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_metric_workloads_name_cells_of_the_manifest(section):
    cells = {w["name"] for w in _manifest()["workloads"]}
    for m in _manifest()[section]:
        assert set(m.get("workloads", [])) <= cells, m["name"]


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  json.loads((REPO / "BENCHMARK.json")
                                             .read_text())["workloads"]])
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    c = run.load_cell(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    moved = {m["moves"] for m in c.per_layer}
    assert moved <= names

"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` at the checkout's root, loads its
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``) by name, and runs the driver of the
configuration's ``kind`` (``bench/drivers/<kind>.py``). With ``--trace 0``
the last line of standard output carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the first seconds of the window is reduced
(``bench/trace.py``) and each per-layer metric is read by its own reader
(``bench/metrics/<metric>.py``). Every run checks what the timed path
produced against the configuration's plain reference (``bench/reference/``)
and prints each compared number beside its limit, on standard error and as
the result's last key.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence, Tuple  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: seconds of the window that a ``--trace 1`` run traces
TRACE_SECONDS = 10.0

if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from spans import CompileCounter, Recorder  # noqa: E402


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a harness file by path (names may hold dots and dashes)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of the manifest, with everything it names loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench: pathlib.Path


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_manifest(root: pathlib.Path = ROOT,
                  bench: pathlib.Path = BENCH) -> Dict[str, Any]:
    """``BENCHMARK.json``, with the entries of ``bench/queued.json``
    appended: cells written but not yet proven on the chip, which the
    benchmark's own runs never name. Calibration runs and tests drive them
    by name until a later benchmark change moves them into the manifest."""
    manifest = load_json(root / "BENCHMARK.json")
    queued = bench / "queued.json"
    if queued.exists():
        for key, entries in load_json(queued).items():
            manifest[key] = manifest[key] + entries
    return manifest


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench: pathlib.Path = BENCH) -> Cell:
    manifest = load_manifest(root, bench)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in manifest["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in manifest["per_layer"]
                           if _applies(m, name)],
                bench=bench)


def use_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says); every program is kept, however
    quickly it compiled, so a warm run loads every program it uses."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, platform: str = "tpu") -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != platform:
        raise NoDevice(f"no TPU: the default backend is {d.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Tracer:
    """Profiler over the first ``limit_s`` seconds of the window; a no-op
    unless enabled. Drivers call :meth:`start` when the window opens and
    :meth:`poll` between units of work."""

    def __init__(self, enabled: bool, rec: Recorder, directory: pathlib.Path,
                 limit_s: float) -> None:
        self.enabled = enabled
        self.rec = rec
        self.dir = directory
        self.limit_s = limit_s
        self.t0: Optional[int] = None
        self.t1: Optional[int] = None
        self._ann = None

    def start(self) -> None:
        if not self.enabled or self.t0 is not None:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self.rec.tracing = True
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()

    def poll(self) -> None:
        if (self.t0 is not None and self.t1 is None
                and time.perf_counter_ns() - self.t0 >= self.limit_s * 1e9):
            self.stop()

    def stop(self) -> None:
        if self.t0 is None or self.t1 is not None:
            return
        import jax

        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        self.rec.tracing = False
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's parameters and the
    harness's recorders."""

    cell: Cell
    seed: int
    seconds: float
    rec: Recorder
    compiles: CompileCounter
    tracer: Tracer
    t_process: float
    chips: int
    #: controls to read beside the program (calibration only)
    controls: Tuple[str, ...] = ()
    #: the configuration's plain reference (``bench/reference/<name>.py``)
    reference: Any = None
    #: set by :meth:`close_window`
    memory_peak_bytes: Optional[int] = None
    compiles_at_open: int = 0
    compiles_in_window: int = 0

    def open_window(self) -> float:
        """Set-up ends here: returns ``setup_s``."""
        self.compiles_at_open = self.compiles.total()
        return time.perf_counter() - self.t_process

    def close_window(self) -> None:
        """Read the compile count and the memory peak before any check
        runs, so that a reference's own memory and programs stay out."""
        self.tracer.stop()
        self.compiles_in_window = self.compiles.total() - self.compiles_at_open
        self.memory_peak_bytes = memory_peak(self.chips)


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""

    setup_s: float
    #: end-to-end metric name -> value (``setup_s`` excluded)
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    #: compared number -> (value, limit); correct iff every value <= limit
    checks: Dict[str, List[float]]
    #: what the per-layer readers read besides spans and the trace
    layer: Dict[str, Any]
    #: lines printed on standard error before the checks
    notes: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class LayerRun:
    """What a per-layer reader sees."""

    rec: Recorder
    trace: Any
    layer: Dict[str, Any]
    peaks: Dict[str, float]
    t0: int
    t1: int


def read_per_layer(cell: Cell, run: LayerRun) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric by its reader. A reader that finds nothing to
    read returns None: the metric is left out of the result, and a line on
    standard error names it, so that a trace that no longer holds what a
    reader looks for shows."""
    out = {}
    for m in cell.per_layer:
        value = load_module(cell.bench / "metrics" / f"{m['name']}.py").read(run)
        if value is None:
            print(f"per-layer {m['name']}: nothing to read in this run",
                  file=sys.stderr)
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peaks_for(kind: str, bench: pathlib.Path = BENCH) -> Dict[str, float]:
    table = load_json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: pathlib.Path = ROOT, bench: pathlib.Path = BENCH,
            platform: str = "tpu", cell: Optional[Cell] = None,
            controls: Sequence[str] = (),
            t_process: float = T_PROCESS) -> Dict[str, Any]:
    """One run; returns the result object (the last line's content).

    ``cell`` replaces the manifest's (the calibration runs change a mix's
    rate), ``controls`` asks the driver to read the named controls beside
    the program (see ``bench/calibrate.py``), ``t_process`` is where set-up
    starts."""
    cell = cell or load_cell(workload, root, bench)
    if platform == "tpu":
        use_compile_cache(root)
    device = device_info(cell.chips, platform)
    peaks = peaks_for(device["kind"], bench) if platform == "tpu" else {}
    rec = Recorder()
    tracer = Tracer(trace, rec, root / ".bench" / "trace" / workload,
                    min(seconds, TRACE_SECONDS))
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    ctx = Context(cell=cell, seed=seed, seconds=seconds, rec=rec,
                  compiles=CompileCounter(), tracer=tracer,
                  t_process=t_process, chips=cell.chips,
                  controls=tuple(controls),
                  reference=load_module(
                      bench / "reference" / f"{cell.config['name']}.py"))
    driver = load_module(bench / "drivers" / f"{cell.config['kind']}.py")
    out = Outcome(**driver.run(ctx))
    ctx.tracer.stop()
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    checks = dict(out.checks)
    checks["compiles_in_window"] = [ctx.compiles_in_window, 0]
    # a control read beside the program (calibration only) is not the run's
    correct = all(v <= lim for k, (v, lim) in checks.items()
                  if not k.startswith("control_")) and out.attempted > 0
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": int(out.attempted),
                              "failed": int(out.failed)}
    if trace:
        red = load_module(bench / "trace.py").read_dir(tracer.dir)
        shutil.rmtree(tracer.dir, ignore_errors=True)
        run = LayerRun(rec=rec, trace=red, layer=out.layer, peaks=peaks,
                       t0=tracer.t0, t1=tracer.t1)
        result["metrics"] = read_per_layer(cell, run)
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s()
        result["device"] = device
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    else:
        values = dict(out.end_to_end, setup_s=out.setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for line in out.notes:
        print(line, file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    return result


def _finite(obj: Any) -> Any:
    """JSON cannot carry nan or inf: write them as strings."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return str(obj)
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except NoDevice as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

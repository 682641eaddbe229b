"""Reduction of a profiler trace to the intervals the metrics read.

The JAX profiler writes an XSpace (``<dir>/plugins/profile/<run>/*.xplane.pb``).
Of it this module keeps:

* per device plane (``/device:TPU:<n>``), the operations that ran
  (line ``XLA Ops``) and the programs they belong to (line ``XLA Modules``),
  as ``(name, start_ns, end_ns)``;
* on the host, the harness's own annotations (``bench.<span>``, see
  :mod:`spans`), with their arguments;
* the traced window: the ``bench.traced`` annotation.

Every number is derived from these lists by the functions below, so a
synthetic :class:`Trace` exercises the same arithmetic as a recorded one.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]

PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class HostSpan:
    name: str
    t0: float
    t1: float
    args: Dict[str, object]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Tuple[float, float]], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that disjoint ``merged`` intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def _base(name: str) -> str:
    """Program name without the trailing ``(<id>)`` the profiler adds."""
    i = name.find("(")
    return name[:i] if i > 0 else name


@dataclasses.dataclass
class Trace:
    #: device plane -> operations, and -> programs
    ops: Dict[str, List[Interval]]
    modules: Dict[str, List[Interval]]
    spans: List[HostSpan]
    window: Tuple[float, float]
    _merged: Optional[Dict[str, List[Tuple[float, float]]]] = None

    # -- device busy time ---------------------------------------------------
    def merged(self) -> Dict[str, List[Tuple[float, float]]]:
        if self._merged is None:
            self._merged = {p: union([(s, e) for _n, s, e in evs])
                            for p, evs in self.ops.items()}
        return self._merged

    def busy_ns(self, lo: float, hi: float) -> float:
        """Device busy time inside ``[lo, hi]``, averaged over devices."""
        m = self.merged()
        if not m:
            return 0.0
        return sum(covered(v, lo, hi) for v in m.values()) / len(m)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        return self.busy_ns(*self.window) * 1e-9

    def idle_share(self) -> Optional[float]:
        w = self.window[1] - self.window[0]
        if w <= 0 or not self.ops:
            return None
        return 1.0 - self.busy_ns(*self.window) / w

    # -- host spans -----------------------------------------------------------
    def named(self, name: str) -> List[HostSpan]:
        lo, hi = self.window
        return [s for s in self.spans
                if s.name == name and s.t0 >= lo and s.t1 <= hi]

    def busy_in(self, spans: Sequence[HostSpan]) -> float:
        """Device busy ns inside the given host spans (each counted once)."""
        iv = union([(s.t0, s.t1) for s in spans])
        return sum(self.busy_ns(s, e) for s, e in iv)

    # -- programs -------------------------------------------------------------
    def module_events(self, prefix: str, lo: Optional[float] = None,
                      hi: Optional[float] = None) -> List[Interval]:
        """Program runs whose name starts with ``prefix`` and that start
        inside ``[lo, hi]`` (the window by default), on the first device."""
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        if not self.modules:
            return []
        plane = sorted(self.modules)[0]
        return [ev for ev in self.modules[plane]
                if _base(ev[0]).startswith(prefix) and lo <= ev[1] <= hi]

    # -- breakdown ------------------------------------------------------------
    def top_ops(self, k: int = 10) -> List[List[object]]:
        """The ``k`` device operations that took most time in the window,
        as ``[name, seconds]`` (first device)."""
        if not self.ops:
            return []
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for n, s, e in self.ops[sorted(self.ops)[0]]:
            d = max(0.0, min(e, hi) - max(s, lo))
            if d > 0:
                tot[n] = tot.get(n, 0.0) + d
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9] for n, v in top]

    def idle_gaps(self, k: int = 10) -> List[List[object]]:
        """Device idle time in the window, summed by what the host was
        doing (the innermost harness span around each gap's midpoint,
        ``host`` where none), largest ``k`` first, as ``[label, seconds]``."""
        if not self.ops:
            return []
        lo, hi = self.window
        busy = self.merged()[sorted(self.ops)[0]]
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, min(s, hi)))
            t = max(t, e)
            if t >= hi:
                break
        if t < hi:
            gaps.append((t, hi))
        inner = sorted((s for s in self.spans if s.name != "traced"),
                       key=lambda s: s.t1 - s.t0)
        tot: Dict[str, float] = {}
        for g0, g1 in gaps:
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            label = next((s.name for s in inner if s.t0 <= mid <= s.t1), "host")
            tot[label] = tot.get(label, 0.0) + (g1 - g0)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9] for n, v in top]


def _stat(v: object) -> object:
    return v.decode() if isinstance(v, bytes) else v


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[HostSpan] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(e.name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns))
                           for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append(HostSpan(
                            e.name[len(PREFIX):], float(e.start_ns),
                            float(e.start_ns + e.duration_ns),
                            {k: _stat(v) for k, v in e.stats}))
    for p in list(modules):
        ops.setdefault(p, modules[p])
    traced = [s for s in spans if s.name == "traced"]
    ts = [x for evs in ops.values() for _n, s, e in evs for x in (s, e)]
    window = (min(ts), max(ts)) if ts else (0.0, 0.0)
    if traced:
        # the harness's own window, unless the device events are on a clock
        # that it does not overlap (then their own extent stands in)
        t0, t1 = traced[0].t0, traced[0].t1
        if not ts or any(s < t1 and e > t0 for evs in ops.values()
                         for _n, s, e in evs):
            window = (t0, t1)
    spans.sort(key=lambda s: s.t0)
    return Trace(ops=ops, modules=modules, spans=spans, window=window)


def read_dir(directory: os.PathLike) -> Trace:
    """Reduce the newest trace written under ``directory``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {directory}")
    return from_profile(ProfileData.from_file(files[-1]))

"""Host spans and the compile counter of one benchmark run.

A span is ``(name, t0_ns, t1_ns, attrs)`` on ``time.perf_counter_ns``. While
the profiler runs, every span is also written into the trace as a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``, carrying the
attributes known when it opens, so the trace reduction (``trace.py``) can
put device events beside what the host did.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, Iterator, List, Optional

#: prefix of the harness's annotations inside a profiler trace
TRACE_PREFIX = "bench."


@dataclasses.dataclass
class Span:
    name: str
    t0: int
    t1: int
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) * 1e-9


class Recorder:
    """Spans in memory; written out only through the result."""

    def __init__(self) -> None:
        self.spans: List[Span] = []
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
        """Record ``name`` around the block; the block may add attrs."""
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(
                TRACE_PREFIX + name,
                **{k: v for k, v in attrs.items()
                   if isinstance(v, (int, float, str))})
        t0 = time.perf_counter_ns()
        with ann:
            try:
                yield attrs
            finally:
                self.spans.append(Span(name, t0, time.perf_counter_ns(), attrs))

    def named(self, name: str, lo: Optional[int] = None,
              hi: Optional[int] = None) -> List[Span]:
        """Spans called ``name`` that lie inside ``[lo, hi]``."""
        return [s for s in self.spans if s.name == name
                and (lo is None or s.t0 >= lo) and (hi is None or s.t1 <= hi)]


class CompileCounter:
    """Counts XLA programs compiled and persistent-cache hits, through
    jax's monitoring events. Register once per process."""

    def __init__(self) -> None:
        import jax

        self.programs = 0
        self.cache_hits = 0

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs += 1

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def total(self) -> int:
        """Programs that had to be built or loaded: either means a new
        shape reached the compiler."""
        return self.programs + self.cache_hits

"""Named spans of the program, on the profiler's clock.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation`` called
``jita.<name>``: while a profiler trace runs (``jax.profiler.trace(dir)``),
it lands in the trace's host plane beside the device's operations and
programs, carrying ``attrs``; with no profiler it records nothing and costs
a microsecond or two. An attribute known only inside the span is added with
``set_metadata`` on the object the ``with`` statement binds.

The profiler's encoding ends a string attribute at ``#`` or ``,``, and
task and instance names hold ``#``: such values are written
percent-encoded (``#`` as ``%23``, ``,`` as ``%2C``, ``%`` as ``%25``), so
``urllib.parse.unquote`` gives them back.

Spans read no clock here and feed nothing back into the planner or the
executor: they only describe what the program is doing.
"""

from __future__ import annotations

import jax

#: prefix of every program span in a profiler trace
PREFIX = "jita."


def _attr(v):
    if isinstance(v, str) and ("#" in v or "," in v or "%" in v):
        return v.replace("%", "%25").replace("#", "%23").replace(",", "%2C")
    return v


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A profiler annotation ``jita.<name>`` with ``attrs`` (str or number)."""
    return jax.profiler.TraceAnnotation(
        PREFIX + name,
        **{k: _attr(v) for k, v in attrs.items()})  # det: ok keyword order is the call's

"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

The kernel wrappers take no ``interpret`` argument; each asks
:func:`interpret` while it traces, so a caller on a TPU always gets the
compiled kernel and a CPU test run the interpreter. Any other backend is
an error rather than a silent fallback.
"""

from __future__ import annotations

import jax


def interpret() -> bool:
    """True on the ``cpu`` backend, False on ``tpu``; raises elsewhere."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas TPU kernels run compiled on 'tpu' or interpreted on "
        f"'cpu', not on {backend!r}")

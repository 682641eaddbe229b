"""Shared test config.

NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
real (single) CPU device; only launch.dryrun (and subprocess-based
distributed tests) request placeholder device counts, in their own
processes.
"""

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)

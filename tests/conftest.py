import os

# Tests run on the single real CPU device (the 512-device override is ONLY
# for launch/dryrun.py, per the assignment).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _fresh_health_counters():
    """Zero the process-global event counters before every test, so
    counter-delta assertions never depend on which tests ran earlier."""
    from repro.health import report
    report.reset_counters()
    yield

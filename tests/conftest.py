"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see the real
single-device CPU; multi-device tests force host devices in their own
subprocess.

The ``hypothesis`` stand-in and the per-test watchdog live in the root
``conftest.py``, so both test trees get them.
"""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")

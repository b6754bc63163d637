"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see the default single
device; multi-device behaviour is exercised via subprocess helpers
(tests/multidev/) so the dry-run's 512-device environment stays isolated."""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration tests")

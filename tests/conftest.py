"""Shared fixtures."""

import jax
import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end test")


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)

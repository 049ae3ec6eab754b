"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see 1 device."""
import jax
import pytest


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")

import jax
import pytest

# Smoke tests and benches run on the single real CPU device; multi-device
# tests set XLA_FLAGS in a subprocess, or run in a CI job that sets it.
jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)

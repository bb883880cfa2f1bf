import jax
import pytest

# Keep default 1-device CPU config — dry-run tests spawn subprocesses with
# their own XLA_FLAGS; nothing here may set device-count flags.
jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)

"""The harness puts the process into the state a configuration states
(``jax_default_matmul_precision``); a test run shares its process with the
repo's other tests, so every test here hands the setting back."""

import jax
import pytest


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


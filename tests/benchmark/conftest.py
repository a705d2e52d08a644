"""The harness puts the process into the state a configuration states
(``jax_default_matmul_precision``); a test run shares its process with the
repo's other tests, so every test here hands the setting back. No test here
is expected to fail."""

import os

import jax
import pytest


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


_HERE = os.path.dirname(os.path.abspath(__file__)) + os.sep


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(items):
    """These are the benchmark's accepted tests, which only a `benchmark` PR
    may change, and none of them is expected to fail: an xfail mark on one,
    from this directory or from outside it, is taken off.
    (``tests/conftest.py:46-62`` still puts a strict xfail on
    ``test_ccd_cell.py``'s manifest test, which passes since PR 38; those
    lines are the next other PR's to delete, and this rule stays.)"""
    for item in items:
        if str(item.path).startswith(_HERE):
            item.own_markers[:] = [m for m in item.own_markers
                                   if m.name != "xfail"]

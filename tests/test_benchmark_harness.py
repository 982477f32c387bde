"""The benchmark's own tests of its harness (``benchmarks/tests``), where
the driver's ``pytest tests/`` collects them: the contract between the
program and the harness breaks here, not on the chip."""

import os

import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_benchmarks")

from benchmarks.tests.test_benchmarks import *  # noqa: E402,F401,F403


@pytest.fixture(scope="module", autouse=True)
def environment_as_found():
    """A rehearsed run sets its worker's operator variables in this
    process (the store's name and size, the model path, the toy
    decoder's seed), as the benchmark's one-run process may. The next
    file on this xdist worker must not inherit them."""
    found = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(found)

"""The benchmark's own tests of its stage readers (``benchmarks/tests``),
a file of their own so that ``--dist loadfile`` gives them a worker."""

import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_stage_metrics")

from benchmarks.tests.test_stage_metrics import *  # noqa: E402,F401,F403

"""Fixtures of the benchmark's own tests."""

import pytest

from tinybench import write_tiny_benchmark


@pytest.fixture
def tiny_root(tmp_path):
    return write_tiny_benchmark(tmp_path)

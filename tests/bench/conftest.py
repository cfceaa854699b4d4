"""Fixtures of the benchmark's tests."""
import pytest
from bench_tiny import make_root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))

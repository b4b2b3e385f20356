"""Fixtures of the benchmark's CPU tests (see ``bench_helpers``)."""
import pytest

from bench_helpers import make_small_tree


@pytest.fixture
def small_tree(tmp_path):
    return make_small_tree(tmp_path)

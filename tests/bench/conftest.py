"""Fixtures for the benchmark's own tests."""
from pathlib import Path

import pytest

from benchtiny import make_tiny


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return make_tiny(tmp_path_factory.mktemp("bench"))

"""Fixtures of the benchmark's own tests (see ``bench_tiny.py``)."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import pytest

from bench_tiny import make_tiny, run


@pytest.fixture
def tiny(tmp_path) -> SimpleNamespace:
    """A tiny copy (``.root``) and ``.run(workload, seed, seconds)``: one run on the CPU."""
    root = make_tiny(tmp_path)
    return SimpleNamespace(root=root, run=functools.partial(run, root))

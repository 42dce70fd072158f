"""Fixtures of the benchmark's own tests (see ``bench_tiny.py``)."""

from __future__ import annotations

import time
from types import SimpleNamespace

import pytest

from bench_tiny import make_tiny


@pytest.fixture
def tiny(tmp_path) -> SimpleNamespace:
    """A tiny copy (``.root``) and ``.run(workload, seed, seconds)``: one run on the CPU."""
    import jax

    from bench import run

    root = make_tiny(tmp_path)

    def run_tiny(workload: str, seed: int = 2**33 + 17, seconds: float = 1.0) -> dict:
        return run.run_cell(root, workload, seed, seconds, False, jax.devices(),
                            time.perf_counter())

    return SimpleNamespace(root=root, run=run_tiny)

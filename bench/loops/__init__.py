"""The general drivers that traffic files name in their ``loop`` key.

Each driver module offers the same five functions:

- ``setup(cell, seed, seconds, devices) -> state``: inputs from the seed,
  the program set up and every shape of the window warmed up;
- ``window(state, seconds) -> Record``: the measured window;
- ``after_trace(state, record) -> dict``: readings a traced run takes
  after its window, outside the profiler;
- ``answers(state, record) -> list[Block]``: what the timed path
  returned, with the inputs it was given, for ``bench/check.py``;
- ``release(state)``: drop every device array the program holds.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Block:
    """Inputs and returned rows of one group of answers (one call, or a serve run).

    ``a``, ``b``, ``c`` are the canonical LP data as the harness made it
    (host NumPy); ``a`` is ``(rows, m, n)``, or one ``(m, n)`` matrix that
    every row shares.  ``status``, ``objective``, ``x`` and ``iterations`` are
    what the program returned for those rows, in the same order.  A row
    the program never returned carries status 0.
    """

    a: np.ndarray
    b: np.ndarray
    c: np.ndarray
    status: np.ndarray
    objective: np.ndarray
    x: np.ndarray
    iterations: np.ndarray


@dataclasses.dataclass
class Record:
    """What one window measured."""

    attempted: int
    end_to_end: Dict[str, float]
    spans: List[tuple]  # (name, start_s, end_s) on the perf_counter clock
    data: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ReadContext:
    """Everything a per-layer metric reader may read."""

    cell: object
    record: Record
    trace: object
    peaks: dict
    extra: dict
    notes: List[str] = dataclasses.field(default_factory=list)


def get(name: str):
    """The driver module ``bench/loops/<name>.py``."""
    return importlib.import_module(f"bench.loops.{name}")


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation) of a non-empty sample."""
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, q)) if values.size else None

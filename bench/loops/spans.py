"""Harness spans: a host-clock interval kept in memory and a profiler annotation.

Each span is written as a ``jax.profiler.TraceAnnotation`` named
``bench.<name>``, so a traced run can tell what the host was doing in
each idle gap of the device, and is kept as ``(name, start, end)`` on
the ``perf_counter`` clock for the metrics the harness times itself.
"""

from __future__ import annotations

import contextlib
import time
from typing import List

import jax

PREFIX = "bench."


class Spans:
    """An in-memory list of spans, written out when the run ends."""

    def __init__(self):
        self.items: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        self.items.append((name, t0, time.perf_counter()))

"""Profiler capture, compile counting, and the reduction from a trace to numbers.

``Profile`` writes one JAX profiler trace of the window to a temporary
directory (under ``TMPDIR``) and deletes it once reduced.  ``reduce``
reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

- device planes are ``/device:TPU:<k>``; their ``XLA Ops`` line holds one
  event per operation run on that chip, named by its HLO text, of which
  the instruction's name is kept (``_solve_jit.1``, ``fusion.3``);
- host planes hold the harness's spans (``bench.<name>`` annotations).

The traced window runs from the first harness span's start to the last
one's end.  Busy time is the union of the operation intervals inside it,
per chip; the idle gaps are the holes in that union, each labelled by
the innermost harness span the host was in at the gap's middle ("no
span" where the harness was between spans, e.g. waiting for arrivals).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


class CompileCounter:
    """Counts compilations and persistent-cache loads through ``jax.monitoring``."""

    EVENTS = {
        "/jax/core/compile/backend_compile_duration": "backend_compiles",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
    }

    def __init__(self):
        import jax

        self.counts = {"backend_compiles": 0, "cache_loads": 0, "traces": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_loads"] += 1

    def reset(self) -> None:
        for k in self.counts:
            self.counts[k] = 0

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


class Profile:
    """``with Profile() as p:`` traces the block; ``p.path`` is the xplane file."""

    def __enter__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        self.path = found[0] if found else None
        return False

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Events:
    """One trace's events as arrays of nanoseconds: per chip ops, and host spans."""

    ops: Dict[str, List[Tuple[str, int, int]]]  # plane -> (name, start, end)
    spans: List[Tuple[str, int, int]]  # (name without prefix, start, end)


def op_name(text: str) -> str:
    """The HLO instruction name of a device event: ``%fusion.3 = f32[...] ...`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> Events:
    """Device operations and harness spans of an ``.xplane.pb`` file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Events(ops, spans)


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals of ``(start, end)`` pairs, clipped to ``[lo, hi]``."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def covered(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


@dataclasses.dataclass
class Reduced:
    """A trace reduced to what the per-layer readers and ``device`` need."""

    events: Events
    lo: int
    hi: int
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy(self, plane: str) -> List[Tuple[float, float]]:
        return union(((s, e) for _, s, e in self.events.ops.get(plane, [])), self.lo, self.hi)

    @property
    def planes(self) -> List[str]:
        return sorted(self.events.ops)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips used."""
        total = sum(covered(self.busy(p)) for p in self.planes)
        return total / 1e9 / max(self.n_devices, 1)

    def op_seconds(self, pattern: str) -> Optional[float]:
        """Summed device time of the ops whose name matches ``pattern`` (all chips)."""
        rx = re.compile(pattern)
        hits = [min(e, self.hi) - max(s, self.lo) for p in self.planes
                for n, s, e in self.events.ops[p] if rx.search(n)]
        hits = [h for h in hits if h > 0]
        return float(sum(hits)) / 1e9 if hits else None

    def idle_within(self, span: str) -> Optional[float]:
        """Seconds, averaged over chips, in which the chip is idle and the host is in ``span``.

        None when the host never entered ``span`` in the window.
        """
        spans = union(((s, e) for n, s, e in self.events.spans if n == span), self.lo, self.hi)
        if not spans:
            return None
        total = sum(_overlap(union(self.gaps(p), self.lo, self.hi), spans) for p in self.planes)
        return total / 1e9 / max(self.n_devices, 1)

    def gaps(self, plane: str) -> List[Tuple[float, float]]:
        out, t = [], self.lo
        for s, e in self.busy(plane):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.hi > t:
            out.append((t, self.hi))
        return out

    def host_label(self, t: float) -> str:
        """The innermost harness span around time ``t``, or ``"no span"``."""
        best = None
        for name, s, e in self.events.spans:
            if s <= t <= e and (best is None or s > best[1]):
                best = (name, s)
        return best[0] if best else "no span"

    def breakdown(self) -> dict:
        """Top device ops by time, and the longest idle gaps by host span."""
        per_op: Dict[str, float] = {}
        for p in self.planes:
            for n, s, e in self.events.ops[p]:
                d = min(e, self.hi) - max(s, self.lo)
                if d > 0:
                    per_op[n] = per_op.get(n, 0.0) + d / 1e9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(((e - s, p, s, e) for p in self.planes for s, e in self.gaps(p)),
                      reverse=True)[:10]
        gaps = [[f"{p[len('/device:'):]} {self.host_label((s + e) / 2)}", d / 1e9]
                for d, p, s, e in gaps]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": gaps}


def _overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(path: str, n_devices: int) -> Reduced:
    """Reduce a trace to its window: from the first harness span to the last."""
    events = load(path)
    if not events.spans:
        raise RuntimeError(f"no harness span in the trace {path}")
    lo = min(s for _, s, _ in events.spans)
    hi = max(e for _, _, e in events.spans)
    if not events.ops:
        raise RuntimeError(f"no device operation in the trace {path}")
    return Reduced(events, lo, hi, n_devices)


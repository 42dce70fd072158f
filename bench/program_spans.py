"""The program's own spans, placed on the clock of the reduced device trace.

The program records its spans in memory (``repro.runtime.trace``) with
times on ``time.perf_counter``; the reduced trace (``bench/tracing.py``)
is in the profiler's nanoseconds.  The harness's spans are on both: as
``(name, start_s, end_s)`` in ``ctx.record.spans`` and as
``(name, start_ns, end_ns)`` in ``ctx.trace.events.spans``.  Pairing
them by name, in order, gives the offset from one clock to the other:
the median over the pairs' starts and ends.

A program without ``repro.runtime.trace``, or a run in which the program
recorded no span, gives None, and the readers report nothing.
"""

from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import tracing


def recorded() -> Optional[list]:
    """The program's in-memory spans, or None where the program has none."""
    try:
        from repro.runtime import trace
    except ImportError:
        return None
    return trace.spans() or None


def offset_ns(record_spans, trace_spans) -> Optional[float]:
    """Trace nanoseconds minus ``perf_counter`` nanoseconds, over the harness's spans."""
    mine, theirs = defaultdict(list), defaultdict(list)
    for name, s, e in record_spans:
        mine[name].append((s, e))
    for name, s, e in trace_spans:
        theirs[name].append((s, e))
    diffs = []
    for name, pairs in mine.items():
        for (s, e), (ts, te) in zip(sorted(pairs), sorted(theirs.get(name, ()))):
            diffs += [ts - s * 1e9, te - e * 1e9]
    return statistics.median(diffs) if diffs else None


def placed(ctx) -> Optional[List[Tuple[str, float, float, Optional[int], dict]]]:
    """The program's closed spans as ``(name, start_ns, end_ns, parent, attrs)``.

    Times are on the trace's clock; ``parent`` indexes this same list.
    None when there is no trace, no program span, or no harness span to
    pair.
    """
    spans = recorded()
    if ctx.trace is None or not spans:
        return None
    off = offset_ns(ctx.record.spans, ctx.trace.events.spans)
    if off is None:
        return None
    return [(s.name, s.t0 * 1e9 + off, None if s.t1 is None else s.t1 * 1e9 + off,
             s.parent, s.attrs) for s in spans]


def in_window(ctx, name: str) -> Optional[List[Tuple[float, float, dict]]]:
    """``(start_ns, end_ns, attrs)`` of the closed spans ``name`` inside the traced window."""
    spans = placed(ctx)
    if spans is None:
        return None
    lo, hi = ctx.trace.lo, ctx.trace.hi
    return [(s, e, a) for n, s, e, _, a in spans
            if n == name and e is not None and lo <= s and e <= hi]


def _gaps(trace) -> list:
    """Each chip's idle gaps in the window."""
    return [tracing.union(trace.gaps(p), trace.lo, trace.hi) for p in trace.planes]


def _idle_over(trace, gaps, intervals) -> float:
    """Seconds, averaged over chips, in which the chip is idle inside ``intervals``."""
    cover = tracing.union(intervals, trace.lo, trace.hi)
    total = sum(tracing._overlap(g, cover) for g in gaps)
    return total / 1e9 / max(trace.n_devices, 1)


def idle_within(ctx, name: str) -> Optional[float]:
    """Seconds, averaged over chips, in which the chip is idle while the host is in ``name``.

    None when no such span closed inside the window.
    """
    spans = in_window(ctx, name)
    if not spans:
        return None
    return _idle_over(ctx.trace, _gaps(ctx.trace), [(s, e) for s, e, _ in spans])


def idle_by_span(ctx) -> Optional[Dict[str, float]]:
    """Idle seconds, averaged over chips, by the innermost program span the host was in.

    A span's own time is its interval less its children's; the chip's
    idle time inside it is charged to its name.  Time in no program span
    is left out.
    """
    spans = placed(ctx)
    if spans is None:
        return None
    children: Dict[int, list] = defaultdict(list)
    for _, s, e, parent, _ in spans:
        if parent is not None and e is not None:
            children[parent].append((s, e))
    gaps = _gaps(ctx.trace)
    out: Dict[str, float] = defaultdict(float)
    for i, (name, s, e, _, _) in enumerate(spans):
        if e is None:
            continue
        own, t = [], s
        for cs, ce in tracing.union(children[i], s, e):
            if cs > t:
                own.append((t, cs))
            t = max(t, ce)
        if e > t:
            own.append((t, e))
        out[name] += _idle_over(ctx.trace, gaps, own)
    return dict(out)

"""Program spans (``runtime/trace.py``): off without a profiler, the span tree of a
chunked solve, the shared clock with the profiler's trace, and host-sync counts.

Host syncs are what ROADMAP Speed 5 ("one host sync per round") is judged
by: a plain solve makes none, ``stats=`` adds one per chunk, and a
compaction solve adds one status read per round after the first.
"""

from __future__ import annotations

import glob
import os
import statistics
import time

import jax
import numpy as np
import pytest

import repro
from repro import SolveOptions, SolveStats
from repro.core.lp import random_lp_batch
from repro.runtime import trace
from repro.serve.engine import LPEngine
from repro.serve.loadgen import lp_request_mix

B, M, N = 12, 6, 5
CHUNK = 4  # 3 chunks


def _batch(seed=0, batch=B):
    rng = np.random.default_rng(seed)
    a = rng.uniform(-1.0, 1.0, (batch, M, N)).astype(np.float32)
    b = rng.uniform(1.0, 2.0, (batch, M)).astype(np.float32)
    c = rng.uniform(0.1, 1.0, (batch, N)).astype(np.float32)
    return repro.LPBatch(a, b, c)


def _traced(fn, tmp_path):
    """Run ``fn`` under the profiler; returns (its result, in-memory spans, xplane path)."""
    trace.clear()
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    return out, trace.spans(), found[0] if found else None


def _solve(batch, options, stats=None):
    sol = repro.solve(batch, options, stats=stats)
    np.asarray(sol.status)
    return sol


@pytest.fixture(scope="module")
def warm():
    """Compile the shapes once, outside any trace."""
    opts = SolveOptions(backend="xla", chunk_size=CHUNK)
    _solve(_batch(), opts)
    return opts


def test_is_enabled_flips_under_the_profiler(tmp_path):
    # jaxlib's TraceMe.is_enabled is what keeps spans free when off; an
    # upgrade that drops or breaks it must fail here, not turn spans off.
    assert callable(jax.profiler.TraceAnnotation.is_enabled)
    assert not trace.recording()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.recording()
    finally:
        jax.profiler.stop_trace()
    assert not trace.recording()


def test_span_off_records_nothing(warm):
    trace.clear()
    with trace.span("anything", rows=3) as sp:
        sp.set(more=1)
    assert trace.span("other") is trace.span("third")  # the shared no-op
    _solve(_batch(), warm, stats=SolveStats())
    assert trace.spans() == []


def test_chunked_solve_records_the_span_tree(warm, tmp_path):
    _, spans, _ = _traced(lambda: _solve(_batch(), warm), tmp_path)
    assert all(s.t1 is not None and s.t1 >= s.t0 for s in spans)
    roots = [i for i, s in enumerate(spans) if s.parent is None]
    assert [spans[i].name for i in roots] == ["solve"]
    (root,) = roots
    assert spans[root].attrs == {"kind": "batch"}
    children = [s for s in spans if s.parent == root]
    assert [s.name for s in children] == ["dispatch.resolve", "dispatch.round"]
    assert children[0].attrs == {"backend": "xla"}
    rnd = spans.index(children[1])
    assert children[1].attrs["round"] == 0 and children[1].attrs["rows"] == B
    inner = [s for s in spans if s.parent == rnd]
    names = [s.name for s in inner]
    assert names.count("dispatch.stage") == 3 and names.count("dispatch.enqueue") == 3
    assert names[-1] == "dispatch.concat" and "dispatch.sync" not in names
    row_bytes = 4 * (M * N + M + N)
    stages = [s for s in inner if s.name == "dispatch.stage"]
    assert [s.attrs for s in stages] == [{"chunk": k, "bytes": CHUNK * row_bytes}
                                         for k in range(3)]
    assert [s.attrs["chunk"] for s in inner if s.name == "dispatch.enqueue"] == [0, 1, 2]
    # Every child lies inside its parent.
    for s in spans:
        if s.parent is not None:
            p = spans[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1


def test_profiler_events_and_in_memory_spans_share_a_clock(warm, tmp_path):
    """The ``repro.*`` host events of the xplane file start where the in-memory
    spans say, once placed through harness-style anchor spans (within 100 us)."""
    anchors = []

    def run():
        for k in range(4):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.anchor"):
                _solve(_batch(seed=k), warm)
            anchors.append((t0, time.perf_counter()))

    _, spans, path = _traced(run, tmp_path)
    assert path is not None
    data = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench.") or e.name.startswith(trace.PREFIX):
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    marks = sorted(events["bench.anchor"])
    assert len(marks) == len(anchors) == 4
    offset = statistics.median(
        [ns - s * 1e9 for (s, e), (ns_s, ns_e) in zip(anchors, marks)
         for s, ns in ((s, ns_s), (e, ns_e))])
    names = sorted({s.name for s in spans})
    assert {"solve", "dispatch.round", "dispatch.stage", "dispatch.enqueue"} <= set(names)
    for name in names:
        mine = sorted(s.t0 * 1e9 + offset for s in spans if s.name == name)
        theirs = sorted(s for s, _ in events[trace.PREFIX + name])
        assert len(mine) == len(theirs), name
        worst = max(abs(a - b) for a, b in zip(mine, theirs))
        assert worst < 100_000, (name, worst)


def test_plain_solve_makes_no_host_sync_and_stats_one_per_chunk(warm, tmp_path):
    _, spans, _ = _traced(lambda: _solve(_batch(), warm), tmp_path)
    assert not [s for s in spans if s.name == "dispatch.sync"]

    stats = SolveStats()
    _, spans, _ = _traced(lambda: _solve(_batch(), warm, stats=stats), tmp_path / "2")
    syncs = [s for s in spans if s.name == "dispatch.sync"]
    assert stats.host_syncs == len(syncs) == 3 == stats.rounds
    assert {s.attrs["site"] for s in syncs} == {"stats.record"}
    assert stats.bytes_staged == B * 4 * (M * N + M + N)


def test_counters_are_kept_without_a_profiler(warm):
    stats = SolveStats()
    _solve(_batch(), warm, stats=stats)
    assert stats.host_syncs == 3
    assert stats.bytes_staged == B * 4 * (M * N + M + N)


def test_stats_reads_phase_rewrites_in_the_chunk_sync(tmp_path):
    """The Pallas kernel's ``phase_rewrites`` come back with the iteration
    counts: still one ``stats.record`` sync per chunk."""
    opts = SolveOptions(backend="pallas", chunk_size=CHUNK)
    made = random_lp_batch(np.random.default_rng(5), B, 2 * N, N, feasible_start=False)
    batch = repro.LPBatch(*(np.asarray(v) for v in (made.a, made.b, made.c)))
    _solve(batch, opts, stats=SolveStats())  # warm
    stats = SolveStats()
    _, spans, _ = _traced(lambda: _solve(batch, opts, stats=stats), tmp_path)
    syncs = [s for s in spans if s.name == "dispatch.sync"]
    assert stats.host_syncs == len(syncs) == 3 == stats.rounds
    assert {s.attrs["site"] for s in syncs} == {"stats.record"}
    assert 3 <= stats.phase_rewrites <= B


def test_compaction_adds_one_status_sync_per_round_after_the_first(tmp_path):
    # A cap of 8 pivots with rounds of 2: caps 2, 4, 8; LPs still at the
    # iteration limit after every round keep all three rounds running.
    opts = SolveOptions(backend="xla", compaction="every_k", compact_every=2, max_iters=8,
                        chunk_size=CHUNK)
    batch = _batch(seed=3)
    _solve(batch, opts, stats=SolveStats())  # warm
    stats = SolveStats()
    _, spans, _ = _traced(lambda: _solve(batch, opts, stats=stats), tmp_path)
    rounds = [s for s in spans if s.name == "dispatch.round"]
    status = [s for s in spans if s.name == "dispatch.sync" and s.attrs["site"] == "round_status"]
    assert [s.attrs["round"] for s in rounds] == [0, 1, 2]
    assert [s.attrs["cap"] for s in rounds] == [2, 4, 8]
    assert len(status) == len(rounds) - 1
    # record() reads every chunk that holds a real row (stats.rounds); a
    # chunk of padding replicas alone is neither read nor counted.
    assert stats.host_syncs == stats.rounds + len(rounds) - 1

    # Without stats= the status reads are the only syncs.
    _, spans, _ = _traced(lambda: _solve(batch, opts), tmp_path / "2")
    syncs = [s for s in spans if s.name == "dispatch.sync"]
    assert len(syncs) == len([s for s in spans if s.name == "dispatch.round"]) - 1


def test_serve_tickets_have_queued_and_inflight_intervals(tmp_path):
    make = lp_request_mix([(4, 6), (6, 4)], seed=11)
    problems = [make(i) for i in range(6)]

    def serve():
        eng = LPEngine(SolveOptions(backend="xla"), flush_every=1 << 30, step_iters=4)
        tickets = []
        for p in problems:
            tickets.append(eng.submit(p))
            eng.step()
        while eng.inflight_count or eng.pending_count:
            eng.step()
        return tickets, eng

    serve()  # warm
    (tickets, eng), spans, _ = _traced(serve, tmp_path)
    assert eng.stats.host_syncs == len([s for s in spans if s.name == "dispatch.sync"])
    assert eng.stats.host_syncs > 0
    for name in ("serve.queued", "serve.inflight"):
        got = [s for s in spans if s.name == name]
        assert sorted(s.attrs["ticket"] for s in got) == tickets
    for t in tickets:
        (q,) = [s for s in spans if s.name == "serve.queued" and s.attrs["ticket"] == t]
        (f,) = [s for s in spans if s.name == "serve.inflight" and s.attrs["ticket"] == t]
        assert 0.0 <= q.t1 - q.t0 and q.t1 == f.t0 and f.t1 >= f.t0
    submits = [s for s in spans if s.name == "serve.submit"]
    assert [s.attrs["ticket"] for s in submits] == tickets
    names = {s.name for s in spans}
    assert {"serve.step", "serve.admit", "serve.advance", "serve.retire"} <= names
    assert all("group" in s.attrs for s in spans
               if s.name in ("serve.admit", "serve.advance", "serve.retire"))


def test_spans_opened_before_clear_are_dropped(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("outer"):
            trace.clear()
            with trace.span("inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    spans = trace.spans()
    assert [(s.name, s.parent) for s in spans] == [("inner", None)]

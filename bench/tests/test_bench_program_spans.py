"""Program spans on the trace's clock, and the readers built on them: known numbers on
hand-made events, the BENCHMARK.json entries, and a trace recorded on the chip."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import cells, loops, program_spans, tracing

REPO = Path(__file__).resolve().parents[2]
METRICS = REPO / "bench" / "metrics"
NEW = ("dispatch.stage_idle_share", "dispatch.stage_gb_per_s")

MS = 1_000_000  # nanoseconds
OFFSET = 7_300_000_123.0  # trace ns minus perf_counter ns, made up
BASE = 1000.0  # perf_counter seconds at the window's start


def _reader(name):
    return cells._reader(METRICS / f"{name}.py")


def _span(name, t0_ms, t1_ms, parent=None, **attrs):
    from repro.runtime import trace

    return trace.Span(name, BASE + t0_ms / 1e3, BASE + t1_ms / 1e3, parent, attrs)


def _ctx(monkeypatch, program, n_devices=1):
    """A window of 100 ms: harness spans on both clocks, chip busy 10-40, 60-70, 80-90 ms."""
    from repro.runtime import trace

    harness = [("solve", 0, 95), ("result", 95, 100)]
    record = loops.Record(attempted=0, end_to_end={},
                          spans=[(n, BASE + s / 1e3, BASE + e / 1e3) for n, s, e in harness])
    lo = BASE * 1e9 + OFFSET
    events = tracing.Events(
        ops={f"/device:TPU:{k}": [("_solve_jit.1", lo + 10 * MS, lo + 40 * MS),
                                  ("fusion.1", lo + 60 * MS, lo + 70 * MS),
                                  ("_solve_jit.1", lo + 80 * MS, lo + 90 * MS)]
             for k in range(n_devices)},
        spans=[(n, lo + s * MS, lo + e * MS) for n, s, e in harness])
    reduced = tracing.Reduced(events, lo, lo + 100 * MS, n_devices)
    monkeypatch.setattr(trace, "spans", lambda: list(program))
    return loops.ReadContext(cell=None, record=record, trace=reduced, peaks={}, extra={})


PROGRAM = [
    _span("solve", 0, 95, kind="batch"),                       # 0
    _span("dispatch.resolve", 0.5, 1.5, 0, backend="pallas"),  # 1
    _span("dispatch.round", 2, 94, 0, round=0, rows=8, cap=9),  # 2
    _span("dispatch.stage", 2, 12, 2, chunk=0, bytes=1_000_000),
    _span("dispatch.enqueue", 12, 13, 2, chunk=0),
    _span("dispatch.stage", 45, 55, 2, chunk=1, bytes=2_000_000),
    _span("dispatch.enqueue", 55, 56, 2, chunk=1),
    _span("dispatch.concat", 90, 93, 2),
    _span("dispatch.stage", 96, 99, None, chunk=0, bytes=5),  # outside the solve span
]


def test_offset_is_the_median_over_the_harness_spans():
    record = [("solve", 1.0, 2.0), ("result", 2.0, 2.5), ("solve", 3.0, 4.0)]
    trace_spans = [(n, s * 1e9 + 42.0, e * 1e9 + 42.0) for n, s, e in record]
    trace_spans[1] = ("result", 2.0e9 + 42.0, 2.5e9 + 9e6)  # one outlier pair end
    assert program_spans.offset_ns(record, trace_spans) == pytest.approx(42.0)
    assert program_spans.offset_ns(record, []) is None


def test_spans_are_placed_on_the_trace_clock(monkeypatch):
    ctx = _ctx(monkeypatch, PROGRAM)
    placed = program_spans.placed(ctx)
    lo = ctx.trace.lo
    assert placed[3][0] == "dispatch.stage" and placed[3][3] == 2
    assert placed[3][1] - lo == pytest.approx(2 * MS, abs=1e3)
    assert placed[3][2] - lo == pytest.approx(12 * MS, abs=1e3)
    stages = program_spans.in_window(ctx, "dispatch.stage")
    assert [a["chunk"] for _, _, a in stages] == [0, 1, 0]


def test_stage_idle_share_and_rate_on_hand_made_events(monkeypatch):
    ctx = _ctx(monkeypatch, PROGRAM)
    # Gaps 0-10, 40-60, 70-80, 90-100 ms; stages 2-12, 45-55, 96-99 ms:
    # idle 8 + 10 + 3 = 21 ms of 100.
    assert _reader("dispatch.stage_idle_share")(ctx) == pytest.approx(21.0, abs=1e-4)
    # The harness's solve span, 0-95 ms: idle 10 + 20 + 10 + 5 ms.
    assert _reader("dispatch.solve_idle_share")(ctx) == pytest.approx(45.0, abs=1e-4)
    # 3,000,005 bytes over 23 ms.
    assert _reader("dispatch.stage_gb_per_s")(ctx) == pytest.approx(
        3_000_005 / 0.023 / 1e9, rel=1e-6)
    split = program_spans.idle_by_span(ctx)
    # Own time, less the children's, intersected with the gaps: solve 0-0.5,
    # 1.5-2, 94-95; round 40-45, 56-60, 70-80, 93-94; enqueue 55-56.
    assert split == pytest.approx({
        "solve": 0.002, "dispatch.resolve": 0.001, "dispatch.round": 0.020,
        "dispatch.stage": 0.021, "dispatch.enqueue": 0.001, "dispatch.concat": 0.003},
        abs=1e-9)
    # With the result span's 2 ms outside every program span, that is all idle.
    assert sum(split.values()) + 0.002 == pytest.approx(0.050, abs=1e-9)
    assert any(n.startswith("idle by program span") for n in ctx.notes)


def test_stage_idle_share_is_averaged_over_chips(monkeypatch):
    ctx = _ctx(monkeypatch, PROGRAM, n_devices=2)
    assert _reader("dispatch.stage_idle_share")(ctx) == pytest.approx(21.0, abs=1e-4)


def test_queue_p95_reads_the_queued_intervals(monkeypatch):
    queued = [_span("serve.queued", k, k + 0.5 * (k + 1), None, ticket=k) for k in range(10)]
    inflight = [_span("serve.inflight", 50, 60, None, ticket=k) for k in range(10)]
    ctx = _ctx(monkeypatch, queued + inflight)
    expect = np.percentile([0.5 * (k + 1) for k in range(10)], 95)
    assert _reader("serve.queue_ms_p95")(ctx) == pytest.approx(expect, rel=1e-6)
    assert _reader("dispatch.stage_idle_share")(ctx) is None


@pytest.mark.parametrize("name", NEW + ("serve.queue_ms_p95",))
def test_readers_report_nothing_without_program_spans(monkeypatch, name):
    ctx = _ctx(monkeypatch, [])
    assert _reader(name)(ctx) is None
    # A program without repro.runtime.trace (an older checkout): None, no error.
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "trace")
    monkeypatch.setitem(sys.modules, "repro.runtime.trace", None)
    assert program_spans.recorded() is None
    assert _reader(name)(ctx) is None


def test_the_new_entries_keep_the_contract():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW)
    for name, unit, better in (("dispatch.stage_idle_share", "%", "lower"),
                               ("dispatch.stage_gb_per_s", "GB/s", "higher")):
        assert entries[name] == {
            "name": name, "unit": unit, "better": better, "source": "program_span",
            "layer": "dispatch", "moves": "lps_per_s",
            "workloads": ["fig8_m100.batch", "fig9_m200.batch"]}
    assert "serve.queue_ms_p95" not in entries and (METRICS / "serve.queue_ms_p95.py").is_file()
    for workload in entries[NEW[0]]["workloads"]:
        cell = cells.load(REPO, workload)
        assert set(NEW) <= set(cell.readers)


#: One traced call of ``fig9_m200.batch`` on one TPU v5e (2,500 two-phase LPs at
#: m=n=200, seed 3000000913), with the program's in-memory spans and the
#: harness's spans of the same window.
CHIP_TRACE = Path(__file__).with_name("data") / "fig9_m200_program_trace.xplane.pb"
CHIP_SPANS = Path(__file__).with_name("data") / "fig9_m200_program_spans.json"


def test_readers_on_a_trace_recorded_on_the_chip(monkeypatch):
    import jax

    from repro.runtime import trace

    saved = json.loads(CHIP_SPANS.read_text())
    program = [trace.Span(*s) for s in saved["program_spans"]]
    reduced = tracing.reduce(str(CHIP_TRACE), 1)
    record = loops.Record(attempted=0, end_to_end={},
                          spans=[tuple(s) for s in saved["record_spans"]])
    monkeypatch.setattr(trace, "spans", lambda: list(program))
    ctx = loops.ReadContext(cell=None, record=record, trace=reduced, peaks={}, extra={})
    assert reduced.window_s == pytest.approx(5.250905293, abs=1e-9)
    stage = _reader("dispatch.stage_idle_share")(ctx)
    solve = _reader("dispatch.solve_idle_share")(ctx)
    assert stage == pytest.approx(0.14617229928393607, rel=1e-9)
    assert solve == pytest.approx(0.23298784337834372, rel=1e-9)
    assert stage <= solve
    assert _reader("dispatch.stage_gb_per_s")(ctx) == pytest.approx(52.6359058437451, rel=1e-9)
    assert [a["bytes"] for _, _, a in program_spans.in_window(ctx, "dispatch.stage")] == [
        404_000_000]

    # The profiler's own repro.* events of the file sit where the in-memory spans say.
    events = {}
    for plane in jax.profiler.ProfileData.from_file(str(CHIP_TRACE)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace.PREFIX):
                        events.setdefault(e.name[len(trace.PREFIX):], []).append(e.start_ns)
    placed = program_spans.placed(ctx)
    assert sorted(events) == sorted({n for n, *_ in placed})
    for name, start, *_ in placed:
        assert min(abs(start - t) for t in events[name]) < 100_000, name

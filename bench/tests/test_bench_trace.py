"""The trace reduction gives known numbers: on hand-made events, and on a trace recorded on the chip."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import tracing

#: A traced window of ``fig9_m200.batch`` on one TPU v5e (4 calls of 2,500
#: two-phase LPs at m=n=200), recorded by ``bench/tracing.py:Profile``.
CHIP_TRACE = Path(__file__).with_name("data") / "fig9_m200_trace.xplane.pb"

MS = 1_000_000  # nanoseconds


def _reduced(ops, spans, n_devices=1):
    events = tracing.Events(ops=ops, spans=spans)
    return tracing.Reduced(events, min(s for _, s, _ in spans), max(e for _, _, e in spans),
                           n_devices)


def test_union_merges_and_clips():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (9, 20)], 1, 10) == [(1, 3), (5, 7), (9, 10)]
    assert tracing.covered([(1, 3), (5, 7)]) == 4


def test_busy_idle_kernel_and_gaps_on_hand_made_events():
    ops = {"/device:TPU:0": [("kernel_a", 10 * MS, 40 * MS), ("copy-start", 35 * MS, 50 * MS),
                             ("fusion.1", 60 * MS, 70 * MS), ("kernel_a", 80 * MS, 90 * MS)]}
    spans = [("solve", 0, 95 * MS), ("result", 95 * MS, 100 * MS), ("submit", 52 * MS, 58 * MS)]
    r = _reduced(ops, spans)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.06)  # 10-50, 60-70, 80-90
    assert r.op_seconds("kernel_a") == pytest.approx(0.04)
    assert r.op_seconds("nothing") is None
    assert r.idle_within("submit") == pytest.approx(0.006)  # 52-58 lies in the 50-60 gap
    assert r.idle_within("solve") == pytest.approx(0.035)  # 0-10, 50-60, 70-80, 90-95
    assert r.idle_within("step") is None
    gaps = r.breakdown()["idle_gaps"]  # 0-10, 50-60, 70-80, 90-100
    assert sorted(g[0] for g in gaps) == ["TPU:0 result", "TPU:0 solve", "TPU:0 solve",
                                          "TPU:0 submit"]
    assert all(g[1] == pytest.approx(0.010) for g in gaps)
    ops_top = r.breakdown()["device_ops"]
    assert ops_top[0] == ["kernel_a", pytest.approx(0.04)]


def test_busy_is_averaged_over_chips():
    ops = {"/device:TPU:0": [("k", 0, 10 * MS)], "/device:TPU:1": [("k", 0, 30 * MS)]}
    r = _reduced(ops, [("solve", 0, 40 * MS)], n_devices=2)
    assert r.busy_s == pytest.approx(0.02)
    assert r.op_seconds("k") == pytest.approx(0.04)


def test_reduction_of_a_trace_recorded_on_the_chip():
    from importlib.util import module_from_spec, spec_from_file_location

    r = tracing.reduce(str(CHIP_TRACE), 1)
    assert r.planes == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(21.002625062, abs=1e-9)
    assert r.busy_s == pytest.approx(20.822670433, abs=1e-9)
    assert sorted({n for n, _, _ in r.events.spans}) == ["result", "solve"]
    assert len(r.events.spans) == 8  # four calls, each a solve and a result span
    spec = spec_from_file_location(
        "roofline", CHIP_TRACE.parents[2] / "metrics" / "kernel.tableau.roofline_share.py")
    roofline = module_from_spec(spec)
    spec.loader.exec_module(roofline)
    assert r.op_seconds(roofline.KERNEL) == pytest.approx(20.745258623, abs=1e-9)
    assert r.idle_within("solve") == pytest.approx(0.044443777, abs=1e-9)
    out = r.breakdown()
    assert out["device_ops"][0] == ["_solve_jit.1", pytest.approx(20.745258623, abs=1e-9)]
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    assert out["idle_gaps"][0] == ["TPU:0 result", pytest.approx(0.032823303, abs=1e-9)]

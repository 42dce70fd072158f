"""Each traffic mix runs at a tiny size on the CPU through the harness's own functions.

The comparison passes there, fails under the lower-precision control,
and fails when the timed path is broken underneath: a step that returns
its state unchanged, half of a batch left out, an answer altered where
it is produced.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench_tiny import SERVED_CELL, SERVED_METRICS, broken_round, control_sizes

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
BENCH["workloads"].append(SERVED_CELL)
BENCH["end_to_end"] += SERVED_METRICS
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_is_correct_on_the_cpu(tiny, workload):
    res = tiny.run(workload)
    assert res["correct"], res["checked"]
    assert res["failed"] == 0 and res["attempted"] > 0
    cell = [w for w in BENCH["workloads"] if w["name"] == workload][0]
    expected = {m["name"] for m in BENCH["end_to_end"]
                if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == expected
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checked"
    assert cell["name"] == workload


@pytest.mark.parametrize("workload", WORKLOADS)
def test_the_bfloat16_control_fails_the_comparison(tiny, workload):
    """The reference in the program's place, in bfloat16, breaks a limit; float64 does not."""
    from bench import cells, control

    cell = cells.load(tiny.root, workload)
    cell.config.update(control_sizes(cell.config))
    for seed in (2**33 + 3, 5):
        assert control.readings(cell, seed, 1.0)["fails"], seed
        assert not control.readings(cell, seed, 1.0, precision="float64")["fails"], seed


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, workload, kind):
    from repro.core import dispatch

    monkeypatch.setattr(dispatch, "dispatch_round", broken_round(kind))
    res = tiny.run(workload)
    assert not res["correct"], (kind, res["checked"])

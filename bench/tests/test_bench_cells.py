"""Every cell of BENCHMARK.json resolves by name, and the file keeps the contract's shape."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench import cells, loops

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_entries_have_the_contract_keys_and_names():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (REPO / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names = [x["name"] for x in metrics + BENCH["configs"] + BENCH["workloads"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


def test_config_files_hold_what_the_harness_reads():
    for c in BENCH["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for key in ("generator", "m", "n", "batch", "options", "check_sample", "limits"):
            assert key in cfg, (c["name"], key)
        assert cfg["limits"]["unanswered"] == 0 and cfg["limits"]["status_mismatch"] == 0
        for key in cfg["reduced"]:
            assert key in cfg.get("published", {}), key


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves_config_traffic_and_metrics_by_name(workload):
    cell = cells.load(REPO, workload)
    assert cell.traffic["loop"] in ("closed", "open")
    driver = loops.get(cell.traffic["loop"])
    for fn in ("setup", "window", "after_trace", "answers", "release"):
        assert callable(getattr(driver, fn))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(cell.readers[m["name"]])
    assert (REPO / "bench" / "traffic" / f"{BENCH['workloads'][WORKLOADS.index(workload)]['traffic']}.json").is_file()


def test_unknown_workload_and_missing_files_raise(tmp_path):
    with pytest.raises(KeyError):
        cells.load(REPO, "no_such.cell")
    bench = dict(BENCH, workloads=[dict(BENCH["workloads"][0], traffic="no_such_mix")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench").symlink_to(REPO / "bench")
    with pytest.raises(FileNotFoundError):
        cells.load(tmp_path, bench["workloads"][0]["name"])
    cfg = json.loads((REPO / BENCH["configs"][0]["file"]).read_text())
    (tmp_path / "no_class.json").write_text(json.dumps(dict(cfg, generator="no_such_class")))
    bench = dict(BENCH, configs=[dict(BENCH["configs"][0], file="no_class.json")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(FileNotFoundError, match="no_such_class"):
        cells.load(tmp_path, bench["workloads"][0]["name"])


def test_a_cell_added_as_data_files_alone_is_picked_up(tiny):
    """A new configuration file, a new traffic file and new entries: no code edited."""
    root = tiny.root
    cfg = json.loads((root / "bench/configs/paper_feasible_m100.json").read_text())
    cfg.update(name="dummy_m7", m=7, n=4, batch=48)
    (root / "bench/configs/dummy_m7.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/closed_pool3.json").write_text(
        json.dumps({"loop": "closed", "pool": 3, "per_chip": False}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy_m7", "source": "https://arxiv.org/abs/1609.08114",
                             "file": "bench/configs/dummy_m7.json", "reduced": [],
                             "why": "dummy"})
    bench["workloads"].append({"name": "dummy_m7.batch", "config": "dummy_m7",
                               "traffic": "closed_pool3", "chips": 1, "why": "dummy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "fig8_m100.batch" in m.get("workloads", []):
            m["workloads"].append("dummy_m7.batch")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = tiny.run("dummy_m7.batch")
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"lps_per_s", "setup_s"}
    assert res["attempted"] % 48 == 0

"""LP classes resolve by name from ``bench/inputs/<generator>.py``.

The two paper classes draw what they drew before they moved there (the
digests were taken from the draws of ``bench/lpgen.py`` before the move,
on the CPU).  A block whose LPs share one ``A`` reads in ``bench/check.py``
as the same rows with ``A`` repeated.  A new class, with one ``A`` for
every LP, is added to a tiny copy of the benchmark as files and entries
alone, and runs, checks and fails as the paper classes do.
"""

from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import broken_round, control_sizes, make_tiny, run

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SEED = 2**33 + 17

DIGESTS = {
    ("feasible", 6, 5, 0): "1ca3eb426e138982f60ce556736f8044934ad16b0e7730124135ef22f58af6ef",
    ("feasible", 6, 5, 1): "56a36dbb75148aec58950058f66a0ba3314c58074ca009a2333c0f01c3d9cb7d",
    ("feasible", 40, 40, 0): "cc608705007943180ce43ce31ce576504ac2aa056ed61145c1351c0183910fd9",
    ("feasible", 40, 40, 1): "a6d9cbef590db3f4980a9435591e7f3005b43f1eef6503bf6d0f4d4094223b7e",
    ("two_phase", 6, 5, 0): "08f29581ad98f30beae488bb0540cc5bf6d31ebf5747c8bce190e41d5a7aac2b",
    ("two_phase", 6, 5, 1): "985c7cc728953bd78db59778b23a69f46350bb285862f74551716ca58f0dcac2",
    ("two_phase", 40, 40, 0): "473d2ec7bdd6d28aa838854d7bb35316dc72228200a3179dbaf92ec2d9718881",
    ("two_phase", 40, 40, 1): "1bd5eafe97beda46aaefbbe34304c5cd12f2cc2ecd90199d9c68af8fd0823f42",
}


def _digest(arrays) -> str:
    return hashlib.sha256(b"".join(np.ascontiguousarray(v).tobytes() for v in arrays)).hexdigest()


@pytest.mark.parametrize("generator,m,n,index", sorted(DIGESTS))
def test_paper_class_draws_are_unchanged(generator, m, n, index):
    from bench import lpgen

    drawn = lpgen.load(REPO, generator).draw({"m": m, "n": n}, SEED, index, 64)
    assert [v.shape for v in drawn] == [(64, m, n), (64, m), (64, n)]
    assert all(v.dtype == np.float32 for v in drawn)
    assert _digest(drawn) == DIGESTS[generator, m, n, index]
    assert _digest(lpgen.host_batch(generator, SEED, index, 64, m, n)) == _digest(drawn)


def test_a_shared_block_reads_as_its_rows_with_a_repeated():
    """Every number of ``check`` equals that of the same rows in ``(rows, m, n)`` form."""
    from bench import check, reference
    from bench.loops import Block

    rng = np.random.default_rng(3)
    rows, m, n = 40, 8, 6
    a = rng.uniform(-1.0, 1.0, (m, n)).astype(np.float32) + np.eye(m, n, dtype=np.float32)
    b = rng.uniform(1.0, 10.0, (rows, m)).astype(np.float32)
    c = rng.uniform(0.1, 1.0, (rows, n)).astype(np.float32)
    dense_a = np.broadcast_to(a, (rows, m, n)).copy()
    status, objective, x = reference.solve(dense_a, b, c)
    # Answers a little off the reference, so that every number has something to read.
    x = (x * (1.0 + rng.uniform(-1e-3, 1e-3, x.shape))).astype(np.float32)
    objective = (objective * (1.0 + rng.uniform(-1e-3, 1e-3, rows))).astype(np.float32)
    status = status.astype(np.int32)
    status[:2] = (2, 0)
    answers = (status, objective, x, np.arange(rows, dtype=np.int32))
    shared, dense = Block(a, b, c, *answers), Block(dense_a, b, c, *answers)
    config = {"check_sample": 16}

    got, want = check.compare([shared], config, 5), check.compare([dense], config, 5)
    assert want["unanswered"] == 1 and want["objective_rel_err"] > 0 and want["primal_resid"] > 0
    assert {k: got[k] for k in ("unanswered", "status_mismatch", "objective_rel_err")} == \
        {k: want[k] for k in ("unanswered", "status_mismatch", "objective_rel_err")}
    # float64 sums of n terms in another order: within n ulps of the constraint's size.
    assert got["primal_resid"] == pytest.approx(want["primal_resid"], rel=0, abs=n * 2.3e-16)

    picks = check.sample_rows([dense], 5, 16)
    ref = check.reference_answers([dense], picks)
    ctrl_shared = check.control_blocks([shared], picks)
    ctrl_dense = check.control_blocks([dense], picks)
    assert all(s.a is a for s in ctrl_shared[0])
    assert check.numbers_against(*ctrl_shared, ref) == pytest.approx(
        check.numbers_against(*ctrl_dense, ref), rel=0, abs=n * 2.3e-16)


def _hashes(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def _without(bench: dict, config: str, workload: str) -> dict:
    """``bench`` with the entries of ``config`` and ``workload`` taken out."""
    out = json.loads(json.dumps(bench))
    out["configs"] = [c for c in out["configs"] if c["name"] != config]
    out["workloads"] = [w for w in out["workloads"] if w["name"] != workload]
    for metric in out["end_to_end"] + out["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [w for w in metric["workloads"] if w != workload]
    return out


@pytest.fixture
def shared_class(tmp_path):
    """A copy of the benchmark with the ``dummy_shared`` class added as files and entries.

    Returns the tiny copy's root, once it has seen that every file of the
    benchmark but ``BENCHMARK.json`` is as it was, and that
    ``BENCHMARK.json`` only gained entries.
    """
    source = tmp_path / "source"
    shutil.copytree(REPO / "bench", source / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", source / "BENCHMARK.json")
    before = _hashes(source)
    old = json.loads((source / "BENCHMARK.json").read_text())

    shutil.copy(DATA / "dummy_shared.py", source / "bench/inputs/dummy_shared.py")
    shutil.copy(DATA / "dummy_shared.json", source / "bench/configs/dummy_shared.json")
    bench = json.loads((source / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy_shared", "source": "https://arxiv.org/abs/1609.08114",
                             "file": "bench/configs/dummy_shared.json", "reduced": [],
                             "why": "one A shared by every LP"})
    bench["workloads"].append({"name": "dummy_shared.batch", "config": "dummy_shared",
                               "traffic": "closed_pool2", "chips": 1, "why": "dummy"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "fig8_m100.batch" in metric.get("workloads", []):
            metric["workloads"].append("dummy_shared.batch")
    (source / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _hashes(source)
    assert set(after) - set(before) == {"bench/inputs/dummy_shared.py",
                                        "bench/configs/dummy_shared.json"}
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"}
    assert _without(bench, "dummy_shared", "dummy_shared.batch") == old
    return make_tiny(tmp_path / "tiny", source=source)


def test_a_shared_class_added_as_files_runs_correct(shared_class, monkeypatch):
    import repro

    seen = []
    solve = repro.solve

    def recording(problem, *args, **kwargs):
        seen.append(type(problem))
        return solve(problem, *args, **kwargs)

    monkeypatch.setattr(repro, "solve", recording)
    cfg = json.loads((shared_class / "bench/configs/dummy_shared.json").read_text())
    assert {k: cfg[k] for k in cfg["tiny"]["cpu"]} == cfg["tiny"]["cpu"]
    res = run(shared_class, "dummy_shared.batch")
    assert res["correct"], res["checked"]
    assert res["failed"] == 0 and res["attempted"] % cfg["batch"] == 0
    assert set(res["metrics"]) == {"lps_per_s", "setup_s"}
    assert seen and set(seen) == {repro.SharedLPBatch}


def test_a_shared_class_control_fails_in_bfloat16(shared_class):
    from bench import cells, control

    cell = cells.load(shared_class, "dummy_shared.batch")
    assert control_sizes(cell.config) == cell.config["tiny"]["control"]
    cell.config.update(control_sizes(cell.config))
    for seed in (2**33 + 3, 5):
        assert control.readings(cell, seed, 1.0)["fails"], seed
        assert not control.readings(cell, seed, 1.0, precision="float64")["fails"], seed


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_a_shared_class_broken_timed_path_is_not_correct(shared_class, monkeypatch, kind):
    from repro.core import dispatch

    monkeypatch.setattr(dispatch, "dispatch_round", broken_round(kind))
    res = run(shared_class, "dummy_shared.batch")
    assert not res["correct"], (kind, res["checked"])


def test_a_shared_class_requests_are_the_rows_of_its_problem(shared_class):
    """The open loop's requests solve to what the closed loop's ``SharedLPBatch`` does."""
    import repro

    from bench import cells

    cell = cells.load(shared_class, "dummy_shared.batch")
    a, b, c = cell.inputs.draw(cell.config, SEED, 0, 6)
    assert a.shape == (cell.config["m"], cell.config["n"])
    batch = repro.solve(cell.inputs.problem(repro, a, b, c))
    rows = repro.solve(cell.inputs.requests(repro, a, b, c))
    assert len(rows) == 6
    np.testing.assert_array_equal([int(s.status[0]) for s in rows], np.asarray(batch.status))
    np.testing.assert_allclose([float(s.objective[0]) for s in rows], np.asarray(batch.objective),
                               rtol=1e-4)

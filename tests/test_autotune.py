"""Tests for the cost-model autotuner (``runtime/autotune.py``).

Covers the three stages (predict / trial / cache) plus the integration
seams: dispatch resolution, routing equivalence with the static table,
cache lifecycle (corrupt / torn / schema bump), the measured-tile
override in ``kernels/ops.py:auto_tile_b``, and the bounded warn-once
table in ``core/backends.py``.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends, dispatch, engine, lp
from repro.core.tableau import DEFAULT_LAYOUT, TableauSpec
from repro.kernels import ops as kernel_ops
from repro.runtime import autotune

F32 = jnp.float32


@pytest.fixture(autouse=True)
def isolated_tuner(tmp_path, monkeypatch):
    """Every test gets a private tuner + cache file (never ~/.cache)."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    autotune.reset(cache_path=path)
    yield path
    autotune._TUNER = None  # later modules rebuild against the real env


# -- knobs and validation ----------------------------------------------------


def test_default_options_leave_tuner_knobs_open():
    opts = backends.SolveOptions()
    assert opts.autotune == "predict"
    assert opts.layout is None
    assert opts.tile_b is None
    assert opts.effective_layout == DEFAULT_LAYOUT


def test_option_validation():
    with pytest.raises(ValueError):
        backends.SolveOptions(autotune="sometimes")
    with pytest.raises(ValueError):
        backends.SolveOptions(tile_b=0)
    with pytest.raises(ValueError):
        backends.SolveOptions(backend="pdhg", layout="dense")
    # None and the default layout are fine on pdhg (the tuner leaves
    # layout=None there)
    backends.SolveOptions(backend="pdhg", layout=None)


# -- predict mode ------------------------------------------------------------


GRID = [(5, 5), (28, 28), (100, 80), (500, 500), (700, 20)]


@pytest.mark.parametrize("m,n", GRID)
def test_predict_reproduces_static_routing(m, n):
    tuned = dispatch.resolve_backend(
        m, n, F32, backends.SolveOptions(backend="auto"), batch=8
    )
    static = dispatch.resolve_backend(
        m, n, F32, backends.SolveOptions(backend="auto", autotune="off"), batch=8
    )
    assert tuned.backend == static.backend
    assert tuned.effective_layout == static.effective_layout


def test_predict_is_pure_and_memoized(isolated_tuner):
    tuner = autotune.get_tuner()
    opts = backends.SolveOptions(backend="auto")
    first = tuner.get(20, 10, F32, opts, batch=8)
    second = tuner.get(20, 10, F32, opts, batch=8)
    assert second is first  # memo hit
    assert tuner.trials_run == 0
    assert not os.path.exists(isolated_tuner)  # prediction never touches disk
    assert first.source == "predicted"
    assert first.predicted_s > 0


def test_predict_resolution_fills_only_open_knobs():
    opts = backends.SolveOptions(backend="xla", layout="dense", tile_b=4)
    resolved = dispatch.resolve_backend(12, 8, F32, opts, batch=8)
    assert resolved.backend == "xla"
    assert resolved.layout == "dense"
    assert resolved.tile_b == 4


def test_predict_routes_pdhg_with_reset_rule_and_layout():
    resolved = dispatch.resolve_backend(
        600, 600, F32, backends.SolveOptions(backend="auto"), batch=4
    )
    assert resolved.backend == "pdhg"
    assert resolved.layout is None
    assert resolved.rule == engine.LPC


def test_stats_record_autotuned_decision():
    stats = backends.SolveStats()
    dispatch.resolve_backend(
        12, 8, F32, backends.SolveOptions(backend="auto"), batch=8, stats=stats
    )
    assert stats.autotuned == 1
    (row,) = stats.autotune_log
    assert row["m"] == 12 and row["n"] == 8
    assert row["source"] == "predicted"
    assert row["backend"] in autotune.TUNABLE_BACKENDS


def test_solve_results_identical_predict_vs_off():
    rng = np.random.default_rng(7)
    batch = lp.random_lp_batch(rng, 8, 6, 5, feasible_start=True, dtype=np.float32)
    sol_tuned = dispatch.solve_canonical(
        batch, backends.SolveOptions(backend="auto")
    )
    sol_static = dispatch.solve_canonical(
        batch, backends.SolveOptions(backend="auto", autotune="off")
    )
    np.testing.assert_array_equal(
        np.asarray(sol_tuned.objective), np.asarray(sol_static.objective)
    )
    np.testing.assert_array_equal(
        np.asarray(sol_tuned.status), np.asarray(sol_static.status)
    )


# -- candidate enumeration and the cost model ---------------------------------


def test_frontier_is_a_constraint_not_a_knob():
    auto = backends.SolveOptions(backend="auto")
    above = autotune.candidate_configs(600, 600, 8, F32, auto)
    assert {name for name, _, _ in above} == {"pdhg"}
    lifted = autotune.candidate_configs(
        600, 600, 8, F32, auto.replace(route_frontier=10_000)
    )
    assert "pdhg" not in {name for name, _, _ in lifted}


def test_cpu_candidates_exclude_pallas():
    if kernel_ops._on_tpu():
        pytest.skip("TPU host: pallas is genuinely feasible here")
    names = {
        name
        for name, _, _ in autotune.candidate_configs(
            12, 8, 8, F32, backends.SolveOptions(backend="auto")
        )
    }
    assert names == {"xla"}
    assert not autotune.feasible("pallas", "compact", None, 12, 8, F32)


def test_vmem_residency_prefers_pallas_when_feasible(monkeypatch):
    monkeypatch.setattr(kernel_ops, "_on_tpu", lambda: True)
    ranked = autotune.rank_candidates(
        12, 8, 64, F32, backends.SolveOptions(backend="auto")
    )
    assert ranked[0].backend == "pallas"  # state streams HBM once per solve
    assert any(c.backend == "xla" for c in ranked)
    assert ranked == sorted(ranked, key=lambda c: c.predicted_s)


def test_infeasible_pin_passes_through_for_dispatch_fallbacks():
    if kernel_ops._on_tpu():
        pytest.skip("TPU host: pallas is genuinely feasible here")
    cands = autotune.candidate_configs(
        12, 8, 8, F32, backends.SolveOptions(backend="pallas")
    )
    assert cands == [("pallas", None, None)]


def test_non_tunable_backend_passes_through():
    cands = autotune.candidate_configs(
        12, 8, 8, F32, backends.SolveOptions(backend="reference")
    )
    assert cands == [("reference", None, None)]


def test_predict_cost_sanity():
    # compact tableau moves fewer bytes per iteration than dense
    compact = autotune.predict_cost("xla", "compact", None, 64, 48, 256, F32)
    dense = autotune.predict_cost("xla", "dense", None, 64, 48, 256, F32)
    assert compact < dense
    # per-grid-step launch overhead: tiny tiles pay it batch/tile times
    big_tile = autotune.predict_cost("pallas", "compact", 128, 24, 16, 1024, F32)
    tiny_tile = autotune.predict_cost("pallas", "compact", 1, 24, 16, 1024, F32)
    assert big_tile < tiny_tile


def test_hlo_features_refine_the_traffic_estimate():
    base = autotune.predict_cost("xla", "compact", None, 8, 6, 16, F32)
    heavy = autotune.predict_cost(
        "xla", "compact", None, 8, 6, 16, F32,
        features={"dot_flops_per_iter": 0.0, "traffic_bytes_per_iter": 1e9},
    )
    assert heavy > base


# -- trial mode and the winner cache ------------------------------------------


def test_trial_measures_persists_and_warm_process_hits(isolated_tuner):
    opts = backends.SolveOptions(backend="auto", autotune="trial")
    tuner = autotune.get_tuner()
    first = tuner.get(6, 5, F32, opts, batch=4)
    assert first.source == "measured"
    assert first.measured_s > 0
    assert tuner.trials_run >= 2  # both simplex layouts were timed
    with open(isolated_tuner) as f:
        data = json.load(f)
    assert data["schema"] == autotune.SCHEMA_VERSION
    key = autotune.cache_key(6, 5, 4, F32)
    assert data["entries"][key]["backend"] == first.backend

    # a "new process": fresh tuner, same cache file -> zero micro-trials
    warm = autotune.reset(cache_path=isolated_tuner)
    hit = warm.get(6, 5, F32, opts, batch=4)
    assert warm.trials_run == 0
    assert hit.source == "cache"
    assert (hit.backend, hit.layout, hit.tile_b) == (
        first.backend, first.layout, first.tile_b,
    )


def test_trial_single_candidate_skips_trials_but_still_caches(isolated_tuner):
    opts = backends.SolveOptions(backend="auto", autotune="trial")
    tuner = autotune.get_tuner()
    choice = tuner.get(600, 600, F32, opts, batch=2)
    assert choice.backend == "pdhg"  # only candidate at this shape
    assert tuner.trials_run == 0  # nothing to compare against
    assert autotune.cache_key(600, 600, 2, F32) in json.load(
        open(isolated_tuner)
    )["entries"]


def test_corrupt_cache_falls_back_and_heals(isolated_tuner):
    with open(isolated_tuner, "w") as f:
        f.write("{this is not json")
    tuner = autotune.reset(cache_path=isolated_tuner)
    opts = backends.SolveOptions(backend="auto", autotune="trial")
    choice = tuner.get(600, 600, F32, opts, batch=2)  # must not crash
    assert choice.backend == "pdhg"
    data = json.load(open(isolated_tuner))  # rewritten valid
    assert data["schema"] == autotune.SCHEMA_VERSION


def test_torn_write_reads_as_empty(isolated_tuner):
    cache = autotune.TuningCache(isolated_tuner)
    cache.store("k", {"backend": "xla"})
    whole = open(isolated_tuner).read()
    with open(isolated_tuner, "w") as f:
        f.write(whole[: len(whole) // 2])  # simulate a torn write
    assert autotune.TuningCache(isolated_tuner).load() == {}


def test_schema_bump_invalidates_every_entry(isolated_tuner):
    cache = autotune.TuningCache(isolated_tuner)
    cache.store("k", {"backend": "xla"})
    data = json.load(open(isolated_tuner))
    data["schema"] = autotune.SCHEMA_VERSION + 1
    with open(isolated_tuner, "w") as f:
        json.dump(data, f)
    assert autotune.TuningCache(isolated_tuner).load() == {}


def test_cache_key_carries_platform_and_shape_classes():
    import jax

    key = autotune.cache_key(6, 5, 12, F32)
    assert key.startswith(jax.default_backend() + "|")
    assert f"vmem{kernel_ops.VMEM_BUDGET_BYTES}" in key
    assert "|lp|" in key and "m8|" in key and "n8|" in key and "b16|" in key
    assert key.endswith("float32")
    shared_key = autotune.cache_key(6, 5, 12, F32, shared=True)
    assert "|shared|" in shared_key and shared_key != key


def test_cached_pin_violating_entry_is_ignored(isolated_tuner):
    key = autotune.cache_key(6, 5, 4, F32)
    autotune.TuningCache(isolated_tuner).store(
        key, {"backend": "xla", "layout": "dense", "tile_b": None}
    )
    tuner = autotune.reset(cache_path=isolated_tuner)
    pinned = backends.SolveOptions(
        backend="auto", layout="compact", autotune="trial"
    )
    choice = tuner.get(6, 5, F32, pinned, batch=4)
    assert choice.layout == "compact"  # cached dense winner must not win
    assert choice.source in ("measured", "predicted")


# -- warm() and the measured-tile override -------------------------------------


def test_warm_tunes_then_rewarm_is_free(isolated_tuner):
    (cfg,) = autotune.warm([(6, 5, 4)])
    assert cfg.backend in autotune.TUNABLE_BACKENDS
    fresh = autotune.reset(cache_path=isolated_tuner)
    (again,) = autotune.warm([(6, 5, 4)])
    assert fresh.trials_run == 0  # pure cache hit
    assert again.source == "cache"
    assert again.backend == cfg.backend


def test_cached_tile_b_overrides_auto_tile_heuristic(
    isolated_tuner, monkeypatch
):
    monkeypatch.setattr(kernel_ops, "_on_tpu", lambda: True)
    spec = TableauSpec(6, 5, "compact")
    heuristic = kernel_ops.auto_tile_b(64, spec, F32, want_state=True)
    assert heuristic != 16  # the pinned value below must be distinguishable
    key = autotune.cache_key(6, 5, 64, F32)
    autotune.TuningCache(isolated_tuner).store(
        key,
        {
            "backend": "pallas",
            "layout": "compact",
            "tile_b": 16,
            "measured_s": 1e-4,
            "m_class": 8,
            "n_class": 8,
            "batch_class": 64,
            "dtype": "float32",
            "shared": False,
        },
    )
    autotune.reset(cache_path=isolated_tuner)
    assert autotune.cached_tile_b(64, 6, 5, F32, "compact") == 16
    assert kernel_ops.auto_tile_b(64, spec, F32, want_state=True) == 16
    # predicted-only entries (no measured_s) never pin a tile
    autotune.TuningCache(isolated_tuner).store(
        key, {"backend": "pallas", "layout": "compact", "tile_b": 16,
              "measured_s": None, "m_class": 8, "n_class": 8,
              "batch_class": 64, "dtype": "float32", "shared": False},
    )
    autotune.reset(cache_path=isolated_tuner)
    assert autotune.cached_tile_b(64, 6, 5, F32, "compact") is None
    assert kernel_ops.auto_tile_b(64, spec, F32, want_state=True) == heuristic


def test_cached_tile_b_without_tuner_is_none():
    autotune._TUNER = None
    assert autotune.cached_tile_b(64, 6, 5, F32, "compact") is None


# -- bounded warn-once table (core/backends.py) --------------------------------


def test_warn_once_table_is_bounded_and_resettable():
    backends.reset_warnings()
    with pytest.warns(UserWarning):
        for i in range(backends._WARN_ONCE_MAX + 40):
            backends._warn_once(("test-bound", i), f"warn {i}")
    assert len(backends._WARN_ONCE) <= backends._WARN_ONCE_MAX
    # dedup: re-warning a live key emits nothing new
    import warnings as _warnings

    live_key = next(reversed(backends._WARN_ONCE))
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        backends._warn_once(live_key, "dup")  # no UserWarning raised
    backends.reset_warnings()
    assert backends._WARN_ONCE == {}
    with pytest.warns(UserWarning, match="re-armed"):
        backends._warn_once(live_key, "re-armed")
    backends.reset_warnings()


def test_roofline_peaks_are_keyed_by_device_kind():
    from repro.runtime import roofline

    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    share = roofline.iteration_profile("compact", 28, 28)["roofline_fraction"]
    assert 0.0 < share < 1.0
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.iteration_profile("compact", 28, 28, device_kind="cpu")

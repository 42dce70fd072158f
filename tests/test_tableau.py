"""The tableau storage layer: dense-vs-compact parity, VMEM tiling, routing.

The compact layout's contract (``core/tableau.py``): dropping the
write-only artificial block changes NOTHING about the solve — objectives,
statuses, bases, and per-LP iteration counts are bit-identical to the
dense layout on both accelerated backends under every pivot rule,
including mid-solve basis-resume splices and warm starts.  The layer's
payoff — fewer bytes/LP, VMEM-budget-aware Pallas tiles, xla fallback for
un-fittable shapes — is covered here too.
"""

import numpy as np
import pytest

import repro
from repro import SolveOptions, SolveStats, TableauSpec
from repro.core import lp, simplex
from repro.core.tableau import DEFAULT_LAYOUT

BACKENDS = ("xla", "pallas")
RULES = ("lpc", "bland", "rpc")


def _mixed_batch(dtype=np.float32) -> lp.LPBatch:
    """Feasible-start + two-phase LPs in one (m=12, n=6) shape class."""
    rng = np.random.default_rng(77)
    easy = lp.random_lp_batch(rng, 10, 12, 6, True, dtype=dtype)
    hard = lp.random_lp_batch(rng, 6, 12, 6, False, dtype=dtype)
    return lp.LPBatch(
        np.concatenate([easy.a, hard.a]),
        np.concatenate([easy.b, hard.b]),
        np.concatenate([easy.c, hard.c]),
    )


@pytest.fixture(scope="module")
def mixed_batch():
    return _mixed_batch()


def _assert_bit_identical(a, b, basis=True):
    np.testing.assert_array_equal(np.asarray(a.status), np.asarray(b.status))
    np.testing.assert_array_equal(np.asarray(a.objective), np.asarray(b.objective))
    np.testing.assert_array_equal(np.asarray(a.iterations), np.asarray(b.iterations))
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    if basis and a.basis is not None and b.basis is not None:
        np.testing.assert_array_equal(np.asarray(a.basis), np.asarray(b.basis))


# ---------------------------------------------------------------------------
# TableauSpec arithmetic
# ---------------------------------------------------------------------------


def test_spec_column_map():
    spec = TableauSpec(12, 6, "compact")
    assert spec.q == 1 + 6 + 12
    assert spec.with_layout("dense").q == 1 + 6 + 2 * 12
    assert spec.slack_start == 7
    # art_start is a basis ID base in BOTH layouts (column only in dense).
    assert spec.art_start == spec.with_layout("dense").art_start == 19
    assert TableauSpec(100, 100).bytes_per_lp(np.float32) == 101 * 201 * 4


def test_spec_default_is_compact():
    assert DEFAULT_LAYOUT == "compact"
    assert TableauSpec(4, 4).layout == "compact"
    assert SolveOptions().layout is None  # open knob: tuner/DEFAULT fills it
    assert SolveOptions().effective_layout == "compact"


def test_spec_from_tableau_recovers_layout():
    assert TableauSpec.from_tableau(12, 6, 19).layout == "compact"
    assert TableauSpec.from_tableau(12, 6, 31).layout == "dense"
    with pytest.raises(ValueError, match="matches no layout"):
        TableauSpec.from_tableau(12, 6, 25)


def test_spec_rejects_unknown_layout():
    with pytest.raises(ValueError, match="layout"):
        TableauSpec(4, 4, "sparse")
    with pytest.raises(ValueError, match="layout"):
        SolveOptions(layout="sparse")


def test_compact_bytes_ratio_on_square_lps():
    # The paper's Table 2 regime (m = n): compact is ~2/3 of dense.
    for size in (5, 28, 100, 200):
        spec = TableauSpec(size, size)
        ratio = spec.bytes_per_lp() / spec.with_layout("dense").bytes_per_lp()
        assert ratio <= 0.75, (size, ratio)


def test_build_tableau_layouts_share_columns():
    batch = _mixed_batch()
    compact = TableauSpec(batch.m, batch.n, "compact")
    t_c, basis_c, phase_c = lp.build_tableau(batch.a, batch.b, batch.c, spec=compact)
    t_d, basis_d, phase_d = lp.build_tableau(
        batch.a, batch.b, batch.c, spec=compact.with_layout("dense")
    )
    assert t_c.shape[-1] == compact.q
    assert t_d.shape[-1] == compact.with_layout("dense").q
    # The shared columns are identical; dense merely appends the block.
    np.testing.assert_array_equal(np.asarray(t_c), np.asarray(t_d)[:, :, : compact.q])
    np.testing.assert_array_equal(np.asarray(basis_c), np.asarray(basis_d))
    np.testing.assert_array_equal(np.asarray(phase_c), np.asarray(phase_d))


# ---------------------------------------------------------------------------
# layout parity: bit-identical solves
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("rule", RULES)
def test_dense_compact_bit_identical(mixed_batch, backend, rule):
    dense = repro.solve(
        mixed_batch, SolveOptions(backend=backend, rule=rule, layout="dense")
    )
    compact = repro.solve(
        mixed_batch, SolveOptions(backend=backend, rule=rule, layout="compact")
    )
    _assert_bit_identical(dense, compact)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("layout", ("dense", "compact"))
def test_basis_resume_splice_matches_off(mixed_batch, backend, layout):
    """every_k + resume="basis" replays one uninterrupted solve —
    iteration counts included — in EITHER layout."""
    off = repro.solve(mixed_batch, SolveOptions(backend=backend, layout=layout))
    spliced = repro.solve(
        mixed_batch,
        SolveOptions(
            backend=backend, layout=layout,
            compaction="every_k", compact_every=3, resume="basis",
        ),
    )
    _assert_bit_identical(off, spliced, basis=False)


def test_compact_resume_round_trip_mid_solve(mixed_batch):
    """Interrupt/resume through the compact driver splices bit-exactly,
    and the carried state is compact-shaped."""
    b = mixed_batch
    full, _ = simplex.solve_batched(b.a, b.b, b.c, max_iters=40, want_state=True)
    half, state = simplex.solve_batched(b.a, b.b, b.c, max_iters=15, want_state=True)
    assert state.tab.shape[-1] == TableauSpec(b.m, b.n, "compact").q
    rest, _ = simplex.resume_batched(b.b, b.c, state, max_iters=25)
    np.testing.assert_array_equal(np.asarray(full.status), np.asarray(rest.status))
    np.testing.assert_array_equal(
        np.asarray(full.objective), np.asarray(rest.objective)
    )
    np.testing.assert_array_equal(
        np.asarray(full.iterations),
        np.asarray(half.iterations) + np.asarray(rest.iterations),
    )


def test_resume_continues_in_the_state_layout(mixed_batch):
    """A dense-produced state resumes IN dense even though the default is
    compact — ResumeState is layout-self-describing."""
    b = mixed_batch
    _, state = simplex.solve_batched(
        b.a, b.b, b.c, max_iters=15, want_state=True, layout="dense"
    )
    assert state.tab.shape[-1] == TableauSpec(b.m, b.n, "dense").q
    rest, out_state = simplex.resume_batched(b.b, b.c, state, max_iters=25)
    assert out_state.tab.shape[-1] == state.tab.shape[-1]
    full = simplex.solve_batched(b.a, b.b, b.c, max_iters=40, layout="dense")
    np.testing.assert_array_equal(
        np.asarray(full.objective), np.asarray(rest.objective)
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_warm_start_equivalent_in_both_layouts(mixed_batch, backend):
    """basis0 warm starts behave identically under dense and compact."""
    cold = repro.solve(mixed_batch, SolveOptions(backend=backend))
    warm_batch = lp.LPBatch(
        mixed_batch.a, mixed_batch.b, mixed_batch.c, basis0=cold.basis
    )
    outs = {}
    for layout in ("dense", "compact"):
        outs[layout] = repro.solve(
            warm_batch, SolveOptions(backend=backend, layout=layout)
        )
        # A re-solve from the optimal basis converges without pivoting.
        ok = np.asarray(cold.status) == lp.OPTIMAL
        assert (np.asarray(outs[layout].iterations)[ok] == 0).all()
    _assert_bit_identical(outs["dense"], outs["compact"])


def test_sweep_session_layout_parity():
    """The compiled lax.scan sweep carries a compact tableau by default
    and agrees with the dense carry bit-for-bit."""
    from repro.core import session

    rng = np.random.default_rng(11)
    a = rng.standard_normal((8, 4)).astype(np.float32)
    b = (np.abs(a).sum(axis=1) + 1.0).astype(np.float32)
    dirs = rng.standard_normal((6, 5, 4)).astype(np.float32)
    sup = {}
    for layout in ("dense", "compact"):
        sup[layout] = np.asarray(
            session.sweep_polytope_supports(a, b, dirs, SolveOptions(layout=layout))
        )
    np.testing.assert_array_equal(sup["dense"], sup["compact"])


# ---------------------------------------------------------------------------
# VMEM tiling + routing (satellites)
# ---------------------------------------------------------------------------


def test_pallas_small_batches_regression():
    """Batches of 1–7 LPs solve on the pallas backend (auto tile clamps
    to the batch instead of asserting on divisibility)."""
    from repro.core import oracle

    rng = np.random.default_rng(5)
    for bsz in range(1, 8):
        batch = lp.random_lp_batch(rng, bsz, 12, 6, feasible_start=(bsz % 2 == 0))
        sol = repro.solve(batch, SolveOptions(backend="pallas"))
        obj, _, st, _ = oracle.solve_batch(
            np.asarray(batch.a), np.asarray(batch.b), np.asarray(batch.c)
        )
        np.testing.assert_array_equal(np.asarray(sol.status), st)
        ok = st == lp.OPTIMAL
        np.testing.assert_allclose(
            np.asarray(sol.objective)[ok], obj[ok], rtol=1e-5
        )


def test_auto_tile_b_scales_with_layout():
    from repro.kernels import ops

    spec_c = TableauSpec(100, 100, "compact")
    spec_d = spec_c.with_layout("dense")
    tile_c = ops.auto_tile_b(4096, spec_c)
    tile_d = ops.auto_tile_b(4096, spec_d)
    assert tile_c >= tile_d  # smaller tableau -> at least as many LPs/tile
    assert tile_c >= 1 and tile_d >= 1
    # Tiny batches never get a tile bigger than their pow2 roundup.
    assert ops.auto_tile_b(4, TableauSpec(6, 6)) <= 4
    # The tile respects the budget.
    per_lp = ops.kernel_vmem_bytes_per_lp(spec_c)
    assert tile_c * per_lp <= ops.VMEM_BUDGET_BYTES * ops.VMEM_TILE_FRACTION


def test_pallas_vmem_fallback_routes_to_xla(mixed_batch, monkeypatch):
    """Shapes whose single-LP tableau busts the budget run via xla —
    same results, no crash."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", 1024)  # nothing fits
    assert not ops.fits_vmem(mixed_batch.m, mixed_batch.n)
    before = ops.compile_cache_size()
    with pytest.warns(UserWarning, match=r"VMEM bytes/LP against the .*budget"):
        sol = repro.solve(mixed_batch, SolveOptions(backend="pallas"))
    assert ops.compile_cache_size() == before  # kernel never launched
    ref = repro.solve(mixed_batch, SolveOptions(backend="xla"))
    _assert_bit_identical(ref, sol)
    # The resumed rounds of a compacted solve route consistently too.
    spliced = repro.solve(
        mixed_batch,
        SolveOptions(
            backend="pallas", compaction="every_k", compact_every=3, resume="basis"
        ),
    )
    off = repro.solve(mixed_batch, SolveOptions(backend="xla"))
    _assert_bit_identical(off, spliced, basis=False)


def test_pallas_resume_routes_on_state_layout(monkeypatch):
    """The resume fallback check uses the CARRIED state's layout, not the
    caller's options: a dense state resumed under compact-default options
    must still route to xla when only compact fits the budget.  Needs a
    shape where the PADDED widths differ (m = n = 100: 256 vs 384 lanes —
    small shapes pad both layouts to the same 128)."""
    from repro.core import backends
    from repro.kernels import ops

    rng = np.random.default_rng(21)
    b = lp.random_lp_batch(rng, 4, 100, 100, feasible_start=True)
    _, state = simplex.solve_batched(
        b.a, b.b, b.c, max_iters=10, want_state=True, layout="dense"
    )
    dense_lp = ops.kernel_vmem_bytes_per_lp(
        TableauSpec(b.m, b.n, "dense"), np.float32, want_state=True
    )
    compact_lp = ops.kernel_vmem_bytes_per_lp(
        TableauSpec(b.m, b.n, "compact"), np.float32, want_state=True
    )
    # A budget that admits a smallest legal tile of compact but not dense.
    budget = int(ops.MIN_TILE_B * (dense_lp + compact_lp) / 2 / ops.VMEM_TILE_FRACTION)
    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", budget)
    assert ops.fits_vmem(b.m, b.n, layout="compact", want_state=True)
    assert not ops.fits_vmem(b.m, b.n, layout="dense", want_state=True)
    before = ops.compile_cache_size()
    lpb = lp.LPBatch(b.a, b.b, b.c)
    sol, out_state = backends.get_backend("pallas").resume_canonical(
        lpb, state, SolveOptions(backend="pallas", max_iters=100)
    )
    # Routed to xla (dense state busts the budget): no kernel compile,
    # and the continuation matches the uninterrupted dense solve.
    assert ops.compile_cache_size() == before
    full = simplex.solve_batched(b.a, b.b, b.c, max_iters=110, layout="dense")
    np.testing.assert_array_equal(
        np.asarray(full.objective), np.asarray(sol.objective)
    )


def test_stats_tableau_bytes_records_peak(mixed_batch):
    stats = {}
    for layout in ("dense", "compact"):
        st = SolveStats()
        repro.solve(mixed_batch, SolveOptions(layout=layout), stats=st)
        spec = TableauSpec(mixed_batch.m, mixed_batch.n, layout)
        assert st.tableau_bytes == mixed_batch.batch * spec.bytes_per_lp(
            np.float32
        )
        stats[layout] = st.tableau_bytes
    assert stats["compact"] < stats["dense"]

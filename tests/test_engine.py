"""Cross-backend engine parity: one iteration engine, every (backend, rule).

The tentpole guarantee of the shared engine (``core/engine.py``): the XLA
lockstep driver and the Pallas kernel driver run the SAME building blocks,
so

* every (backend, rule) pair agrees with the float64 NumPy oracle on
  statuses and objectives over a mixed fixture batch (feasible /
  infeasible / unbounded / degenerate LPs), and
* xla vs pallas agree BIT-WISE on iteration counts under the
  deterministic rules (and, because the RPC noise is a stateless counter
  hash keyed on global row/column, under rpc too).
"""

import numpy as np
import pytest

import repro
from repro import SolveOptions
from repro.core import engine, lp, oracle, simplex
from repro.core.lp import LPBatch

BACKENDS = ("xla", "pallas")
RULES = engine.RULES


def _fixture_batch(dtype=np.float64) -> LPBatch:
    """Mixed batch: feasible-start, two-phase, unbounded, infeasible,
    and degenerate LPs in one (m=12, n=6) shape class."""
    rng = np.random.default_rng(1234)
    m, n = 12, 6
    easy = lp.random_lp_batch(rng, 10, m, n, True, dtype=dtype)
    hard = lp.random_lp_batch(rng, 6, m, n, False, dtype=dtype)

    # Unbounded: all constraint coefficients <= 0, positive costs.
    a_unb = -np.abs(rng.uniform(0.1, 1.0, size=(2, m, n)))
    b_unb = np.ones((2, m))
    c_unb = np.abs(rng.uniform(0.1, 1.0, size=(2, n)))

    # Infeasible: x_0 <= 1 conflicts with x_0 >= 3.
    a_inf = np.zeros((2, m, n))
    b_inf = np.ones((2, m))
    a_inf[:, 0, 0] = 1.0
    b_inf[:, 0] = 1.0
    a_inf[:, 1, 0] = -1.0
    b_inf[:, 1] = -3.0
    c_inf = np.ones((2, n))

    # Degenerate: redundant copies of the same facet meet at the optimum
    # (plus a zero-RHS row) — exercises ties in the ratio test and the
    # zero_art escape interplay.
    a_deg = np.zeros((2, m, n))
    b_deg = np.ones((2, m))
    a_deg[:, 0, :2] = 1.0
    a_deg[:, 1, :2] = 1.0
    a_deg[:, 2, :2] = 2.0
    b_deg[:, 2] = 2.0
    a_deg[:, 3, 0] = 1.0
    b_deg[:, 3] = 0.5
    a_deg[:, 4, 1] = -1.0
    b_deg[:, 4] = 0.0  # x_1 >= 0 (redundant, RHS exactly 0)
    c_deg = np.zeros((2, n))
    c_deg[:, :2] = 1.0

    return LPBatch(
        np.concatenate([easy.a, hard.a, a_unb, a_inf, a_deg]).astype(dtype),
        np.concatenate([easy.b, hard.b, b_unb, b_inf, b_deg]).astype(dtype),
        np.concatenate([easy.c, hard.c, c_unb, c_inf, c_deg]).astype(dtype),
    )


@pytest.fixture(scope="module")
def fixture_batch():
    return _fixture_batch()


@pytest.fixture(scope="module")
def oracle_solution(fixture_batch):
    b = fixture_batch
    obj, xs, st, it = oracle.solve_batch(
        np.asarray(b.a), np.asarray(b.b), np.asarray(b.c)
    )
    # The fixture really is mixed.
    assert (st == lp.OPTIMAL).any()
    assert (st == lp.UNBOUNDED).any()
    assert (st == lp.INFEASIBLE).any()
    return obj, st


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("rule", RULES)
def test_every_backend_rule_pair_matches_oracle(
    fixture_batch, oracle_solution, backend, rule
):
    obj, st = oracle_solution
    sol = repro.solve(fixture_batch, SolveOptions(backend=backend, rule=rule))
    assert np.array_equal(st, np.asarray(sol.status)), (backend, rule)
    ok = st == lp.OPTIMAL
    np.testing.assert_allclose(
        np.asarray(sol.objective)[ok], obj[ok], rtol=1e-9, atol=1e-9,
        err_msg=f"{backend}/{rule}",
    )


def test_reference_backend_matches_oracle(fixture_batch, oracle_solution):
    obj, st = oracle_solution
    sol = repro.solve(fixture_batch, SolveOptions(backend="reference"))
    assert np.array_equal(st, np.asarray(sol.status))
    ok = st == lp.OPTIMAL
    np.testing.assert_allclose(np.asarray(sol.objective)[ok], obj[ok], rtol=1e-12)


@pytest.mark.parametrize("rule", RULES)
def test_xla_pallas_bitwise_iteration_parity(fixture_batch, rule):
    """Deterministic rules MUST match bit-wise; the counter-hash RPC noise
    is keyed on (seed, step, global row, column), so rpc matches too."""
    xla = repro.solve(fixture_batch, SolveOptions(backend="xla", rule=rule))
    pal = repro.solve(fixture_batch, SolveOptions(backend="pallas", rule=rule))
    assert np.array_equal(np.asarray(xla.status), np.asarray(pal.status))
    np.testing.assert_array_equal(
        np.asarray(xla.iterations), np.asarray(pal.iterations)
    )
    np.testing.assert_array_equal(np.asarray(xla.basis), np.asarray(pal.basis))
    ok = np.asarray(xla.status) == lp.OPTIMAL
    np.testing.assert_array_equal(
        np.asarray(xla.objective)[ok], np.asarray(pal.objective)[ok]
    )


def test_pallas_parity_independent_of_tiling(fixture_batch):
    from repro.kernels import ops

    b = fixture_batch
    s4 = ops.simplex_solve(b.a, b.b, b.c, rule="rpc", tile_b=4)
    s8 = ops.simplex_solve(b.a, b.b, b.c, rule="rpc", tile_b=8)
    np.testing.assert_array_equal(np.asarray(s4.iterations), np.asarray(s8.iterations))
    np.testing.assert_array_equal(np.asarray(s4.status), np.asarray(s8.status))


def test_rpc_noise_uses_objective_dtype():
    """The RPC draw happens in the tableau dtype (old bug: float32 always)."""
    import jax.numpy as jnp

    for dtype in (jnp.float32, jnp.float64):
        noise = engine.rpc_noise(0, 0, 0, 4, 8, dtype)
        assert noise.dtype == dtype
        arr = np.asarray(noise)
        assert ((arr >= 0) & (arr < 1)).all()
    # Different (seed, step) -> different draws; same key -> identical.
    n0 = np.asarray(engine.rpc_noise(0, 0, 0, 4, 8, jnp.float32))
    n1 = np.asarray(engine.rpc_noise(1, 0, 0, 4, 8, jnp.float32))
    n2 = np.asarray(engine.rpc_noise(0, 1, 0, 4, 8, jnp.float32))
    assert not np.array_equal(n0, n1)
    assert not np.array_equal(n0, n2)
    np.testing.assert_array_equal(
        n0, np.asarray(engine.rpc_noise(0, 0, 0, 4, 8, jnp.float32))
    )


def test_rpc_seed_changes_trajectory(fixture_batch):
    b = fixture_batch
    s0 = simplex.solve_batched(b.a, b.b, b.c, rule=engine.RPC, seed=0)
    s1 = simplex.solve_batched(b.a, b.b, b.c, rule=engine.RPC, seed=99)
    assert np.array_equal(np.asarray(s0.status), np.asarray(s1.status))
    assert not np.array_equal(np.asarray(s0.iterations), np.asarray(s1.iterations))


def test_tolerance_honored_by_pallas(fixture_batch):
    """An absurdly large tolerance must change pallas results (proof the
    knob reaches the kernel), while the default matches the oracle."""
    b = fixture_batch
    loose = repro.solve(
        b, SolveOptions(backend="pallas", tolerance=1e6)
    )
    # With tol=1e6 every reduced cost is "non-positive": zero pivots.
    assert (np.asarray(loose.iterations) == 0).all()


def test_unknown_rule_raises():
    with pytest.raises(ValueError, match="pivot rule"):
        SolveOptions(rule="steepest-edge")


def test_unknown_rule_raises_in_engine():
    import jax.numpy as jnp

    obj = jnp.zeros((2, 8))
    elig = engine.eligible_mask(8, 2, 3)
    with pytest.raises(ValueError, match="pivot rule"):
        engine.select_entering(obj, elig, "nope", 1e-6)


def test_zero_art_lives_only_in_engine():
    """The degenerate-artificial escape exists in exactly one jnp module."""
    import pathlib

    src = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"
    hits = [
        p.relative_to(src).as_posix()
        for p in src.rglob("*.py")
        if "zero_art" in p.read_text()
    ]
    assert hits == ["core/engine.py"], hits


def test_engine_solution_extraction_matches_manual(fixture_batch):
    """extract_solution's one-hot scatter equals the dense reconstruction."""
    b = fixture_batch
    sol = simplex.solve_batched(b.a, b.b, b.c)
    st = np.asarray(sol.status)
    x = np.asarray(sol.x)
    a = np.asarray(b.a)
    bb = np.asarray(b.b)
    ok = st == lp.OPTIMAL
    # Returned points are primal feasible and attain the objective.
    for i in np.nonzero(ok)[0]:
        assert (a[i] @ x[i] <= bb[i] + 1e-7).all()
        assert (x[i] >= -1e-9).all()
        np.testing.assert_allclose(
            float(np.asarray(b.c)[i] @ x[i]), float(sol.objective[i]), rtol=1e-9
        )


# ---------------------------------------------------------------------------
# The Pallas kernel's gated objective rewrite (kernels/simplex_pallas.py)
# ---------------------------------------------------------------------------

TILE = 8


def _tiled_batch(dtype=np.float64) -> LPBatch:
    """Three tiles of 8 (m=12, n=6): no phase-I LP; every LP in phase I;
    a mixed tile whose two phase-I LPs leave phase I in different
    iterations (one has a single lower bound, the other six)."""
    m, n = 12, 6
    rng = np.random.default_rng(7)
    easy = lp.random_lp_batch(rng, 14, m, n, True, dtype=dtype)
    hard = lp.random_lp_batch(rng, 10, m, n, False, dtype=dtype)

    def rows(batch, lo, hi):
        return [np.asarray(v)[lo:hi] for v in (batch.a, batch.b, batch.c)]

    parts = [rows(easy, 0, 8), rows(hard, 0, 8), rows(easy, 8, 11),
             rows(hard, 8, 10), rows(easy, 11, 14)]
    a, b, c = (np.concatenate(p) for p in zip(*parts))
    b[19, n + 1 : 2 * n] = 1.0  # drop five of LP 19's six lower bounds
    return LPBatch(a, b, c)


def _phase1_per_tile(b) -> np.ndarray:
    return (np.asarray(b) < 0).any(axis=1).reshape(-1, TILE).sum(axis=1)


def _assert_same(xla, pal):
    for field in ("status", "iterations", "basis", "objective", "x"):
        np.testing.assert_array_equal(
            np.asarray(getattr(xla, field)), np.asarray(getattr(pal, field)),
            err_msg=field,
        )


def test_gated_rewrite_matches_xla_driver_bitwise_per_kind_of_tile():
    from repro.kernels import ops

    batch = _tiled_batch()
    np.testing.assert_array_equal(_phase1_per_tile(batch.b), [0, 8, 2])
    xla = simplex.solve_batched(batch.a, batch.b, batch.c)
    pal = ops.simplex_solve(batch.a, batch.b, batch.c, tile_b=TILE)
    _assert_same(xla, pal)
    assert (np.asarray(xla.status) == lp.OPTIMAL).all()
    # The tile's count rides on its first row.  The mixed tile's gate
    # opened twice: its two phase-I LPs crossed in different iterations.
    rewrites = np.asarray(pal.phase_rewrites).reshape(-1, TILE)
    np.testing.assert_array_equal(rewrites[:, 1:], 0)
    np.testing.assert_array_equal(rewrites[:, 0], [0, 1, 2])


def test_gated_rewrite_resumes_across_the_phase_transition():
    """A capped first round that stops before any LP leaves phase I, and a
    resumed round that crosses it, equal the uninterrupted XLA solve."""
    from repro.kernels import ops

    batch = _tiled_batch()
    xla = simplex.solve_batched(batch.a, batch.b, batch.c)
    first, state = ops.simplex_solve(
        batch.a, batch.b, batch.c, tile_b=TILE, max_iters=1, want_state=True
    )
    assert (np.asarray(first.phase_rewrites) == 0).all()
    np.testing.assert_array_equal(
        (np.asarray(state.phase) == 1).reshape(-1, TILE).sum(axis=1), [0, 8, 2]
    )
    rest = lp.auto_cap(12, 6) - 1
    second, _ = ops.simplex_resume(
        batch.b, batch.c, state, tile_b=TILE, max_iters=rest
    )
    np.testing.assert_array_equal(np.asarray(second.phase_rewrites)[::TILE], [0, 1, 2])
    np.testing.assert_array_equal(
        np.asarray(first.iterations) + np.asarray(second.iterations),
        np.asarray(xla.iterations),
    )
    for field in ("status", "basis", "objective", "x"):
        np.testing.assert_array_equal(
            np.asarray(getattr(xla, field)), np.asarray(getattr(second, field)),
            err_msg=field,
        )


@pytest.mark.parametrize("two_phase", [False, True])
def test_phase_rewrites_counter(two_phase):
    """``SolveStats.phase_rewrites``: 0 where every LP starts in phase II,
    else between 1 and the tile's phase-I LPs per tile; read in the same
    host sync as the iteration counts."""
    from repro.kernels import ops

    batch = lp.random_lp_batch(
        np.random.default_rng(21), 4 * TILE, 12, 6, not two_phase, dtype=np.float64
    )
    pal = ops.simplex_solve(batch.a, batch.b, batch.c, tile_b=TILE)
    per_tile = np.asarray(pal.phase_rewrites)[::TILE]
    phase1 = _phase1_per_tile(batch.b)
    if two_phase:
        assert (phase1 > 0).all()
        assert ((per_tile >= 1) & (per_tile <= phase1)).all(), (per_tile, phase1)
    else:
        assert (per_tile == 0).all()

    counted = {}
    for backend in ("xla", "pallas"):
        stats = repro.SolveStats()
        repro.solve(batch, SolveOptions(backend=backend, tile_b=TILE), stats=stats)
        counted[backend] = stats
    assert counted["pallas"].phase_rewrites == int(per_tile.sum())
    assert counted["xla"].phase_rewrites == 0
    assert counted["pallas"].host_syncs == counted["xla"].host_syncs == counted["pallas"].rounds

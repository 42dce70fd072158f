"""Fault-injection tests for the robustness layer (PR 9 acceptance surface).

Every fault here is injected deterministically by ``runtime/chaos.py``
and must be absorbed by ``core/dispatch.py``'s recovery wrapper and
numerical guardrails:

* an injected backend exception re-dispatches the SAME round from the
  same carried resume state — healthy LPs recover bit-identically to the
  fault-free run, with zero recompiles on a warmed cache;
* a NaN-poisoned carried state retires exactly the poisoned rows with
  the ``NUMERICAL`` status (never a wrong OPTIMAL/UNBOUNDED/INFEASIBLE
  certificate), while untouched rows stay bit-identical;
* the opt-in quarantine lane re-solves flagged rows on the float64
  oracle and upgrades them back to real answers;
* host-boundary validation rejects NaN/Inf input before a dispatch ever
  sees it, naming the offending field.
"""

import jax
import numpy as np
import pytest

from repro import SolveOptions, SolveStats
from repro.core import dispatch
from repro.core.lp import (
    NUMERICAL,
    OPTIMAL,
    random_lp_batch,
    random_shared_lp_batch,
)
from repro.core.problem import LPProblem, canonicalize_shared
from repro.runtime import chaos

# Basis-resume compaction: rounds carry exact state, which is what the
# retry-from-ResumeState and poison-the-carried-state tests exercise.
RESUME = dict(compaction="every_k", compact_every=4, resume="basis")


def _batch(bsz=6, m=8, n=6, seed=0):
    return random_lp_batch(np.random.default_rng(seed), bsz, m, n)


def _assert_identical(ref, sol, rows=slice(None), iterations=True):
    assert np.array_equal(
        np.asarray(ref.status)[rows], np.asarray(sol.status)[rows]
    )
    np.testing.assert_array_equal(
        np.asarray(ref.objective)[rows], np.asarray(sol.objective)[rows]
    )
    np.testing.assert_array_equal(
        np.asarray(ref.x)[rows], np.asarray(sol.x)[rows]
    )
    if iterations:
        np.testing.assert_array_equal(
            np.asarray(ref.iterations)[rows], np.asarray(sol.iterations)[rows]
        )


# -- retry-from-ResumeState ----------------------------------------------


def test_injected_failure_recovers_bit_identical():
    batch = _batch()
    opts = SolveOptions(backend="xla", **RESUME)
    ref = dispatch.solve_canonical(batch, opts)

    stats = SolveStats()
    with chaos.inject(chaos.ChaosMonkey(fail_rounds=(1,))) as mk:
        sol = dispatch.solve_canonical(batch, opts, stats=stats)
    assert mk.faults_injected == 1
    assert stats.retries == 1
    assert stats.faults_injected == 1
    _assert_identical(ref, sol)


def test_retry_budget_exhausted_raises():
    batch = _batch()
    opts = SolveOptions(backend="xla", retry_budget=1, retry_backoff=0.0, **RESUME)
    with chaos.inject(chaos.ChaosMonkey(fail_rounds=tuple(range(32)))):
        with pytest.raises(chaos.ChaosError):
            dispatch.solve_canonical(batch, opts)


def test_retry_budget_zero_fails_fast():
    batch = _batch()
    stats = SolveStats()
    opts = SolveOptions(backend="xla", retry_budget=0, **RESUME)
    with chaos.inject(chaos.ChaosMonkey(fail_rounds=(0,))):
        with pytest.raises(chaos.ChaosError):
            dispatch.solve_canonical(batch, opts, stats=stats)
    assert stats.retries == 0


def test_non_transient_errors_are_not_retried():
    assert not chaos.is_transient(ValueError("bad argument"))
    assert not chaos.is_transient(TypeError("bad type"))
    assert chaos.is_transient(chaos.ChaosError("injected"))
    assert chaos.is_transient(RuntimeError("device lost"))
    # A deterministic caller bug propagates immediately: unknown backend
    # names raise ValueError out of dispatch_round_safe without burning
    # the retry budget on hopeless re-dispatches.
    stats = SolveStats()
    with pytest.raises(ValueError):
        dispatch.dispatch_round_safe(
            _batch(), SolveOptions(backend="no-such-backend"), None, (), stats
        )
    assert stats.retries == 0


class LoweringException(Exception):
    """Stands in for Pallas's lowering exception (matched by class name)."""


#: Compile-time refusals as the TPU stack raises them, and a runtime fault.
MOSAIC_REFUSAL = jax.errors.JaxRuntimeError(
    "INTERNAL: Mosaic failed to compile TPU kernel: Not implemented: Lane broadcast"
)
HBM_REFUSAL = jax.errors.JaxRuntimeError(
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of memory "
    "in memory space hbm."
)


def test_compile_errors_are_not_transient():
    for exc in (MOSAIC_REFUSAL, HBM_REFUSAL, LoweringException("no rule")):
        assert chaos.is_compile_error(exc)
        assert not chaos.is_transient(exc)
    device_fault = jax.errors.JaxRuntimeError("UNAVAILABLE: TPU device lost")
    assert not chaos.is_compile_error(device_fault)
    assert chaos.is_transient(device_fault)


@pytest.mark.parametrize(
    "error,retried",
    [
        (MOSAIC_REFUSAL, False),
        (LoweringException("no lowering rule"), False),
        (NotImplementedError("no lowering rule"), False),
        (chaos.ChaosError("injected"), True),
    ],
)
def test_pallas_round_retries_only_runtime_faults(monkeypatch, recwarn, error, retried):
    """A kernel compile error surfaces; only a runtime fault retries on xla."""
    from repro.core import backends
    from repro.kernels import ops

    batch = _batch()
    opts = SolveOptions(backend="pallas", retry_backoff=0.0)
    ref = dispatch.solve_canonical(batch, opts)
    real = ops.simplex_solve
    calls = []

    def failing_once(*args, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise error
        return real(*args, **kw)

    monkeypatch.setattr(ops, "simplex_solve", failing_once)
    backends.reset_warnings()
    stats = SolveStats()
    if not retried:
        with pytest.raises(type(error)):
            dispatch.solve_canonical(batch, opts, stats=stats)
        assert stats.retries == 0
        assert not [w for w in recwarn if "dispatch fault" in str(w.message)]
        return
    sol = dispatch.solve_canonical(batch, opts, stats=stats)
    assert stats.retries == 1
    assert [w for w in recwarn if "dispatch fault" in str(w.message)]
    _assert_identical(ref, sol)


def test_shard_crash_mid_round_recovers_bit_identical():
    batch = _batch(bsz=8)
    opts = SolveOptions(backend="xla", chunk_size=4)
    ref = dispatch.solve_canonical(batch, opts)
    stats = SolveStats()
    with chaos.inject(
        chaos.ChaosMonkey(crash_rounds=(0,), max_faults=1)
    ) as mk:
        sol = dispatch.solve_canonical(batch, opts, stats=stats)
    assert mk.faults_injected == 1
    assert stats.retries == 1
    _assert_identical(ref, sol)


@pytest.mark.parametrize("backend", ["xla", "pallas", "pdhg", "xla-shared"])
def test_recovery_across_backends(backend):
    """fail-once → retry recovers bit-identically on every backend family.

    The pallas twins retry on their routed xla fallback (bit-identical
    engine blocks); xla/pdhg retry in place.
    """
    rng = np.random.default_rng(1)
    if backend == "xla-shared":
        batch = random_shared_lp_batch(rng, 6, 8, 6)
    else:
        batch = random_lp_batch(rng, 6, 8, 6)
    opts = SolveOptions(backend=backend)
    ref = dispatch.solve_canonical(batch, opts)
    stats = SolveStats()
    with chaos.inject(chaos.ChaosMonkey(fail_rounds=(0,), max_faults=1)):
        sol = dispatch.solve_canonical(batch, opts, stats=stats)
    assert stats.retries == 1
    _assert_identical(ref, sol)


def test_recovery_reuses_warm_executables():
    """Zero steady-state recompiles: the retry re-enters the same cache."""
    batch = _batch()
    opts = SolveOptions(backend="xla", **RESUME)
    dispatch.solve_canonical(batch, opts)  # warm the compile cache
    stats = SolveStats()
    with chaos.inject(chaos.ChaosMonkey(fail_rounds=(1,))):
        dispatch.solve_canonical(batch, opts, stats=stats)
    assert stats.retries == 1
    assert stats.compiles == 0


# -- numerical guardrails -------------------------------------------------


def test_poisoned_state_retires_numerical():
    batch = _batch()
    opts = SolveOptions(backend="xla", **RESUME)
    ref = dispatch.solve_canonical(batch, opts)
    stats = SolveStats()
    with chaos.inject(chaos.ChaosMonkey(poison_rows={0: (0,)})) as mk:
        sol = dispatch.solve_canonical(batch, opts, stats=stats)
    assert mk.rows_poisoned == 1
    st = np.asarray(sol.status)
    assert st[0] == NUMERICAL
    assert np.isnan(np.asarray(sol.objective)[0])
    # Healthy rows are untouched by the neighbor's corruption.
    _assert_identical(ref, sol, rows=slice(1, None))


def test_guardrails_never_flag_honest_statuses():
    """UNBOUNDED/INFEASIBLE/limit rows pass the health mask untouched.

    ``extract_solution`` fills non-OPTIMAL objectives with -inf, so a
    naive isfinite mask would misretire every honest non-optimal row;
    the guardrail must scope its objective check to claimed optima.
    """
    rng = np.random.default_rng(2)
    m, n = 8, 6
    easy = random_lp_batch(rng, 2, m, n)
    a_unb = -np.abs(rng.uniform(0.1, 1.0, size=(2, m, n)))
    b_unb = np.ones((2, m))
    c_unb = np.abs(rng.uniform(0.1, 1.0, size=(2, n)))
    a_inf = np.zeros((2, m, n))
    b_inf = np.ones((2, m))
    a_inf[:, 0, 0] = 1.0
    a_inf[:, 1, 0] = -1.0
    b_inf[:, 0] = 1.0
    b_inf[:, 1] = -3.0
    c_inf = np.ones((2, n))
    batch = type(easy)(
        np.concatenate([easy.a, a_unb, a_inf]),
        np.concatenate([easy.b, b_unb, b_inf]),
        np.concatenate([easy.c, c_unb, c_inf]),
    )
    off = dispatch.solve_canonical(
        batch, SolveOptions(backend="xla", guardrails=False)
    )
    on = dispatch.solve_canonical(batch, SolveOptions(backend="xla"))
    assert not np.any(np.asarray(on.status) == NUMERICAL)
    _assert_identical(off, on)


def test_quarantine_rescues_poisoned_rows():
    batch = _batch()
    opts = SolveOptions(backend="xla", **RESUME)
    ref = dispatch.solve_canonical(batch, opts)
    stats = SolveStats()
    with chaos.inject(chaos.ChaosMonkey(poison_rows={0: (0,)})):
        sol = dispatch.solve_canonical(
            batch, opts.replace(quarantine=True), stats=stats
        )
    assert stats.quarantined == 1
    st = np.asarray(sol.status)
    assert st[0] == OPTIMAL
    # The quarantine lane answers from the float64 oracle: numerically
    # equal to the device answer, not bit-equal.
    assert abs(float(sol.objective[0]) - float(ref.objective[0])) < 1e-6
    _assert_identical(ref, sol, rows=slice(1, None))


# -- input validation -----------------------------------------------------


def test_make_rejects_nan_naming_field():
    c = np.array([[1.0, np.nan]])
    a = np.ones((1, 2, 2))
    b = np.ones((1, 2))
    with pytest.raises(ValueError, match=r"\.c contains NaN"):
        LPProblem.make(c=c, a=a, bu=b)
    with pytest.raises(ValueError, match=r"\.a contains"):
        LPProblem.make(
            c=np.ones((1, 2)), a=np.full((1, 2, 2), np.inf), bu=b
        )
    # Inf in bounds is legal ("no bound"), never rejected.
    LPProblem.make(
        c=np.ones((1, 2)), a=a, bu=np.full((1, 2), np.inf)
    )
    # Opt-out for callers that pre-validated.
    p = LPProblem.make(c=c, a=a, bu=b, validate=False)
    assert p.batch == 1


def test_canonicalize_shared_rejects_poisoned_input():
    c = np.ones((2, 2))
    c[1, 0] = np.nan
    a = np.broadcast_to(np.eye(2), (2, 2, 2)).copy()
    b = np.ones((2, 2))
    p = LPProblem.make(c=c, a=a, bu=b, validate=False)
    with pytest.raises(ValueError, match="NaN"):
        canonicalize_shared(p)


# -- delays, determinism, speculation ------------------------------------


def test_delay_injection_counts():
    batch = _batch()
    with chaos.inject(chaos.ChaosMonkey(delay_s=0.005)) as mk:
        dispatch.solve_canonical(batch, SolveOptions(backend="xla"))
    assert mk.delays_injected >= 1


def test_chaos_schedule_is_deterministic():
    batch = _batch()
    opts = SolveOptions(
        backend="xla", retry_budget=8, retry_backoff=0.0, **RESUME
    )

    def run():
        stats = SolveStats()
        mk = chaos.ChaosMonkey(seed=7, error_rate=1.0, max_faults=3)
        with chaos.inject(mk):
            sol = dispatch.solve_canonical(batch, opts, stats=stats)
        return sol, mk, stats

    sol_a, mk_a, st_a = run()
    sol_b, mk_b, st_b = run()
    assert mk_a.faults_injected == mk_b.faults_injected == 3
    assert mk_a.rounds_seen == mk_b.rounds_seen
    assert st_a.retries == st_b.retries
    _assert_identical(sol_a, sol_b)


def test_inject_restores_previous_monkey():
    assert chaos.active() is None
    with chaos.inject(chaos.ChaosMonkey()) as mk:
        assert chaos.active() is mk
    assert chaos.active() is None


def test_speculative_chunks_bit_identical():
    batch = _batch(bsz=8)
    opts = SolveOptions(backend="xla", chunk_size=2)
    ref = dispatch.solve_canonical(batch, opts)
    sol = dispatch.solve_canonical(batch, opts.replace(speculation=True))
    _assert_identical(ref, sol)
    # ... and still under injected per-round delay (the straggler case
    # speculation exists for).
    with chaos.inject(chaos.ChaosMonkey(delay_s=0.002)):
        slow = dispatch.solve_canonical(
            batch, opts.replace(speculation=True)
        )
    _assert_identical(ref, slow)


def test_options_validate_robustness_knobs():
    with pytest.raises(ValueError):
        SolveOptions(retry_budget=-1)
    with pytest.raises(ValueError):
        SolveOptions(retry_backoff=-0.5)

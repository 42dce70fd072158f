"""Serve-loop degradation under injected faults (LPEngine robustness).

The continuous-batching engine must degrade, not die: a transient
dispatch fault is absorbed by the round-level retry
(``dispatch_round_safe``); a fault that exhausts the per-round retry
budget retires only ITS shape-class group through the dead-letter path
— tickets complete with ``NUMERICAL`` status — while every other group
keeps advancing and stays bit-identical to the fault-free run.
Poisoned input never reaches a dispatch at all: ``submit`` validates at
the host boundary, naming the offending field.
"""

import numpy as np
import pytest

from repro import SolveOptions
from repro.core.lp import NUMERICAL, OPTIMAL
from repro.core.problem import LPProblem
from repro.runtime import chaos
from repro.serve.engine import LPEngine


def _problem(n, m, seed):
    rng = np.random.default_rng(seed)
    a = rng.uniform(-1.0, 1.0, size=(1, m, n))
    for j in range(min(m, n)):
        a[:, j, j] = abs(a[:, j, j]) + 1.0
    b = rng.uniform(1.0, 10.0, size=(1, m))
    c = rng.uniform(0.1, 1.0, size=(1, n))
    return LPProblem.make(c=c, a=a, bu=b)


def _run_engine(monkey=None, retry_budget=2):
    """Two shape classes, three LPs each; returns (engine, results)."""
    opts = SolveOptions(
        backend="xla", retry_budget=retry_budget, retry_backoff=0.0
    )
    eng = LPEngine(opts, flush_every=10**9, step_iters=8)
    tickets = [eng.submit(_problem(4, 6, s)) for s in range(3)]
    tickets += [eng.submit(_problem(6, 9, 10 + s)) for s in range(3)]
    ctx = chaos.inject(monkey) if monkey is not None else None
    if ctx is not None:
        ctx.__enter__()
    try:
        for _ in range(200):
            eng.step()
            if all(eng.done(t) for t in tickets):
                break
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
    return eng, [eng.result(t) for t in tickets]


# -- submit validation ----------------------------------------------------


def test_submit_rejects_nan_payload_naming_field():
    eng = LPEngine(SolveOptions(backend="xla"), flush_every=10**9)
    bad = LPProblem.make(
        c=np.array([[1.0, np.nan]]),
        a=np.ones((1, 2, 2)),
        bu=np.ones((1, 2)),
        validate=False,
    )
    with pytest.raises(ValueError, match=r"submit: problem\.c contains NaN"):
        eng.submit(bad)
    assert eng.pending_count == 0  # rejected before a ticket existed


def test_submit_rejects_bad_deadline():
    eng = LPEngine(SolveOptions(backend="xla"), flush_every=10**9)
    p = _problem(4, 6, 0)
    with pytest.raises(ValueError, match="deadline"):
        eng.submit(p, deadline=-1.0)
    with pytest.raises(ValueError, match="deadline"):
        eng.submit(p, deadline=float("nan"))
    assert eng.pending_count == 0


# -- group isolation + dead-letter ---------------------------------------


def test_fault_isolated_to_one_group_dead_letters():
    ref_eng, ref = _run_engine()
    assert all(int(s.status[0]) == OPTIMAL for s in ref)

    # Budget 0 + exactly one injected fault: the first group's round
    # fails once and dead-letters; the other group never sees a fault.
    monkey = chaos.ChaosMonkey(error_rate=1.0, max_faults=1)
    eng, out = _run_engine(monkey, retry_budget=0)
    assert monkey.faults_injected == 1
    assert len(eng.dead_letters) == 3
    assert eng.stats.dead_lettered == 3
    numerical = [i for i, s in enumerate(out) if int(s.status[0]) == NUMERICAL]
    assert len(numerical) == 3
    for i in numerical:
        assert np.isnan(float(out[i].objective[0]))
        assert np.all(np.asarray(out[i].x) == 0.0)
    # The surviving group is bit-identical to the fault-free run.
    for i, (r, o) in enumerate(zip(ref, out)):
        if i in numerical:
            continue
        np.testing.assert_array_equal(
            np.asarray(r.objective), np.asarray(o.objective)
        )
        np.testing.assert_array_equal(np.asarray(r.x), np.asarray(o.x))
        np.testing.assert_array_equal(
            np.asarray(r.iterations), np.asarray(o.iterations)
        )


def test_group_retry_recovers_bit_identical():
    _, ref = _run_engine()
    monkey = chaos.ChaosMonkey(error_rate=1.0, max_faults=2)
    eng, out = _run_engine(monkey, retry_budget=2)
    assert eng.stats.dead_lettered == 0
    assert eng.stats.retries == 2
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(
            np.asarray(r.objective), np.asarray(o.objective)
        )
        np.testing.assert_array_equal(np.asarray(r.x), np.asarray(o.x))
        np.testing.assert_array_equal(
            np.asarray(r.status), np.asarray(o.status)
        )
        np.testing.assert_array_equal(
            np.asarray(r.iterations), np.asarray(o.iterations)
        )


def test_poisoned_row_retires_numerical_in_serve_loop():
    """A NaN-poisoned carried-state row is caught by the per-round
    guardrail inside ``resume_round`` and retires as a NUMERICAL ticket;
    its groupmates keep solving and match the fault-free run."""
    _, ref = _run_engine()
    monkey = chaos.ChaosMonkey(poison_rows={0: (0,)})
    eng, out = _run_engine(monkey)
    assert monkey.rows_poisoned == 1
    assert eng.stats.dead_lettered == 0
    statuses = [int(s.status[0]) for s in out]
    assert statuses.count(NUMERICAL) == 1
    poisoned = statuses.index(NUMERICAL)
    assert np.isnan(float(out[poisoned].objective[0]))
    for i, (r, o) in enumerate(zip(ref, out)):
        if i == poisoned:
            continue
        np.testing.assert_array_equal(
            np.asarray(r.objective), np.asarray(o.objective)
        )
        np.testing.assert_array_equal(np.asarray(r.x), np.asarray(o.x))


def test_dead_letter_keeps_engine_serviceable():
    """After a dead-lettered group the engine still serves new work."""
    monkey = chaos.ChaosMonkey(error_rate=1.0, max_faults=1)
    eng, _ = _run_engine(monkey, retry_budget=0)
    t = eng.submit(_problem(4, 6, 99))
    sol = eng.result(t)
    assert int(sol.status[0]) == OPTIMAL


def test_engine_raises_on_kernel_compile_error(monkeypatch):
    """A kernel the compiler refuses propagates out of step(): no dead letters."""
    import jax

    from repro.kernels import ops

    def refuse(*args, **kw):
        raise jax.errors.JaxRuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel: Not implemented"
        )

    monkeypatch.setattr(ops, "simplex_solve", refuse)
    monkeypatch.setattr(ops, "simplex_resume", refuse)
    eng = LPEngine(
        SolveOptions(backend="pallas", retry_backoff=0.0),
        flush_every=10**9, step_iters=8,
    )
    eng.submit(_problem(4, 6, 0))
    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic failed to compile"):
        for _ in range(10):
            eng.step()
    assert eng.dead_letters == []
    assert eng.stats.dead_lettered == 0

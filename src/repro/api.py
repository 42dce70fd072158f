"""``repro.solve`` — the unified front-end over every solver path.

One functional entry point replaces the old ``BatchedLPSolver`` object:

    import repro
    from repro import LPProblem, SolveOptions

    # a batch of general-form LPs (one shape)
    sol = repro.solve(LPProblem.make(c, a, bl=bl, bu=bu, lo=lo, hi=hi,
                                     maximize=False))

    # a heterogeneous list — bucketed by shape class, megabatched,
    # results scattered back in input order
    sols = repro.solve([p1, p2, p3], options=SolveOptions(backend="pallas"))

    # an already-canonical LPBatch (max c.x, Ax <= b, x >= 0)
    sol = repro.solve(LPBatch(a, b, c))

Routing:

  * ``LPProblem``  -> hyperbox closed form when ``boxlike`` (no general
    rows, finite box), else canonicalize -> chunked dispatch ->
    uncanonicalize back to user coordinates.
  * ``list/tuple`` of ``LPProblem`` -> shape bucketing (core/bucketing.py),
    one solve per bucket, per-problem single-LP solutions in input order.
  * ``LPBatch``    -> straight to the chunked dispatch (no mapping).
  * ``SharedLPBatch`` (one A, batched c/b) -> the chunked dispatch on
    the shared revised-simplex backends (``xla-shared`` /
    ``pallas-shared``), which keep only per-LP basis state and read the
    constraint matrix from a single broadcast buffer.

``mesh`` shards the batch dimension across the mesh's data axes; all solver
knobs live in the frozen ``SolveOptions`` record (core/backends.py).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from .core import dispatch as _dispatch
from .core.backends import SolveOptions, SolveStats
from .core.bucketing import ShapeGrid, bucket_problems, scatter_solutions
from .core.lp import INFEASIBLE, LPBatch, LPSolution, SharedLPBatch
from .core.problem import LPProblem, canonicalize, solve_box, uncanonicalize
from .runtime import trace as _trace

Solvable = Union[LPProblem, LPBatch, SharedLPBatch, Sequence[LPProblem]]


def solve(
    problem: Solvable,
    options: Optional[SolveOptions] = None,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axes: Sequence[str] = ("data",),
    grid: Optional[ShapeGrid] = None,
    stats: Optional[SolveStats] = None,
) -> Union[LPSolution, List[LPSolution]]:
    """Solve general-form LP problem(s); see module docstring for routing.

    Parameters
    ----------
    problem : LPProblem | LPBatch | sequence of LPProblem
        One batched general-form problem, one canonical batch, or a
        heterogeneous list (bucketed by shape class and megabatched).
        ``LPProblem.basis0`` / ``LPBatch.basis0`` warm-start the simplex
        where the carrying backend supports it.
    options : SolveOptions, optional
        All solver/pipeline knobs — backend, pivot rule, iteration caps,
        ``chunk_size`` (overlapped chunking), ``compaction`` +
        ``compact_every`` (convergence compaction), ``first_cap`` (legacy
        two-pass).  Defaults to ``SolveOptions()``.
    mesh : jax.sharding.Mesh, optional
        Shard the batch dimension across the mesh's ``batch_axes``.
    batch_axes : sequence of str, default ("data",)
        Mesh axis names eligible to shard the batch dimension.
    grid : sequence of (int, int), optional
        Caller-pinned shape classes for list inputs (see
        ``core.bucketing.shape_class``).
    stats : SolveStats, optional
        Opt-in counters (LPs, dispatch rounds, simplex iterations,
        warm-started LPs) accumulated across every dispatch this call
        performs.

    Returns
    -------
    LPSolution or list of LPSolution
        One ``LPSolution`` for a single ``LPProblem``/``LPBatch`` input;
        a list of single-LP ``LPSolution``s in input order for a list
        input.

    Raises
    ------
    TypeError
        For any other input type.

    Notes
    -----
    Under ``jax.profiler`` the call is one ``repro.solve`` span, with
    the front end's and the dispatch's spans inside it
    (``repro.runtime.trace``).
    """
    if isinstance(problem, (LPBatch, SharedLPBatch)):
        with _trace.span("solve", kind="batch"):
            return _dispatch.solve_canonical(
                problem, options, mesh=mesh, batch_axes=batch_axes, stats=stats
            )
    if isinstance(problem, LPProblem):
        with _trace.span("solve", kind="problem"):
            return _solve_problem(problem, options, mesh, batch_axes, stats)
    if isinstance(problem, (list, tuple)):
        with _trace.span("solve", kind="list"):
            return _solve_many(problem, options, mesh, batch_axes, grid, stats)
    raise TypeError(
        f"repro.solve expects LPProblem, LPBatch, SharedLPBatch, or a "
        f"list of LPProblem; got {type(problem).__name__}"
    )


def solve_hyperbox(
    lo,
    hi,
    directions,
    options: Optional[SolveOptions] = None,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axes: Sequence[str] = ("data",),
    stats: Optional[SolveStats] = None,
) -> LPSolution:
    """Support of the box [lo, hi] in each direction (paper Sec. 6).

    Parameters
    ----------
    lo, hi : array_like
        Box bounds, broadcastable to ``directions``' shape ``(B, n)``.
    directions : array_like
        (B, n) objective directions, one closed-form LP per row.
    options : SolveOptions, optional
        Backend selection; iteration knobs are irrelevant here.
    mesh, batch_axes
        As for :func:`solve`.
    stats : SolveStats, optional
        Counters to accumulate into (box LPs do 0 iterations).

    Returns
    -------
    LPSolution
        Support values in ``objective``, maximizing vertices in ``x``.
    """
    return _dispatch.solve_hyperbox(
        lo, hi, directions, options, mesh=mesh, batch_axes=batch_axes, stats=stats
    )


def _solve_problem(
    problem: LPProblem,
    options: Optional[SolveOptions],
    mesh,
    batch_axes: Sequence[str],
    stats: Optional[SolveStats] = None,
) -> LPSolution:
    if problem.batch == 0:
        return _dispatch.empty_solution(problem.n, problem.dtype)
    if problem.boxlike:
        # No general rows + finite box: closed form, no simplex. The jnp
        # closed form (solve_box) is already a single fused op; a non-default
        # backend routes through its registered hyperbox kernel instead
        # ("auto" counts as default: the routing frontier is about
        # iteration cost, which a closed-form solve does not have).
        if options is None or options.backend in ("xla", "auto"):
            sol = solve_box(problem)
            if stats is not None:
                stats.record(sol)
            return sol
        return _solve_box_via_backend(problem, options, mesh, batch_axes, stats)
    with _trace.span("frontend.canonicalize", rows=problem.batch):
        canon = canonicalize(problem)
    sol = _dispatch.solve_canonical(
        canon.batch, options, mesh=mesh, batch_axes=batch_axes, stats=stats
    )
    return uncanonicalize(canon, sol)


def _solve_box_via_backend(
    problem: LPProblem,
    options: SolveOptions,
    mesh,
    batch_axes: Sequence[str],
    stats: Optional[SolveStats] = None,
) -> LPSolution:
    """Boxlike solve through the backend's hyperbox kernel (sign-adjusted).

    The kernel maximizes, so minimize flips the direction; the objective is
    re-evaluated as c.x in user space and empty boxes report INFEASIBLE
    (kernels assume lo <= hi).
    """
    sign = 1.0 if problem.maximize else -1.0
    sol = _dispatch.solve_hyperbox(
        problem.lo, problem.hi, sign * problem.c, options,
        mesh=mesh, batch_axes=batch_axes, stats=stats,
    )
    infeasible = jnp.any(problem.lo > problem.hi, axis=-1)
    bad = -jnp.inf if problem.maximize else jnp.inf
    objective = jnp.where(
        infeasible, bad, jnp.sum(problem.c * sol.x, axis=-1)
    )
    x = jnp.where(infeasible[:, None], 0.0, sol.x)
    status = jnp.where(infeasible, INFEASIBLE, sol.status).astype(jnp.int32)
    return LPSolution(
        objective=objective, x=x, status=status, iterations=sol.iterations
    )


def _solve_many(
    problems: Sequence[LPProblem],
    options: Optional[SolveOptions],
    mesh,
    batch_axes: Sequence[str],
    grid: Optional[ShapeGrid],
    stats: Optional[SolveStats] = None,
) -> List[LPSolution]:
    if not problems:
        return []
    with _trace.span("frontend.bucket", rows=len(problems)):
        buckets = bucket_problems(problems, grid)
    sols = [
        _solve_problem(b.problem, options, mesh, batch_axes, stats)
        for b in buckets
    ]
    with _trace.span("frontend.scatter", rows=len(problems)):
        return scatter_solutions(buckets, sols, len(problems))

"""Persistent solve sessions: compile-once serving and swept workloads.

Two steady-state workloads dominate the library's traffic profile:

  * **serving** (``serve/engine.py:LPEngine``) — an endless stream of
    heterogeneous problems, bucketed into recurring power-of-two shape
    classes.  Once every class has been seen, no call should compile
    anything: :class:`SolveSession` pins the options, funnels every solve
    through one ``SolveStats`` record, and makes the contract observable
    via the ``compiles`` / ``cache_hits`` counters the dispatch layer
    maintains.

  * **sweeps** (``core/support.py:Polytope.support_sweep``) — the SAME
    polytope evaluated in S slowly-rotating direction batches, each step
    warm-started from the previous step's optimal basis.  A python loop
    pays per-step dispatch overhead S times (the 27x steady-state
    regression of BENCH_compaction.json); :func:`sweep_problems` instead
    compiles the WHOLE sweep once — ``lax.scan`` over steps, the step
    body being exactly the canonicalize -> lockstep-solve ->
    uncanonicalize pipeline the python path runs — so a steady-state
    sweep is one executable call with zero per-step host work.

Both reuse the shape-class discipline of ``core/bucketing.py``: a
session's executables are keyed by padded shape class, and a sweep is one
shape class by construction.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import engine as _engine
from . import simplex as _simplex
from .backends import SolveOptions, SolveStats
from .bucketing import ShapeGrid
from .lp import LPBatch, LPSolution, OPTIMAL, build_tableau
from .problem import LPProblem, canonicalize, uncanonicalize
from .tableau import TableauSpec


class SolveSession:
    """A pinned-options solve context that makes executable reuse observable.

    Wraps :func:`repro.solve` with a fixed ``SolveOptions`` / mesh / shape
    grid and one persistent :class:`SolveStats` record, so a serving loop
    can assert its steady state ("after warm-up, ``stats.compiles`` stops
    moving and only ``cache_hits`` grow").  The executable cache itself is
    process-wide (JAX's jit cache keyed by shape class and static
    options), so sessions are cheap: create one per traffic profile.

    Parameters
    ----------
    options : SolveOptions, optional
        Pinned solver configuration for every call.
    mesh : jax.sharding.Mesh, optional
        Mesh for batch-dimension sharding, as for :func:`repro.solve`.
    grid : sequence of (int, int), optional
        Caller-pinned shape classes for list inputs
        (``core.bucketing.shape_class``); None = power-of-two classes.
    stats : SolveStats, optional
        The record to accumulate into; a fresh one is created by default.
    """

    def __init__(
        self,
        options: Optional[SolveOptions] = None,
        *,
        mesh: Optional[jax.sharding.Mesh] = None,
        grid: Optional[ShapeGrid] = None,
        stats: Optional[SolveStats] = None,
    ):
        self.options = options or SolveOptions()
        self.mesh = mesh
        self.grid = grid
        self.stats = stats if stats is not None else SolveStats()
        # Tuned-config pins: shape class -> resolved options.  A session
        # pays the autotuner (cost-model ranking, and under
        # autotune="trial" the micro-trials) ONCE per shape class; every
        # later round/admission of that class reuses the pinned record.
        self._pinned: Dict[tuple, SolveOptions] = {}

    def solve(
        self, problem: Union[LPProblem, LPBatch, Sequence[LPProblem]]
    ) -> Union[LPSolution, List[LPSolution]]:
        """Solve through the pinned configuration, recording into ``stats``."""
        from .. import api  # lazy: api imports this package

        return api.solve(
            problem,
            self.options,
            mesh=self.mesh,
            grid=self.grid,
            stats=self.stats,
        )

    def solve_hyperbox(self, lo, hi, directions) -> LPSolution:
        """Box-LP batch through the pinned configuration (paper Sec. 6)."""
        from . import dispatch as _dispatch

        return _dispatch.solve_hyperbox(
            lo, hi, directions, self.options, mesh=self.mesh, stats=self.stats
        )

    # -- continuous-batching primitives (serve/engine.py) -------------------
    #
    # The serve loop advances each shape class one capped dispatch round
    # per scheduler step, splicing newly admitted LPs (as iteration-0
    # states) into the round alongside the carried survivors.  These three
    # methods are that loop's entire solver surface, pinned to the
    # session's options/mesh/stats so its steady state stays observable
    # through the same compiles/cache_hits counters as flush-mode serving.

    def resolve_options(
        self, m: int, n: int, dtype, batch: Optional[int] = None
    ) -> SolveOptions:
        """The pinned options with the open config knobs resolved for a shape.

        One resolution per canonical shape class, at admission — every
        subsequent round of that class runs the same concrete backend
        (mixing drivers mid-solve would break the resume-state contract).
        The resolved record is memoized per shape class for the session's
        lifetime, so the autotuner (``runtime/autotune.py``) prices —
        and, in trial mode, micro-benchmarks — each class at most once
        per session.
        """
        from . import dispatch as _dispatch
        from .bucketing import next_pow2

        key = (m, n, np.dtype(dtype).name, next_pow2(batch) if batch else 0)
        hit = self._pinned.get(key)
        if hit is not None:
            return hit
        resolved = _dispatch.resolve_backend(
            m, n, dtype, self.options, batch=batch, stats=self.stats
        )
        self._pinned[key] = resolved
        return resolved

    def init_state(self, batch: LPBatch, options: Optional[SolveOptions] = None):
        """Iteration-0 resume state for a canonical batch (the splice input).

        Uses the backend's ``init_canonical`` hook — resuming the returned
        state for ``K`` steps is bit-identical to a cold solve with cap
        ``K`` — and attributes the hook's compile-cache delta to
        ``stats`` like any dispatch.

        Parameters
        ----------
        batch : LPBatch
            Canonical rows to materialize (may carry ``basis0``).
        options : SolveOptions, optional
            Resolved (concrete-backend) options for the batch's shape
            class; defaults to the session options, which must then name
            a concrete backend.
        """
        from .backends import get_backend

        options = options or self.options
        backend = get_backend(options.backend)
        if backend.init_canonical is None:
            raise ValueError(
                f"backend {backend.name!r} has no init_canonical hook; "
                "it cannot splice new LPs into in-flight rounds"
            )
        before = backend.cache_size() if backend.cache_size else None
        state = backend.init_canonical(batch, options)
        if before is not None:
            self.stats.record_cache(before, backend.cache_size())
        return state

    def resume_round(
        self,
        batch: LPBatch,
        state,
        cap: int,
        options: Optional[SolveOptions] = None,
        size_class: Optional[int] = None,
    ):
        """One capped continuation round through the dispatch primitive.

        Advances every LP of ``batch`` by at most ``cap`` ADDITIONAL
        iterations from ``state``, returning ``(LPSolution, new_state)``
        with the round's incremental iteration counts.  ``size_class``
        pads the batch to the scheduler's power-of-two class so rounds of
        different in-flight sizes reuse one executable.

        Runs through the fault-recovery wrapper
        (:func:`repro.core.dispatch.dispatch_round_safe`): a transient
        backend failure re-dispatches the same round from the same
        carried state, up to ``options.retry_budget`` times, before the
        error reaches the caller — the serve loop dead-letters a group
        whose round exhausts the budget (``serve/engine.py``).  When
        ``options.guardrails`` is on (the default), the round's solution
        passes :func:`repro.core.dispatch.apply_guardrails` on the way
        out: rows whose carried state or claimed-optimal answer went
        non-finite return as ``NUMERICAL`` instead of carrying NaNs
        forward.

        Parameters
        ----------
        batch : LPBatch
            The canonical rows (full data — the pdhg backend re-reads
            ``a`` every step; the simplex backends only ``b``/``c``).
        state
            The carried resume state, row-aligned with ``batch``.
        cap : int
            The round's incremental iteration budget (> 0).
        options : SolveOptions, optional
            Resolved options for the class; defaults to session options.
        size_class : int, optional
            Power-of-two pad target for the batch dimension.
        """
        from . import dispatch as _dispatch

        base = (options or self.options).replace(
            max_iters=int(cap), compaction="off", first_cap=None, resume="scratch"
        )
        sol, out_state = _dispatch.dispatch_round_safe(
            batch,
            base,
            self.mesh,
            ("data",),
            self.stats,
            state=state,
            want_state=True,
            size_class=size_class,
        )
        if base.guardrails:
            sol = _dispatch.apply_guardrails(sol, out_state)
        self.stats.resumed += batch.batch
        return sol, out_state


# ---------------------------------------------------------------------------
# compiled warm-started sweeps
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "rule", "unroll", "tol", "layout",
        "maximize", "split", "row_lower", "var_upper",
    ),
)
def _sweep_jit(
    c_stack,  # (S, K, n) per-step user objectives
    a, bl, bu, lo, hi,  # (K, ...) problem data, constant across steps
    cap,  # () int32 traced iteration cap
    seed,
    *,
    rule, unroll, tol, layout, maximize, split, row_lower, var_upper,
):
    """The whole warm-started sweep as ONE executable: scan over steps.

    The step body mirrors the python path — construct the step's
    ``LPProblem`` (only ``c`` varies), ``canonicalize``, run the shared
    lockstep loop (``simplex._iterate``), ``uncanonicalize`` — but the
    warm start carries the previous step's TERMINAL TABLEAU, not just its
    basis: the constraints never change across a sweep, so the carried
    body rows stay valid verbatim and only the objective row needs
    re-pricing for the new costs (``engine.phase2_objective``).  That
    replaces the per-step ``B^-1 [b | A | I]`` rebuild (a batched
    ``linalg.solve``) with one dot product — the same optimum, reached
    from the same vertex, minus the rebuild cost.  LPs whose previous
    step did not converge fall back to the cold two-phase start.
    """

    def body(carry, c_s):
        prev_tab, prev_basis, warm = carry
        prob = LPProblem(
            c=c_s, a=a, bl=bl, bu=bu, lo=lo, hi=hi, basis0=None,
            maximize=maximize, split=split, boxlike=False,
            row_lower=row_lower, var_upper=var_upper,
        )
        canon = canonicalize(prob)
        ac, bc, cc = canon.batch.a, canon.batch.b, canon.batch.c
        m = ac.shape[1]
        spec = TableauSpec(m, ac.shape[2], layout)
        cold_tab, cold_basis, cold_phase = build_tableau(ac, bc, cc, spec=spec)
        c_ext = _simplex._phase2_costs(cc, spec)
        # Re-price the carried tableau's objective row for this step's
        # costs; body rows are reused as-is (same constraints).
        warm_obj = _engine.phase2_objective(
            prev_tab, prev_basis[:, :, None], spec, c_ext, gather=True
        )
        warm_tab = prev_tab.at[:, m, :].set(warm_obj[:, 0, :])
        tab = jnp.where(warm[:, None, None], warm_tab, cold_tab)
        basis = jnp.where(warm[:, None], prev_basis, cold_basis)
        phase = jnp.where(warm, 2, cold_phase)
        sol, state = _simplex._iterate(
            tab, basis, phase, c_ext, _engine.phase1_feasibility_tol(bc),
            cap, seed, spec=spec, rule=rule, unroll=unroll, tol=tol,
            static_cap=None,
        )
        out = uncanonicalize(canon, sol)
        # Carry only states of LPs that actually converged; the rest
        # cold-start next step (same gating as the python path).
        nxt = (state.tab, state.basis, sol.status == OPTIMAL)
        return nxt, (out.objective, sol.status, sol.iterations, warm.sum())

    k = c_stack.shape[1]
    prob0 = LPProblem(
        c=c_stack[0], a=a, bl=bl, bu=bu, lo=lo, hi=hi, basis0=None,
        maximize=maximize, split=split, boxlike=False,
        row_lower=row_lower, var_upper=var_upper,
    )
    batch0 = canonicalize(prob0).batch
    spec0 = TableauSpec(batch0.m, batch0.n, layout)
    carry0 = (
        jnp.zeros((k, batch0.m + 1, spec0.q), c_stack.dtype),
        jnp.zeros((k, batch0.m), jnp.int32),
        jnp.zeros((k,), bool),
    )
    _, (objs, statuses, iters, warm_counts) = jax.lax.scan(body, carry0, c_stack)
    return objs, statuses, iters, warm_counts


def sweep_compile_cache_size() -> int:
    """Compiled sweep executables so far (the session observability hook)."""
    return int(_sweep_jit._cache_size())


def sweep_supported(options: SolveOptions) -> bool:
    """Whether :func:`sweep_problems` can honor the given options.

    The compiled sweep drives the XLA lockstep core directly, so it
    covers exactly the configurations the plain python sweep would lower
    to a single uncompacted ``xla`` dispatch per step.  ``backend="auto"``
    counts as ``xla`` here: a sweep is a warm-started simplex workload by
    construction (each step pivots from the previous step's vertex — a
    first-order method has no vertex to carry), so the routing directive
    pins to the simplex leg rather than consulting the shape frontier.
    """
    return (
        options.backend in ("xla", "auto")
        and options.compaction == "off"
        and options.first_cap is None
        and options.chunk_size is None
        and options.dynamic_caps
    )


def sweep_problems(
    template: LPProblem,
    c_stack,
    options: Optional[SolveOptions] = None,
    stats: Optional[SolveStats] = None,
):
    """Warm-started sweep over problems differing only in their objective.

    Parameters
    ----------
    template : LPProblem
        The step-0 problem batch (any general form, batch K).  Every
        step reuses its rows/bounds/static flags; only ``c`` changes.
    c_stack : array_like
        ``(S, K, n)`` per-step objectives (``c_stack[0]`` should equal
        ``template.c`` for the usual sweep semantics, but any stack is
        accepted).
    options : SolveOptions, optional
        Must satisfy :func:`sweep_supported`; defaults do.
    stats : SolveStats, optional
        Accumulates the same counters the per-step python path records —
        per step: K LPs, one round, the step's simplex/lockstep
        iterations, warm-started LPs — plus the sweep-level
        ``compiles``/``cache_hits`` attribution.

    Returns
    -------
    jnp.ndarray
        ``(S, K)`` objective values in user coordinates.  Each step
        reaches the same optimum as solving it through
        :func:`repro.solve` with the previous step's basis, but from a
        tableau carried verbatim rather than rebuilt from the basis, so
        values can differ from the python path at float level (and, on a
        degenerate optimum, a different optimal vertex may be reported).

    Raises
    ------
    ValueError
        If the options demand a configuration the compiled sweep cannot
        honor (use the python path in ``Polytope.support_sweep`` then).
    """
    options = options or SolveOptions()
    if not sweep_supported(options):
        raise ValueError(
            "sweep_problems supports the plain xla path only "
            "(no compaction/two-pass/chunking); got incompatible options"
        )
    c_stack = jnp.asarray(c_stack, template.dtype)
    canon0 = canonicalize(template)  # fixes the canonical shape (m', n')
    k = template.batch
    cap = _simplex.resolve_cap(options.max_iters, canon0.batch.m, canon0.batch.n)
    tol = options.tolerance
    if tol <= 0.0:
        tol = _engine.default_tolerance(template.dtype)

    before = sweep_compile_cache_size() if stats is not None else 0
    objs, statuses, iters, warm_counts = _sweep_jit(
        c_stack,
        template.a, template.bl, template.bu, template.lo, template.hi,
        jnp.int32(cap),
        options.seed,
        rule=options.rule,
        unroll=options.unroll,
        tol=tol,
        layout=options.effective_layout,
        maximize=template.maximize,
        split=template.split,
        row_lower=template.row_lower,
        var_upper=template.var_upper,
    )
    if stats is not None:
        stats.record_cache(before, sweep_compile_cache_size())
        it = np.asarray(iters)
        steps = it.shape[0]
        stats.lps += steps * k
        stats.rounds += steps
        stats.simplex_iterations += int(it.sum())
        stats.lockstep_iterations += int(it.max(axis=1).sum()) * k
        stats.warm_started += int(np.asarray(warm_counts).sum())
    return objs


def sweep_polytope_supports(
    a,
    b,
    direction_stack,
    options: Optional[SolveOptions] = None,
    stats: Optional[SolveStats] = None,
):
    """Support values of ``{x : Ax <= b, x free}`` over a direction sweep.

    The compiled counterpart of ``Polytope.support_sweep``'s python loop:
    one executable runs all S steps, carrying each step's optimal basis
    into the next (see :func:`sweep_problems`).

    Parameters
    ----------
    a, b : array_like
        Polytope rows ``(m, n)`` and bounds ``(m,)``.
    direction_stack : array_like
        ``(S, K, n)`` direction batches, swept in order.
    options, stats
        As for :func:`sweep_problems`.

    Returns
    -------
    jnp.ndarray
        ``(S, K)`` support values.
    """
    direction_stack = np.asarray(direction_stack)
    s, k, n = direction_stack.shape
    a = np.asarray(a)
    bu = np.asarray(b)
    template = LPProblem.make(
        c=direction_stack[0],
        a=np.broadcast_to(a, (k, *a.shape)),
        bu=np.broadcast_to(bu, (k, bu.shape[0])),
        lo=-np.inf,
        hi=np.inf,
        dtype=direction_stack.dtype,
    )
    return sweep_problems(template, direction_stack, options, stats)

"""Batched lockstep simplex in JAX — the paper's core technique, TPU-native.

The paper maps one CUDA block per LP and parallelizes tableau operations
across j >= q threads.  TPUs have no independent block scheduler, so the
TPU-native formulation is *lockstep batching*: a single ``lax.while_loop``
advances every LP in the batch by one simplex iteration per step, with all
tableau operations vectorized over the batch dimension (which lands on VPU
lanes).  Finished LPs are masked inactive; the loop exits when every LP has
terminated or the iteration cap is hit.

This module is a thin DRIVER: the pivot machinery itself — entering-column
selection for every rule, the min-ratio test with the degenerate-artificial
escape, the in-loop phase transition, the rank-1 pivot update, and solution
extraction — lives once in ``core/engine.py``, shared verbatim with the
Pallas kernel (``kernels/simplex_pallas.py``).  The loop here only owns
what is XLA-specific: the ``while_loop`` scaffolding, the unroll knob, and
status/iteration bookkeeping.

Compile-once dispatch: the iteration cap is a TRACED scalar, not a static
argument — the geometric round caps of the compaction scheduler
(``[k, 2k, 4k, ...]``) all execute the SAME compiled program per tableau
shape.  Two jit entry points exist per shape: :func:`solve_batched` (cold
start: build the tableau, iterate) and :func:`resume_batched` (continue a
carried :class:`~repro.core.lp.ResumeState` exactly where a previous
capped round stopped).  ``dynamic_cap=False`` restores the pre-traced
behavior (one executable per distinct cap) as a benchmark baseline.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import engine
from .engine import BLAND, LPC, RPC  # noqa: F401  (re-exported API)
from .lp import (
    ITER_LIMIT,
    LPBatch,
    LPSolution,
    RUNNING,
    ResumeState,
    UNBOUNDED,
    auto_cap,
    build_tableau,
)
from .tableau import DEFAULT_LAYOUT, TableauSpec


class _State(NamedTuple):
    tab: jnp.ndarray  # (B, m+1, q)
    basis: jnp.ndarray  # (B, m, 1) int32 column vector
    phase: jnp.ndarray  # (B, 1, 1) int32 (1 or 2)
    status: jnp.ndarray  # (B, 1, 1) int32
    iters: jnp.ndarray  # (B, 1, 1) int32
    step: jnp.ndarray  # () int32


def resolve_cap(max_iters, m: int, n: int):
    """The host-side 0 -> auto rule, shared by both driver entry points."""
    if isinstance(max_iters, (int, np.integer)):
        return auto_cap(m, n) if max_iters <= 0 else int(max_iters)
    return max_iters  # already a traced/array value


def _phase2_costs(c: jnp.ndarray, spec: TableauSpec) -> jnp.ndarray:
    """(B, 1, spec.q) extended phase-II cost row (zeros outside columns 1..n)."""
    bsz, n = c.shape
    return jnp.zeros((bsz, 1, spec.q), c.dtype).at[:, 0, 1 : 1 + n].set(c)


def _iterate(
    tab, basis, phase, c_ext, feas_tol, cap, seed, *,
    spec, rule, unroll, tol, static_cap
):
    """The lockstep iteration loop, shared by the cold and resume paths.

    ``cap`` is a traced int32 scalar unless ``static_cap`` overrides it
    with a trace-time constant (the ``dynamic_cap=False`` baseline).
    ``spec`` (static) names the tableau layout; the loop itself is
    layout-blind — every layout-sensitive step lives in the engine.
    Returns ``(LPSolution, ResumeState)`` — callers drop the state when
    they don't need it.
    """
    m, n = spec.m, spec.n
    bsz = tab.shape[0]
    dtype = tab.dtype
    limit = static_cap if static_cap is not None else cap

    elig = engine.eligible_mask(tab.shape[2], m, n)
    feas_tol = feas_tol[:, None, None]

    def cond(s: _State):
        return (s.step < limit) & jnp.any(s.status == RUNNING)

    def body(s: _State):
        active = s.status == RUNNING
        noise = (
            engine.rpc_noise(seed, s.step, 0, bsz, tab.shape[2], dtype)
            if rule == RPC
            else None
        )
        e, max_c = engine.select_entering(
            s.tab[:, m : m + 1, :], elig, rule, tol, noise
        )
        at_opt = max_c <= tol

        new_tab, new_phase, status = engine.phase_transition(
            s.tab, s.basis, s.phase, s.status, at_opt, c_ext, feas_tol, spec,
            gather=True,
        )

        pivoting = active & ~at_opt
        l, min_ratio, full_col = engine.ratio_test(new_tab, s.basis, e, spec, tol)
        unbounded = pivoting & (min_ratio >= engine.BIG / 2)
        status = jnp.where(unbounded, UNBOUNDED, status)
        do_pivot = pivoting & ~unbounded

        new_tab, new_basis = engine.pivot_update(
            new_tab, s.basis, e, l, full_col, do_pivot, spec, tol, gather=True
        )
        iters = s.iters + do_pivot.astype(jnp.int32)
        return _State(new_tab, new_basis, new_phase, status, iters, s.step + 1)

    init = _State(
        tab=tab,
        basis=basis[:, :, None],
        phase=phase[:, None, None],
        status=jnp.full((bsz, 1, 1), RUNNING, jnp.int32),
        iters=jnp.zeros((bsz, 1, 1), jnp.int32),
        step=jnp.asarray(0, jnp.int32),
    )
    if unroll > 1:
        # while_loop has no unroll knob; do it manually. Each inner body is
        # a no-op for terminated LPs (all updates are masked on RUNNING).
        inner = body

        def body(s: _State):  # noqa: F811
            for _ in range(unroll):
                s = inner(s)
            return s

    final = jax.lax.while_loop(cond, body, init)

    status = jnp.where(final.status == RUNNING, ITER_LIMIT, final.status)
    objective, x = engine.extract_solution(
        final.tab, final.basis, status, spec, n, fill=-jnp.inf
    )
    basis_out = final.basis[:, :, 0]
    sol = LPSolution(
        objective=objective[:, 0, 0],
        x=x[:, 0, :],
        status=status[:, 0, 0],
        iterations=final.iters[:, 0, 0],
        basis=basis_out,
    )
    return sol, ResumeState(final.tab, basis_out, final.phase[:, 0, 0])


def solve_traced(
    a, b, c, basis0, cap, seed, *, rule, unroll, tol, static_cap=None, spec=None
):
    """Pure traced cold solve: build the tableau, then iterate.

    The un-jitted composition shared by :func:`solve_batched` and the
    compiled sweep session (``core/session.py``), so both produce
    identical arithmetic.  ``tol`` must already be resolved (> 0) and
    ``cap`` is a traced scalar (or ``static_cap`` a constant).  ``spec``
    selects the tableau layout (None = the compact default).
    Returns ``(LPSolution, ResumeState)``.
    """
    bsz, m, n = a.shape
    if spec is None:
        spec = TableauSpec(m, n)
    tab, basis, phase = build_tableau(a, b, c, basis0, spec)
    c_ext = _phase2_costs(c, spec)
    feas_tol = engine.phase1_feasibility_tol(b)
    return _iterate(
        tab, basis, phase, c_ext, feas_tol, cap, seed,
        spec=spec, rule=rule, unroll=unroll, tol=tol, static_cap=static_cap,
    )


@functools.partial(
    jax.jit,
    static_argnames=("spec", "rule", "unroll", "tol", "want_state", "static_cap"),
)
def _solve_jit(
    a, b, c, basis0, cap, seed, *, spec, rule, unroll, tol, want_state, static_cap
):
    sol, state = solve_traced(
        a, b, c, basis0, cap, seed,
        rule=rule, unroll=unroll, tol=tol, static_cap=static_cap, spec=spec,
    )
    return (sol, state) if want_state else sol


@functools.partial(
    jax.jit,
    static_argnames=("spec", "rule", "unroll", "tol", "want_state", "static_cap"),
)
def _resume_jit(
    b, c, state, cap, seed, *, spec, rule, unroll, tol, want_state, static_cap
):
    c_ext = _phase2_costs(c, spec)
    feas_tol = engine.phase1_feasibility_tol(b)
    sol, out_state = _iterate(
        state.tab, state.basis, state.phase, c_ext, feas_tol, cap, seed,
        spec=spec, rule=rule, unroll=unroll, tol=tol, static_cap=static_cap,
    )
    return (sol, out_state) if want_state else sol


@functools.partial(jax.jit, static_argnames=("spec",))
def _init_jit(a, b, c, basis0, *, spec):
    tab, basis, phase = build_tableau(a, b, c, basis0, spec)
    return ResumeState(tab, basis, phase)


def compile_cache_size() -> int:
    """Number of XLA-driver executables compiled so far (cold + resume + init).

    The observability hook behind ``SolveStats.compiles`` /
    ``SolveStats.cache_hits`` for the ``xla`` backend: the dispatch layer
    reads it before and after each backend call and attributes the delta.
    """
    return (
        int(_solve_jit._cache_size())
        + int(_resume_jit._cache_size())
        + int(_init_jit._cache_size())
    )


def init_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    basis0: Optional[jnp.ndarray] = None,
    layout: str = DEFAULT_LAYOUT,
) -> ResumeState:
    """The iteration-0 :class:`ResumeState`: tableau built, nothing pivoted.

    The splice primitive of the continuous-batching serve loop
    (``serve/engine.py``): a newly admitted LP is materialized as a resume
    state so it can ride the SAME capped resume dispatch as the round's
    carried survivors.  Exactness: :func:`solve_traced` is literally
    ``build_tableau`` followed by the shared iteration loop, and
    :func:`resume_batched` re-derives the cost row and feasibility
    threshold from ``b``/``c`` the same way — so
    ``resume_batched(b, c, init_batched(a, b, c), max_iters=K)`` is
    bit-identical to ``solve_batched(a, b, c, max_iters=K)``, and a chain
    of resumed rounds whose budgets sum to ``K`` still is.

    Args:
      a, b, c: canonical batch ``(B, m, n)``, ``(B, m)``, ``(B, n)``.
      basis0: optional ``(B, m)`` warm-start basis (as for
        :func:`solve_batched`); feasible rows start in phase II.
      layout: tableau storage layout for the built state.
    """
    bsz, m, n = a.shape
    return _init_jit(a, b, c, basis0, spec=TableauSpec(m, n, layout))


def solve_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    rule: str = LPC,
    max_iters: int = 0,
    seed: int = 0,
    unroll: int = 1,
    tol: float = 0.0,
    basis0: Optional[jnp.ndarray] = None,
    want_state: bool = False,
    dynamic_cap: bool = True,
    layout: str = DEFAULT_LAYOUT,
) -> LPSolution:
    """Solve a batch of LPs (max c.x, Ax <= b, x >= 0) in lockstep.

    Args:
      a, b, c: (B, m, n), (B, m), (B, n).
      rule: "lpc" | "rpc" | "bland".
      max_iters: simplex iteration cap across both phases
        (default 50*(m+n), matching the oracle).  Passed to the compiled
        program as a TRACED scalar: different caps over the same tableau
        shape reuse one executable (the compile-once dispatch contract).
      seed: RPC-rule noise seed (ignored by the deterministic rules).
      unroll: while_loop body unroll factor (perf knob).
      tol: reduced-cost/pivot tolerance (0 = dtype default).
      basis0: optional (B, m) warm-start basis; feasible rows skip
        phase I entirely (see ``build_tableau``).
      want_state: also return the terminal :class:`ResumeState` —
        ``(LPSolution, ResumeState)`` — for round-resumed dispatch.
      dynamic_cap: False re-specializes the executable on the concrete
        cap value (the pre-compile-once behavior; benchmark baseline).
      layout: tableau storage layout, ``"compact"`` (default; artificial
        block implicit) or ``"dense"`` (the paper's explicit map).  Both
        produce bit-identical results; they differ only in memory and
        pivot-update flops (see ``core/tableau.py``).

    The returned ``LPSolution.basis`` holds the final basis, reusable as
    the next solve's ``basis0`` (warm-start sweeps, core/support.py).
    """
    bsz, m, n = a.shape
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(a.dtype)
    static_cap = None if dynamic_cap else int(cap)
    return _solve_jit(
        a, b, c, basis0, jnp.int32(cap if dynamic_cap else 0), seed,
        spec=TableauSpec(m, n, layout), rule=rule, unroll=unroll, tol=tol,
        want_state=want_state, static_cap=static_cap,
    )


def resume_batched(
    b: jnp.ndarray,
    c: jnp.ndarray,
    state: ResumeState,
    rule: str = LPC,
    max_iters: int = 0,
    seed: int = 0,
    unroll: int = 1,
    tol: float = 0.0,
    want_state: bool = True,
    dynamic_cap: bool = True,
):
    """Continue a batch from a carried :class:`ResumeState`.

    ``b``/``c`` are the same canonical arrays the interrupted solve used
    (they re-derive the phase-II costs and the phase-I feasibility
    threshold bit-identically); ``max_iters`` is the ADDITIONAL step
    budget for this round.  Returns ``(LPSolution, ResumeState)`` when
    ``want_state``, else just the solution.  Because the carried state is
    exact, a sequence of resumed rounds whose budgets sum to ``K`` ends
    bit-identical to one uninterrupted solve with cap ``K``.  The layout
    is recovered from the carried tableau itself
    (``TableauSpec.from_tableau``), so a resume always continues in the
    layout the interrupted solve used.
    """
    m = state.basis.shape[1]
    n = c.shape[-1]
    spec = TableauSpec.from_tableau(m, n, state.tab.shape[-1])
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(state.tab.dtype)
    static_cap = None if dynamic_cap else int(cap)
    return _resume_jit(
        b, c, state, jnp.int32(cap if dynamic_cap else 0), seed,
        spec=spec, rule=rule, unroll=unroll, tol=tol,
        want_state=want_state, static_cap=static_cap,
    )


def solve(batch: LPBatch, **kw) -> LPSolution:
    kw.setdefault("basis0", batch.basis0)
    return solve_batched(batch.a, batch.b, batch.c, **kw)

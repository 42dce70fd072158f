"""Batched revised simplex over a SHARED constraint matrix (XLA driver).

The tableau engines (``core/simplex.py``, ``kernels/simplex_pallas.py``)
carry O(m·n) state per LP because every LP owns a private tableau.  For
the paper's headline workloads — support sweeps, reachability, scenario
analysis — thousands of LPs share ONE ``A`` and differ only in ``c``
and/or ``b``, so the tableau replicates the same matrix B times.  This
module is the revised-simplex counterpart (the engine arXiv 2211.10979
identifies as the right choice once ``A`` is read-shared): per LP it
keeps only

* ``basis``  — (m,) basis column IDs (same convention as the tableau path),
* ``binv``   — (m, m) basis inverse, maintained by the SAME rank-1
  product-form update the tableau pivot applies to its columns,
* ``xb``     — (m,) current basic solution (the tableau's RHS column),
* ``phase``  — the two-phase flag,

and re-prices the reduced-cost row fresh each iteration: one shared
``(B, m) @ (m, n)`` contraction against the single broadcast ``A``
replaces the per-LP rank-1 sweep over O(n) tableau columns.  Stored
problem data drops from O(m·n) to O(m + n + m·n/B) bytes per LP and
iteration state from O(m·n) to O(m²).

Numerical relationship to the tableau path
------------------------------------------
The tableau's body columns ARE the ``B⁻¹``-images of the original
columns, maintained by exactly the rank-1 Gauss-Jordan update used here
on ``binv``/``xb`` — so the product-form numerics are the same family
the tableau engines already trust, and the ratio test / degenerate-
artificial escape / unboundedness certificate reuse the engine's
formulas verbatim.  Reduced costs are re-priced each iteration instead
of incrementally updated, which is *more* accurate (no drift
accumulation in the objective row).  Pivot trajectories therefore track
the tableau path's to floating-point reassociation, and statuses /
objectives match to tolerance (asserted in ``tests/test_revised.py``).

Sign convention: rows with ``b_i < 0`` are negated up front exactly as
``build_tableau`` does (``sgn = -1`` there, artificial basic), so the
iterated system is ``S[A|I]`` with ``S = diag(sgn)``; the cold basis
matrix is the identity in EITHER case (signed slack on ``b >= 0`` rows,
artificial on ``b < 0`` rows), hence cold ``binv = I`` with no solve.

The loop scaffolding (traced iteration cap, unroll knob, lockstep
masking, ITER_LIMIT bookkeeping) mirrors ``core/simplex.py`` so the
dispatch layer's compile-once / resume-exactly contracts carry over:
a chain of capped :func:`resume_batched` rounds is bit-identical to one
uninterrupted solve, because each iteration reads only the carried
``(binv, basis, xb, phase)`` and the unchanged ``(a, b, c)``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import engine
from .engine import LPC, RPC
from .lp import (
    INFEASIBLE,
    ITER_LIMIT,
    LPSolution,
    OPTIMAL,
    RUNNING,
    SharedLPBatch,
    UNBOUNDED,
)
from .simplex import resolve_cap


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RevisedResumeState:
    """Interrupted revised-simplex state — the shared-path resume record.

    Third implementation of the dispatch layer's resume protocol
    (registered pytree + ``batch`` property + ``take(idx)`` gather),
    alongside :class:`~repro.core.lp.ResumeState` and
    :class:`~repro.core.pdhg.PDHGResumeState`.  O(m²) per LP versus the
    tableau's O(m·n): the shared ``A`` is NOT carried — resume callers
    pass the canonical arrays back in, as they already do for ``b``/``c``.
    """

    binv: jnp.ndarray  # (B, m, m) basis inverse in the signed system
    basis: jnp.ndarray  # (B, m) int32 basis column IDs
    xb: jnp.ndarray  # (B, m) basic solution (>= 0)
    phase: jnp.ndarray  # (B,) int32 simplex phase (1 or 2)

    @property
    def batch(self) -> int:
        return self.basis.shape[0]

    def take(self, idx) -> "RevisedResumeState":
        """Gather state rows (compaction gather between rounds)."""
        return RevisedResumeState(
            self.binv[idx], self.basis[idx], self.xb[idx], self.phase[idx]
        )


class _RState(NamedTuple):
    binv: jnp.ndarray  # (B, m, m)
    basis: jnp.ndarray  # (B, m, 1) int32 column
    xb: jnp.ndarray  # (B, m, 1) column
    phase: jnp.ndarray  # (B, 1, 1) int32
    status: jnp.ndarray  # (B, 1, 1) int32
    iters: jnp.ndarray  # (B, 1, 1) int32
    step: jnp.ndarray  # () int32


def state_bytes_per_lp(m: int, n: int, dtype=jnp.float32) -> int:
    """Resident iteration-state bytes per LP: binv + xb floats, basis + phase ints."""
    item = jnp.dtype(dtype).itemsize
    return (m * m + m) * item + (m + 1) * 4


def stored_bytes_per_lp(m: int, n: int, batch: int, dtype=jnp.float32) -> float:
    """Stored problem-data bytes per LP: one shared ``A`` amortized over B rows."""
    item = jnp.dtype(dtype).itemsize
    return (m * n / batch + m + n) * item


def _signs(b: jnp.ndarray, dtype) -> jnp.ndarray:
    """(B, m) row signs: -1 on b<0 rows (negated, artificial basic), +1 else."""
    return jnp.where(b < 0, -1.0, 1.0).astype(dtype)


def _cold_state(a: jnp.ndarray, b: jnp.ndarray) -> RevisedResumeState:
    """The all-slack/artificial start: basis matrix = I, so binv = I, xb = |b|."""
    bsz, m = b.shape
    n = a.shape[1]
    dtype = a.dtype
    neg = b < 0
    art_start = 1 + n + m
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    basis = jnp.where(neg, art_start + row_ids, 1 + n + row_ids).astype(jnp.int32)
    binv = jnp.broadcast_to(jnp.eye(m, dtype=dtype), (bsz, m, m))
    xb = _signs(b, dtype) * b
    phase = jnp.where(jnp.any(neg, axis=1), 1, 2).astype(jnp.int32)
    return RevisedResumeState(binv, basis, xb, phase)


def _warm_state(
    a: jnp.ndarray, b: jnp.ndarray, basis0: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Factorize a proposed basis — the revised twin of ``_warm_tableau``.

    Same acceptance rule as the tableau path: every ID in range (1..n+m,
    no artificials), the factorization finite, and the implied basic
    solution primal feasible; rows failing any test fall back to the
    cold start (caller overlays on the ``ok`` mask).  The basis matrix
    is assembled in the UNSIGNED system ``[A|I]`` and the inverse
    converted to the signed system by column scaling
    (``(S·B)⁻¹ = B⁻¹·S``); ``xb = B⁻¹ b`` is identical either way.
    Because ``A`` is shared, the gather pulls per-LP columns from ONE
    (m, n+m) buffer — no (B, m, n) replication even at init time.
    """
    bsz, m = b.shape
    n = a.shape[1]
    dtype = a.dtype
    in_range = (basis0 >= 1) & (basis0 <= n + m)
    safe = jnp.where(in_range, basis0, 1).astype(jnp.int32)
    ai = jnp.concatenate([a, jnp.eye(m, dtype=dtype)], axis=1)  # (m, n+m) shared
    bmat = jnp.moveaxis(jnp.take(ai, safe - 1, axis=1), 1, 0)  # (B, m, m)
    eye = jnp.broadcast_to(jnp.eye(m, dtype=dtype), (bsz, m, m))
    binv_u = jnp.linalg.solve(bmat, eye)
    xb = jnp.einsum("bij,bj->bi", binv_u, b, precision=jax.lax.Precision.HIGHEST)
    sgn = _signs(b, dtype)
    binv = binv_u * sgn[:, None, :]  # column scaling into the signed system
    feas_tol = 1e-9 if dtype == jnp.float64 else 1e-6
    feas_tol = feas_tol * jnp.maximum(1.0, jnp.max(jnp.abs(b), axis=-1))
    finite = jnp.all(jnp.isfinite(binv_u), axis=(1, 2)) & jnp.all(
        jnp.isfinite(xb), axis=-1
    )
    feasible = jnp.all(xb >= -feas_tol[:, None], axis=-1)
    ok = jnp.all(in_range, axis=-1) & finite & feasible
    binv = jnp.where(jnp.isfinite(binv), binv, 0.0)
    xb = jnp.maximum(jnp.where(jnp.isfinite(xb), xb, 0.0), 0.0)
    return binv, safe, xb, ok


def init_traced(
    a: jnp.ndarray, b: jnp.ndarray, basis0: Optional[jnp.ndarray]
) -> RevisedResumeState:
    """Iteration-0 state: cold start with the warm overlay where ``ok``."""
    cold = _cold_state(a, b)
    if basis0 is None:
        return cold
    wbinv, wbasis, wxb, ok = _warm_state(a, b, basis0)
    return RevisedResumeState(
        jnp.where(ok[:, None, None], wbinv, cold.binv),
        jnp.where(ok[:, None], wbasis, cold.basis),
        jnp.where(ok[:, None], wxb, cold.xb),
        jnp.where(ok, 2, cold.phase).astype(jnp.int32),
    )


def _basic_costs(
    basis: jnp.ndarray,
    phase: jnp.ndarray,
    c: jnp.ndarray,
    m: int,
    n: int,
    gather: bool = True,
):
    """(B, m, 1) cost of each basic variable under the CURRENT phase.

    ``basis``: (B, m, 1) column; ``phase``: (B, 1, 1); ``c``: (B, 1, n)
    row — the rank-preserving orientation of ``core/engine.py``.
    Phase I: -1 per basic artificial (ID >= 1+n+m), 0 else.  Phase II:
    ``c[j]`` for original variables, 0 for slacks — and 0 for a
    still-basic degenerate artificial, matching ``phase2_objective``'s
    pricing of it under both layouts.  ``gather=False`` selects the
    one-hot form (Mosaic-friendly; one nonzero term, so the sum is the
    bitwise-same float the gather reads).
    """
    dtype = c.dtype
    art_start = 1 + n + m
    cb1 = -(basis >= art_start).astype(dtype)
    is_var = (basis >= 1) & (basis <= n)
    if gather:
        cvals = jnp.take_along_axis(c, jnp.clip(basis - 1, 0, n - 1), axis=2)
    else:
        hit = basis - 1 == engine.column_ids(n)  # (B, m, n)
        cvals = jnp.sum(jnp.where(hit, c, 0.0), axis=2, keepdims=True)
    cb2 = jnp.where(is_var, cvals, 0.0)
    return jnp.where(phase == 1, cb1, cb2)


def _price(w: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """Row (B, 1, m) times the shared (m, n) ``A`` -> row (B, 1, n).

    ONE 2-D GEMM for the whole batch on every target (the same form in
    both drivers, so they round identically); ``HIGHEST`` keeps XLA's and
    Mosaic's TPU matmuls at float32 precision instead of one bfloat16
    pass.
    """
    bsz, _, m = w.shape
    out = jax.lax.dot_general(
        w.reshape(bsz, m), a, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=a.dtype,
    )
    return out.reshape(bsz, 1, a.shape[1])


def iteration_step(
    a,
    c,
    sgn,
    feas_tol,
    s: _RState,
    *,
    rule: str,
    tol: float,
    seed: int,
    row0=0,
    gather: bool = True,
) -> _RState:
    """One lockstep revised-simplex iteration over the whole batch.

    The single iteration body shared by the XLA driver (:func:`_iterate`,
    ``gather=True``) and the Pallas kernel
    (``kernels/revised_pallas.py``, ``gather=False`` — one-hot forms
    only, same floats) — the revised counterpart of the
    ``core/engine.py`` blocks both tableau drivers share.  ``row0`` is
    the batch-row base keying the RPC noise, so a tiled kernel draws
    bitwise the same noise as the untiled XLA path.

    Orientation (``core/engine.py``): ``binv`` (B, m, m); ``basis`` and
    ``xb`` (B, m, 1) columns over basis rows; ``c`` (B, 1, n) and the
    constraint signs ``sgn`` (B, 1, m) rows; per-LP scalars (B, 1, 1).
    """
    m, n = a.shape
    bsz = c.shape[0]
    dtype = a.dtype
    art_start = 1 + n + m
    rows = engine.row_ids(m)

    active = s.status == RUNNING
    p1 = s.phase == 1

    # Pricing: y = c_B . B^-1, then ONE shared GEMM against A.
    cb = _basic_costs(s.basis, s.phase, c, m, n, gather=gather)  # (B, m, 1)
    y = jnp.sum(cb * s.binv, axis=1, keepdims=True)  # (B, 1, m)
    w = y * sgn
    priced = _price(w, a)  # (B, 1, n): every LP reads the SAME A
    r_vars = jnp.where(p1, 0.0, c) - priced
    r_slack = -w
    obj0 = -jnp.sum(cb * s.xb, axis=1, keepdims=True)  # == tab[m, 0] (-z slot)

    # The reduced-cost row is [obj0 | r_vars | r_slack] over q = 1 + n + m
    # columns; only vars and slacks are eligible, so select per segment.
    noises = (
        [
            engine.rpc_noise(seed, s.step, row0, bsz, n, dtype, col_offset=1),
            engine.rpc_noise(seed, s.step, row0, bsz, m, dtype, col_offset=1 + n),
        ]
        if rule == RPC
        else None
    )
    e, max_c = engine.select_entering_segments(
        [r_vars, r_slack], [1, 1 + n], rule, tol, noises
    )
    at_opt = max_c <= tol

    # Phase transition — no objective-row rewrite needed: pricing is
    # recomputed from (basis, phase) next iteration anyway.
    p1_done = active & at_opt & p1
    feasible = obj0 <= feas_tol
    status = jnp.where(p1_done & ~feasible, INFEASIBLE, s.status)
    status = jnp.where(active & at_opt & (s.phase == 2), OPTIMAL, status)
    new_phase = jnp.where(p1_done & feasible, 2, s.phase)

    # Entering column u = B^-1 . (S M_e): pick ONE column of the shared A
    # (or a signed slack one-hot) as a row over constraints, then an
    # (m, m) matvec.
    is_var_e = e <= n
    e_var = jnp.clip(e - 1, 0, n - 1)
    if gather:
        col_a = jnp.take(a, e_var[:, 0, 0], axis=1).T[:, None, :]  # (B, 1, m)
    else:
        oh = (engine.column_ids(n) == e_var).astype(dtype)  # (B, 1, n)
        col_a = jax.lax.dot_general(
            oh.reshape(bsz, n), a, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=dtype,
        ).reshape(bsz, 1, m)  # one-hot row-combination of A's columns
    col_s = (engine.column_ids(m) == jnp.clip(e - 1 - n, 0, m - 1)).astype(dtype)
    me = sgn * jnp.where(is_var_e, col_a, col_s)  # (B, 1, m)
    u = jnp.sum(s.binv * me, axis=2, keepdims=True)  # (B, m, 1)

    # Ratio test — engine.ratio_test's formulas on (u, xb).
    ratios = jnp.where(u > tol, s.xb / jnp.where(u > tol, u, 1.0), engine.BIG)
    art_escape = (s.basis >= art_start) & (s.xb <= tol) & (u < -tol)
    ratios = jnp.where(art_escape, 0.0, ratios)
    min_ratio = jnp.min(ratios, axis=1, keepdims=True)
    l = engine.first_index(ratios == min_ratio, axis=1)

    pivoting = active & ~at_opt
    unbounded = pivoting & (min_ratio >= engine.BIG / 2)
    status = jnp.where(unbounded, UNBOUNDED, status)
    do_pivot = pivoting & ~unbounded

    # Rank-1 product-form update — engine.pivot_update's formulas
    # applied to binv and xb (the tableau applies the identical
    # update to its B^-1-image columns and RHS).
    pe = engine.take_row(u, l, gather)  # (B, 1, 1)
    safe_pe = jnp.where(jnp.abs(pe) > tol, pe, 1.0)
    pr = engine.take_row(s.binv, l, gather)  # (B, 1, m)
    npr = pr / safe_pe
    l_rows = rows == l  # (B, m, 1)
    upd_binv = jnp.where(l_rows, npr, s.binv - u * npr)
    px = engine.take_row(s.xb, l, gather)
    npx = px / safe_pe
    upd_xb = jnp.where(l_rows, npx, s.xb - u * npx)

    keep = engine.widen_rows(do_pivot, m)  # (B, m, 1)
    binv = jnp.where(keep, upd_binv, s.binv)
    xb = jnp.where(keep, upd_xb, s.xb)
    basis = jnp.where(keep & l_rows, e, s.basis)
    iters = s.iters + do_pivot.astype(jnp.int32)
    return _RState(binv, basis, xb, new_phase, status, iters, s.step + 1)


def finalize(final: _RState, c, m: int, n: int, gather: bool = True):
    """Terminal (x, status) from a finished loop state.

    Shared by both drivers, in :func:`iteration_step`'s orientation:
    ITER_LIMIT for rows still RUNNING, and the one-hot scatter of basic
    values into the primal point as a (B, 1, n) row, zeros for
    non-OPTIMAL rows.  The objective is :func:`objective_value`, which
    both drivers evaluate outside any kernel.
    """
    status = jnp.where(final.status == RUNNING, ITER_LIMIT, final.status)
    # The OPTIMAL mask widens along rows before it meets the lanes (see
    # engine.extract_solution).
    hit = (final.basis == engine.column_ids(n) + 1) & engine.widen_rows(
        status == OPTIMAL, m
    )  # (B, m, n)
    x = jnp.sum(jnp.where(hit, final.xb, 0.0), axis=1, keepdims=True)
    return x, status


def objective_value(basis, xb, status, c, m: int, n: int) -> jnp.ndarray:
    """Phase-II objective ``c_B . x_B`` at a terminal basis, -inf unless OPTIMAL.

    (B, m) ``basis``/``xb``, (B,) ``status``, (B, n) ``c``.  Both drivers
    call this one XLA function — the Pallas kernel's wrapper on the
    kernel's exact (basis, xb) outputs — because a multi-term reduction
    lowered inside the kernel may reassociate differently; this way both
    backends return the same floats.  Equals the tableau's ``-tab[m, 0]``.
    """
    bsz = basis.shape[0]
    cb2 = _basic_costs(
        basis[:, :, None], jnp.full((bsz, 1, 1), 2, jnp.int32), c[:, None, :], m, n
    )[:, :, 0]
    # An explicit left fold, not a reduce: XLA may vectorize a reduce
    # differently depending on the program around it, while a chain of
    # adds rounds the same way in every caller.
    prod = cb2 * xb
    total = prod[:, 0]
    for i in range(1, m):
        total = total + prod[:, i]
    return jnp.where(status == OPTIMAL, total, jnp.asarray(-jnp.inf, c.dtype))


def _iterate(
    a, b, c, state, feas_tol, cap, seed, *, rule, unroll, tol, static_cap
):
    """The lockstep revised iteration loop (cold and resume paths).

    Mirrors ``simplex._iterate``'s scaffolding — traced ``cap`` unless
    ``static_cap`` pins it, manual unroll, masked lockstep updates,
    ITER_LIMIT for rows still RUNNING at the cap — with the tableau
    operations replaced by their revised equivalents
    (:func:`iteration_step`).  Returns ``(LPSolution,
    RevisedResumeState)``.
    """
    m, n = a.shape
    bsz = b.shape[0]
    dtype = a.dtype
    limit = static_cap if static_cap is not None else cap
    sgn = _signs(b, dtype)[:, None, :]
    c3 = c[:, None, :]
    feas_tol = feas_tol[:, None, None]

    def cond(s: _RState):
        return (s.step < limit) & jnp.any(s.status == RUNNING)

    def body(s: _RState):
        return iteration_step(
            a, c3, sgn, feas_tol, s, rule=rule, tol=tol, seed=seed
        )

    init = _RState(
        binv=state.binv,
        basis=state.basis[:, :, None],
        xb=state.xb[:, :, None],
        phase=state.phase[:, None, None],
        status=jnp.full((bsz, 1, 1), RUNNING, jnp.int32),
        iters=jnp.zeros((bsz, 1, 1), jnp.int32),
        step=jnp.asarray(0, jnp.int32),
    )
    if unroll > 1:
        inner = body

        def body(s: _RState):  # noqa: F811
            for _ in range(unroll):
                s = inner(s)
            return s

    final = jax.lax.while_loop(cond, body, init)

    x, status = finalize(final, c3, m, n)
    basis, xb, status = final.basis[:, :, 0], final.xb[:, :, 0], status[:, 0, 0]
    sol = LPSolution(
        objective=objective_value(basis, xb, status, c, m, n),
        x=x[:, 0, :],
        status=status,
        iterations=final.iters[:, 0, 0],
        basis=basis,
    )
    return sol, RevisedResumeState(final.binv, basis, xb, final.phase[:, 0, 0])


@functools.partial(
    jax.jit,
    static_argnames=("rule", "unroll", "tol", "want_state", "static_cap"),
)
def _solve_jit(
    a, b, c, basis0, cap, seed, *, rule, unroll, tol, want_state, static_cap
):
    state0 = init_traced(a, b, basis0)
    feas_tol = engine.phase1_feasibility_tol(b)
    sol, state = _iterate(
        a, b, c, state0, feas_tol, cap, seed,
        rule=rule, unroll=unroll, tol=tol, static_cap=static_cap,
    )
    return (sol, state) if want_state else sol


@functools.partial(
    jax.jit,
    static_argnames=("rule", "unroll", "tol", "want_state", "static_cap"),
)
def _resume_jit(
    a, b, c, state, cap, seed, *, rule, unroll, tol, want_state, static_cap
):
    feas_tol = engine.phase1_feasibility_tol(b)
    sol, out_state = _iterate(
        a, b, c, state, feas_tol, cap, seed,
        rule=rule, unroll=unroll, tol=tol, static_cap=static_cap,
    )
    return (sol, out_state) if want_state else sol


@jax.jit
def _init_jit(a, b, basis0):
    return init_traced(a, b, basis0)


def compile_cache_size() -> int:
    """Revised-driver executables compiled so far (cold + resume + init + sweep)."""
    return (
        int(_solve_jit._cache_size())
        + int(_resume_jit._cache_size())
        + int(_init_jit._cache_size())
        + int(_sweep_jit._cache_size())
    )


def init_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    basis0: Optional[jnp.ndarray] = None,
) -> RevisedResumeState:
    """The iteration-0 :class:`RevisedResumeState` (the serve-splice primitive).

    ``c`` is accepted for signature parity with the tableau driver's
    ``init_batched`` but unused — the revised state carries no cost row
    (pricing is recomputed every iteration from ``basis``/``phase``).
    Exactness contract as in ``simplex.init_batched``:
    ``resume_batched(a, b, c, init_batched(a, b, c), max_iters=K)`` is
    bit-identical to ``solve_batched(a, b, c, max_iters=K)``.
    """
    del c
    return _init_jit(a, b, basis0)


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
) -> LPSolution:
    """Solve B LPs (max c_k.x, A x <= b_k, x >= 0) over ONE shared ``A``.

    The revised-simplex twin of ``simplex.solve_batched``: identical
    knobs and contracts (traced cap, rpc seed, unroll, warm ``basis0``
    with per-row cold fallback, ``want_state`` resume handoff), but
    ``a`` is (m, n) — stored once — and the carried state is O(m²)/LP.
    """
    m, n = a.shape
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(a.dtype)
    static_cap = None if dynamic_cap else int(cap)
    return _solve_jit(
        a, b, c, basis0, jnp.int32(cap if dynamic_cap else 0), seed,
        rule=rule, unroll=unroll, tol=tol,
        want_state=want_state, static_cap=static_cap,
    )


def resume_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    state: RevisedResumeState,
    rule: str = LPC,
    max_iters: int = 0,
    seed: int = 0,
    unroll: int = 1,
    tol: float = 0.0,
    want_state: bool = True,
    dynamic_cap: bool = True,
):
    """Continue a batch from a carried :class:`RevisedResumeState`.

    Unlike the tableau resume, the shared ``a`` must be passed back in
    (the state deliberately does not replicate it); ``b``/``c`` re-derive
    the cost row and feasibility threshold exactly as the interrupted
    solve did, so capped rounds summing to ``K`` are bit-identical to
    one uninterrupted cap-``K`` solve.
    """
    m, n = a.shape
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(a.dtype)
    static_cap = None if dynamic_cap else int(cap)
    return _resume_jit(
        a, b, c, state, jnp.int32(cap if dynamic_cap else 0), seed,
        rule=rule, unroll=unroll, tol=tol,
        want_state=want_state, static_cap=static_cap,
    )


def solve(batch: SharedLPBatch, **kw) -> LPSolution:
    kw.setdefault("basis0", batch.basis0)
    return solve_batched(batch.a, batch.b, batch.c, **kw)


# ---------------------------------------------------------------------------
# Warm objective sweep: one (A, b), a stack of cost vectors
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("rule", "unroll", "tol", "warm", "static_cap"),
)
def _sweep_jit(a, b, c_stack, cap, seed, *, rule, unroll, tol, warm, static_cap):
    cold = _cold_state(a, b)
    feas_tol = engine.phase1_feasibility_tol(b)
    bsz = b.shape[0]

    def step(carry, c_t):
        state, ok = carry
        start = RevisedResumeState(
            jnp.where(ok[:, None, None], state.binv, cold.binv),
            jnp.where(ok[:, None], state.basis, cold.basis),
            jnp.where(ok[:, None], state.xb, cold.xb),
            jnp.where(ok, 2, cold.phase).astype(jnp.int32),
        )
        sol, out = _iterate(
            a, b, c_t, start, feas_tol, cap, seed,
            rule=rule, unroll=unroll, tol=tol, static_cap=static_cap,
        )
        new_ok = (sol.status == OPTIMAL) if warm else jnp.zeros((bsz,), bool)
        return (out, new_ok), (sol.objective, sol.x, sol.status, sol.iterations)

    carry0 = (cold, jnp.zeros((bsz,), bool))
    _, ys = jax.lax.scan(step, carry0, c_stack)
    return ys


def sweep_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c_stack: jnp.ndarray,
    rule: str = LPC,
    max_iters: int = 0,
    seed: int = 0,
    unroll: int = 1,
    tol: float = 0.0,
    warm: bool = True,
):
    """Solve a (T, B, n) stack of objectives over ONE ``(A, b)`` system.

    The support-sweep inner loop (``Polytope.support_sweep``): a sweep is
    exactly one polytope, many directions.  ``A`` and ``b`` are staged
    once for ALL T·B solves; a compiled ``lax.scan`` carries the basis
    state across steps.  With ``warm=True`` (default) each step restarts
    from the previous direction's optimal basis where one exists — since
    ``b`` is unchanged, that basis is still primal feasible, so the warm
    start is exact (phase II, zero refactorization) and only the
    re-pricing differs; rows that did not finish OPTIMAL fall back to
    the cold start.  Returns ``(objective, x, status, iterations)``,
    each with a leading (T, B) block.
    """
    m, n = a.shape
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(a.dtype)
    return _sweep_jit(
        a, b, c_stack, jnp.int32(cap), seed,
        rule=rule, unroll=unroll, tol=tol, warm=warm, static_cap=None,
    )

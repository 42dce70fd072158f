"""Batched restarted PDHG: the first-order backend for large LPs.

The tableau simplex (the paper's subject) explicitly cedes the m, n >= 500
regime — its dense tableau costs O(m (n + m)) per LP and every pivot
touches all of it.  This module is the other side of that frontier: a
batched, jit-compiled **restarted primal-dual hybrid gradient** (PDHG)
solver in the style of PDLP / cuPDLP (arXiv 2311.12180; see also the GPU
first-order survey, arXiv 2506.02174).  PDHG stores only the problem data
(A, b, c: O(m n) per LP) plus a handful of length-m/n iterate vectors, and
each iteration is two matvecs and two projections — pure vmap-friendly
arithmetic with no pivoting, no factorization, and no tableau at all.

For the canonical problem (``max c.x  s.t.  Ax <= b, x >= 0``; dual
``min b.y  s.t.  A'y >= c, y >= 0``) the iteration is the standard
Chambolle–Pock primal-dual update with extrapolation on the primal:

    x+ = max(0, x + tau * (c - A'y))
    y+ = max(0, y + sigma * (A (2 x+ - x) - b))

which converges for ``tau * sigma * ||A||^2 < 1``.  Following PDLP:

* **step sizes** — ``eta = 0.9 / ||A||_2`` with ``||A||_2`` from a few
  power iterations on ``A'A`` (per LP, inside the jit), split
  ``tau = eta / omega``, ``sigma = eta * omega`` by the primal weight
  ``omega = ||c|| / ||b||`` so primal and dual progress at similar rates;
* **restarts** — the iterate average since the last restart is a strictly
  better point than the last iterate (PDHG's ergodic rate beats its
  last-iterate rate), so every ``restart`` steps the iterate is reset to
  that running average (the fixed-period flavor of cuPDLP's restart
  scheme — chosen over the adaptive one so the trajectory of one LP never
  depends on batch composition, which is what lets the dispatch layer's
  compaction carry :class:`PDHGResumeState` bit-stably);
* **termination** — relative KKT residuals (primal feasibility, dual
  feasibility, duality gap) against ``pdhg_tol``, checked every iteration
  on quantities the iteration already computes, so the check is free;
* **certificates** — a diverging dual iterate whose normalization is an
  approximate Farkas ray (``A'y >= 0, b.y < 0``) flags ``INFEASIBLE``; a
  diverging primal iterate that is an improving feasible ray
  (``Ax <= 0, c.x > 0`` with small primal residual) flags ``UNBOUNDED`` —
  the same status contract as the simplex backends.  Both certificates
  are checked at restart boundaries only and additionally require the
  iterate norm to have GROWN over the period (:data:`GROWTH_FRACTION`):
  a bounded LP with a large-norm optimum passes every pointwise ray test
  near ``x*`` but plateaus there, while a genuine ray keeps growing.
  Even gated, the flags stay heuristic — the dispatch layer re-derives
  every one exactly before reporting it (:func:`confirm_certificates`).

The loop carries everything it needs in :class:`PDHGResumeState` (current
iterates, the cached matvec ``A x``, the restart running sums and
counter), so the round-scheduler (core/dispatch.py) can interrupt a solve
at any cap, compact the survivors, and resume them EXACTLY: a sequence of
resumed rounds whose step budgets sum to K is bit-identical to one
uninterrupted run with cap K, per LP, regardless of batch composition —
the same contract the simplex ``ResumeState`` honors.

:func:`crossover` converts a converged PDHG point into a simplex basis
guess (the m largest of the concatenated primal values and slacks) and
polishes it with the existing lockstep engine's warm-start path, which
validates the basis per LP and silently cold-starts where the guess is
infeasible/singular — so crossover output is always an EXACT vertex with
a reusable basis, which is what ``support_sweep`` warm starts need.

The shared step function (:func:`pdhg_step`) is driver-agnostic: the XLA
path calls it with ``einsum`` matvecs, the Pallas kernel
(kernels/pdhg_pallas.py) with broadcast-multiply-reduce ones that Mosaic
lowers, mirroring how ``core/engine.py`` serves both simplex drivers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .lp import (
    INFEASIBLE,
    ITER_LIMIT,
    LPBatch,
    LPSolution,
    OPTIMAL,
    RUNNING,
    UNBOUNDED,
)

#: Default relative KKT tolerance when ``SolveOptions.pdhg_tol`` is 0.
#: 1e-4 is the "moderate accuracy" setting of PDLP/cuPDLP; pair with
#: ``crossover=True`` when exact vertices are required.
DEFAULT_PDHG_TOL = 1e-4

#: Default restart period when ``SolveOptions.pdhg_restart`` is 0.
DEFAULT_RESTART = 64

#: Power iterations for the per-LP ||A||_2 estimate.
POWER_ITERS = 24

#: Step-size safety factor: eta = STEP_SAFETY / ||A||_2 keeps
#: tau * sigma * ||A||^2 strictly below 1 even when the power-iteration
#: estimate slightly undershoots the true spectral norm.
STEP_SAFETY = 0.9

#: Relative tolerance for the Farkas-ray feasibility of a normalized
#: diverging iterate (the certificate checks).
CERT_EPS = 1e-3

#: Iterate-norm threshold before a divergence certificate may fire —
#: guards against transient false positives while the iterates are still
#: mixing.  Absolute by design: the random/benchmark problem classes here
#: have O(1)-O(10) data, so bounded (convergent) trajectories stay orders
#: of magnitude below it.
DIVERGENCE_GUARD = 1e3

#: Fraction of the ideal per-period ray growth (``restart * step * eps *
#: scale``) an iterate must actually sustain between restart boundaries
#: before a divergence certificate may fire.  A bounded LP with a
#: large-norm optimum can satisfy every POINTWISE ray condition near
#: ``x*`` (a feasible point has ``relu(Ax) = 0`` exactly), but its norm
#: plateaus there; only a genuine ray keeps growing period after period.
GROWTH_FRACTION = 0.25

_TINY = 1e-30


def auto_cap_pdhg(m: int, n: int) -> int:
    """The pdhg backend's auto iteration cap for ``max_iters <= 0``.

    First-order iterations are much cheaper than simplex pivots (two
    matvecs vs a full tableau pass) and PDHG needs more of them, so the
    pdhg backend overrides the library-wide ``auto_cap`` through the
    ``Backend.auto_cap`` hook with this larger budget.
    """
    return max(20_000, 40 * (m + n))


def resolve_cap(max_iters: int, m: int, n: int) -> int:
    """``max_iters`` with the pdhg 0 -> auto rule applied."""
    return max_iters if max_iters > 0 else auto_cap_pdhg(m, n)


def resolve_tol(tol: float) -> float:
    """``pdhg_tol`` with the 0 -> :data:`DEFAULT_PDHG_TOL` rule applied."""
    return tol if tol > 0.0 else DEFAULT_PDHG_TOL


def resolve_restart(restart: int) -> int:
    """``pdhg_restart`` with the 0 -> :data:`DEFAULT_RESTART` rule applied."""
    return restart if restart > 0 else DEFAULT_RESTART


def state_bytes_per_lp(m: int, n: int, dtype=jnp.float32) -> int:
    """Resident bytes one LP costs the pdhg solver (problem data + state).

    Problem data A/b/c (``m n + m + n``) plus the iterate state carried by
    :class:`PDHGResumeState` (x and its running sum: ``2n``; y, the cached
    ``A x``, and their running sums: ``4m``; the two period-boundary norms
    for the divergence growth gate) plus the int32 restart counter.  The memory counterpart of the tableau's
    ``TableauSpec.bytes_per_lp`` — O(m n) versus the tableau's
    O(m (n + m)), with a ~1x constant instead of the tableau's
    row-times-column blowup (see ``benchmarks/fig_memory.py``).
    """
    item = jnp.dtype(dtype).itemsize
    return item * (m * n + m + n + 2 * n + 4 * m + 2) + 4


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PDHGResumeState:
    """Mid-solve PDHG state, carried between dispatch rounds.

    The first-order counterpart of :class:`~repro.core.lp.ResumeState`:
    everything the iteration loop carries, so a capped round can be
    continued EXACTLY.  ``ax`` caches the matvec ``A x`` the loop threads
    from step to step — it is part of the state (rather than recomputed
    at resume) because after a restart-to-average the loop's ``ax`` is
    the averaged accumulator, not a fresh ``A x``, and bit-stable resume
    must replay the loop's arithmetic, not a mathematical equivalent.

    The restart running sums (``x_sum``/``y_sum``/``ax_sum``) and the
    per-LP step counter ``inner`` make the fixed-period restart schedule
    itself resume-invariant: each LP restarts at the same absolute
    iteration numbers no matter how the rounds were sliced.  ``x_grow``
    and ``y_grow`` record the iterate norms at the last restart boundary
    for the divergence-certificate growth gate — carrying them keeps the
    gate's period comparisons identical across round slicing too.
    """

    x: jnp.ndarray  # (B, n) primal iterate
    y: jnp.ndarray  # (B, m) dual iterate
    ax: jnp.ndarray  # (B, m) carried A @ x
    x_sum: jnp.ndarray  # (B, n) running primal sum since last restart
    y_sum: jnp.ndarray  # (B, m) running dual sum since last restart
    ax_sum: jnp.ndarray  # (B, m) running A @ x sum since last restart
    inner: jnp.ndarray  # (B,) int32 steps since last restart
    x_grow: jnp.ndarray  # (B,) ||x|| at the last restart boundary
    y_grow: jnp.ndarray  # (B,) ||y|| at the last restart boundary

    @property
    def batch(self) -> int:
        return self.x.shape[0]

    def take(self, idx) -> "PDHGResumeState":
        """Gather state rows (compaction gather between rounds)."""
        return jax.tree_util.tree_map(lambda v: v[idx], self)


def init_state(bsz: int, m: int, n: int, dtype) -> PDHGResumeState:
    """The cold-start state: x = 0, y = 0 (and A @ 0 = 0)."""
    z = functools.partial(jnp.zeros, dtype=dtype)
    return PDHGResumeState(
        x=z((bsz, n)),
        y=z((bsz, m)),
        ax=z((bsz, m)),
        x_sum=z((bsz, n)),
        y_sum=z((bsz, m)),
        ax_sum=z((bsz, m)),
        inner=jnp.zeros((bsz,), jnp.int32),
        x_grow=z((bsz,)),
        y_grow=z((bsz,)),
    )


# ---------------------------------------------------------------------------
# matvecs — the only operation the two drivers implement differently
# ---------------------------------------------------------------------------


def matvec(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Batched ``A @ x``: (B, m, n), (B, n) -> (B, m) via dot_general.

    ``HIGHEST`` precision: XLA's default on TPU would contract float32 in
    a single bfloat16 pass, far too coarse for a 1e-4 KKT tolerance.
    """
    return jnp.einsum("bmn,bn->bm", a, x, precision=jax.lax.Precision.HIGHEST)


def rmatvec(a: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Batched ``A' @ y``: (B, m, n), (B, m) -> (B, n) (``HIGHEST`` precision)."""
    return jnp.einsum("bmn,bm->bn", a, y, precision=jax.lax.Precision.HIGHEST)


def _l2(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.sum(v * v, axis=-1))


def spectral_norm(
    a: jnp.ndarray,
    iters: int = POWER_ITERS,
    mv: Callable = matvec,
    rmv: Callable = rmatvec,
) -> jnp.ndarray:
    """Per-LP ||A||_2 estimate by power iteration on ``A'A``.

    Deterministic (all-ones start), so every solve and every resumed
    round recomputes bit-identical step sizes from the same ``A``.
    """
    bsz, _, n = a.shape
    v = jnp.full((bsz, n), 1.0 / np.sqrt(n), a.dtype)

    def body(_, v):
        w = rmv(a, mv(a, v))
        return w / jnp.maximum(_l2(w), _TINY)[:, None]

    v = jax.lax.fori_loop(0, iters, body, v)
    return _l2(mv(a, v))


def step_sizes(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    mv: Callable = matvec,
    rmv: Callable = rmatvec,
) -> Tuple[jnp.ndarray, jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """Per-LP (tau, sigma, (anorm, bscale, cscale)).

    ``tau * sigma = (STEP_SAFETY / ||A||)^2`` guarantees convergence; the
    primal weight ``omega = ||c|| / ||b||`` (clipped, 1 when degenerate)
    splits the product so primal and dual move at comparable rates —
    PDLP's initial primal-weight heuristic.
    """
    anorm = spectral_norm(a, mv=mv, rmv=rmv)
    eta = STEP_SAFETY / jnp.maximum(anorm, _TINY)
    bn = _l2(b)
    cn = _l2(c)
    omega = jnp.where((bn > 1e-12) & (cn > 1e-12), cn / jnp.maximum(bn, _TINY), 1.0)
    omega = jnp.clip(omega, 1e-2, 1e2)
    tau = eta / omega
    sigma = eta * omega
    return tau, sigma, (anorm, 1.0 + bn, 1.0 + cn)


# ---------------------------------------------------------------------------
# the shared iteration — one step function for both drivers
# ---------------------------------------------------------------------------


def _lp_axes(v: jnp.ndarray) -> Tuple[int, ...]:
    """The non-batch axes of ``v`` that are longer than 1.

    Reducing only those keeps each Mosaic reduction a single-axis one
    (rows reduce over lanes, columns over sublanes).
    """
    return tuple(i for i in range(1, v.ndim) if v.shape[i] != 1)


def _lp_sum(v: jnp.ndarray) -> jnp.ndarray:
    """Per-LP sum over every non-batch axis, keeping the rank."""
    return jnp.sum(v, axis=_lp_axes(v), keepdims=True)


def _lp_max(v: jnp.ndarray) -> jnp.ndarray:
    """Per-LP max over every non-batch axis, keeping the rank."""
    return jnp.max(v, axis=_lp_axes(v), keepdims=True)


def _lp_norm(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(_lp_sum(v * v))


def pdhg_step(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    x: jnp.ndarray,
    y: jnp.ndarray,
    ax: jnp.ndarray,
    x_sum: jnp.ndarray,
    y_sum: jnp.ndarray,
    ax_sum: jnp.ndarray,
    inner: jnp.ndarray,
    x_grow: jnp.ndarray,
    y_grow: jnp.ndarray,
    status: jnp.ndarray,
    iters: jnp.ndarray,
    tau: jnp.ndarray,
    sigma: jnp.ndarray,
    scales: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    *,
    tol: float,
    restart: int,
    mv: Callable = matvec,
    rmv: Callable = rmatvec,
):
    """One lockstep PDHG iteration over a batch (or kernel tile) of LPs.

    Order of operations per step: (1) termination/certificate checks on
    the CURRENT iterate using the cached ``ax`` and this step's ``A'y``
    — both needed by the update anyway, so the checks cost only
    reductions; (2) the primal/dual prox updates; (3) restart-to-average
    bookkeeping.  Rows whose status left ``RUNNING`` are frozen
    everywhere, so converged/certified LPs coast (lockstep) without
    their results drifting.

    Rank-agnostic: per-LP scalars (``inner``, the growth norms,
    ``status``, ``iters``, ``tau``, ``sigma``, ``scales``) keep the rank
    of the vectors — (B, 1) against (B, n) vectors in the XLA driver,
    (B, 1, 1) against (B, 1, n) rows and (B, m, 1) columns in the Pallas
    kernel — so every broadcast is a plain one and no axis ever moves.

    Everything here is per-LP arithmetic — no cross-LP reduction — which
    is the property the compaction bit-stability contract rests on.
    """
    anorm, bscale, cscale = scales
    active = status == RUNNING
    aty = rmv(a, y)

    # --- (1) termination: relative KKT residuals on (x, y) -----------------
    pres = _lp_norm(jnp.maximum(ax - b, 0.0)) / bscale
    dres = _lp_norm(jnp.maximum(c - aty, 0.0)) / cscale
    pobj = _lp_sum(c * x)
    dobj = _lp_sum(b * y)
    gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
    opt = (pres <= tol) & (dres <= tol) & (gap <= tol)

    # --- certificates: normalized diverging iterates as Farkas rays --------
    # Checked only at restart boundaries, where the growth gate has a full
    # period to compare against: the pointwise ray conditions alone cannot
    # tell an unbounded ray from a bounded LP with a large-norm optimum (a
    # feasible iterate has relu(Ax - b) = 0 exactly), but only the ray
    # keeps GROWING by ~restart * step * (c . d) per period — a bounded
    # iterate plateaus at ||x*|| and fails the growth test.
    xnorm = _lp_norm(x)
    ynorm = _lp_norm(y)
    at_period = inner + 1 >= restart
    ray_eps = CERT_EPS * jnp.maximum(anorm, 1.0)
    # Primal infeasibility: y/||y|| with A'y >= 0 (up to ray_eps) and
    # b.y < 0 — the dual ray a primal-infeasible LP drives to infinity.
    dual_ray = _lp_max(jnp.maximum(-aty, 0.0)) / jnp.maximum(ynorm, _TINY)
    infeas = (
        at_period
        & (ynorm >= DIVERGENCE_GUARD)
        & (ynorm - y_grow >= GROWTH_FRACTION * restart * sigma * CERT_EPS * bscale)
        & (dual_ray <= ray_eps)
        & (dobj / jnp.maximum(ynorm, _TINY) <= -CERT_EPS * bscale)
    )
    # Unboundedness: x/||x|| with Ax <= 0 and c.x > 0, AND a near-feasible
    # trajectory (small pres) — an infeasible LP can also blow up its
    # primal block, but never with a small primal residual.
    prim_ray = _lp_max(jnp.maximum(ax, 0.0)) / jnp.maximum(xnorm, _TINY)
    unbounded = (
        at_period
        & (xnorm >= DIVERGENCE_GUARD)
        & (xnorm - x_grow >= GROWTH_FRACTION * restart * tau * CERT_EPS * cscale)
        & (prim_ray <= ray_eps)
        & (pobj / jnp.maximum(xnorm, _TINY) >= CERT_EPS * cscale)
        & (pres <= CERT_EPS)
    )

    status = jnp.where(active & opt, OPTIMAL, status)
    status = jnp.where(active & ~opt & infeas, INFEASIBLE, status)
    status = jnp.where(active & ~opt & ~infeas & unbounded, UNBOUNDED, status)

    live = status == RUNNING
    iters = iters + live.astype(jnp.int32)

    # --- (2) prox steps ----------------------------------------------------
    x1 = jnp.maximum(x + tau * (c - aty), 0.0)
    ax1 = mv(a, x1)
    y1 = jnp.maximum(y + sigma * (2.0 * ax1 - ax - b), 0.0)

    # --- (3) restart-to-average bookkeeping --------------------------------
    cnt = inner + 1
    xs1 = x_sum + x1
    ys1 = y_sum + y1
    axs1 = ax_sum + ax1
    do_restart = cnt >= restart
    denom = cnt.astype(x.dtype)
    x2 = jnp.where(do_restart, xs1 / denom, x1)
    y2 = jnp.where(do_restart, ys1 / denom, y1)
    ax2 = jnp.where(do_restart, axs1 / denom, ax1)
    zero = jnp.zeros((), x.dtype)
    xs2 = jnp.where(do_restart, zero, xs1)
    ys2 = jnp.where(do_restart, zero, ys1)
    axs2 = jnp.where(do_restart, zero, axs1)
    inner2 = jnp.where(do_restart, 0, cnt)
    # Growth gate: record the boundary norms (pre-averaging, the same
    # measure the certificate compares) for the next period's test.
    xg2 = jnp.where(do_restart, xnorm, x_grow)
    yg2 = jnp.where(do_restart, ynorm, y_grow)

    # Freeze finished rows.
    x = jnp.where(live, x2, x)
    y = jnp.where(live, y2, y)
    ax = jnp.where(live, ax2, ax)
    x_sum = jnp.where(live, xs2, x_sum)
    y_sum = jnp.where(live, ys2, y_sum)
    ax_sum = jnp.where(live, axs2, ax_sum)
    inner = jnp.where(live, inner2, inner)
    x_grow = jnp.where(live, xg2, x_grow)
    y_grow = jnp.where(live, yg2, y_grow)
    return x, y, ax, x_sum, y_sum, ax_sum, inner, x_grow, y_grow, status, iters


def iterate(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    state: PDHGResumeState,
    cap,
    *,
    tol: float,
    restart: int,
    static_cap: Optional[int] = None,
    mv: Callable = matvec,
    rmv: Callable = rmatvec,
) -> Tuple[LPSolution, PDHGResumeState]:
    """Run up to ``cap`` ADDITIONAL steps from ``state`` (the shared loop).

    ``cap`` is a traced scalar under the compile-once contract
    (``static_cap`` restores the cap-specialized lowering).  Step sizes
    are recomputed from ``a`` — deterministically, so a resumed round
    uses bit-identical tau/sigma — and rows still ``RUNNING`` at the cap
    report ``ITER_LIMIT``, which is the round-scheduler's survivor
    signal.
    """
    tau, sigma, scales = step_sizes(a, b, c, mv=mv, rmv=rmv)
    bsz = a.shape[0]
    limit = static_cap if static_cap is not None else cap

    def col(v):  # (B,) per-LP scalar -> (B, 1), pdhg_step's convention
        return v[:, None]

    tau, sigma, scales = col(tau), col(sigma), tuple(col(s) for s in scales)

    def body(carry):
        x, y, ax, xs, ys, axs, inner, xg, yg, status, iters, step = carry
        out = pdhg_step(
            a, b, c, x, y, ax, xs, ys, axs, inner, xg, yg, status, iters,
            tau, sigma, scales, tol=tol, restart=restart, mv=mv, rmv=rmv,
        )
        return (*out, step + 1)

    def cond(carry):
        status, step = carry[-3], carry[-1]
        return jnp.logical_and(step < limit, jnp.any(status == RUNNING))

    carry0 = (
        state.x, state.y, state.ax,
        state.x_sum, state.y_sum, state.ax_sum,
        col(state.inner), col(state.x_grow), col(state.y_grow),
        jnp.full((bsz, 1), RUNNING, jnp.int32), jnp.zeros((bsz, 1), jnp.int32),
        jnp.int32(0),
    )
    x, y, ax, xs, ys, axs, inner, xg, yg, status, iters, _ = jax.lax.while_loop(
        cond, body, carry0
    )
    status = jnp.where(status[:, 0] == RUNNING, ITER_LIMIT, status[:, 0])
    pobj = jnp.sum(c * x, axis=-1)
    objective = jnp.where(status == OPTIMAL, pobj, -jnp.inf)
    sol = LPSolution(
        objective=objective, x=x, status=status, iterations=iters[:, 0], y=y
    )
    out_state = PDHGResumeState(
        x=x, y=y, ax=ax, x_sum=xs, y_sum=ys, ax_sum=axs, inner=inner[:, 0],
        x_grow=xg[:, 0], y_grow=yg[:, 0],
    )
    return sol, out_state


# ---------------------------------------------------------------------------
# jitted drivers + compile-cache observability
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("tol", "restart", "static_cap", "want_state")
)
def _solve_jit(a, b, c, cap, *, tol, restart, static_cap, want_state):
    bsz, m, n = a.shape
    sol, state = iterate(
        a, b, c, init_state(bsz, m, n, a.dtype), cap,
        tol=tol, restart=restart, static_cap=static_cap,
    )
    return (sol, state) if want_state else sol


@functools.partial(
    jax.jit, static_argnames=("tol", "restart", "static_cap", "want_state")
)
def _resume_jit(a, b, c, state, cap, *, tol, restart, static_cap, want_state):
    sol, out_state = iterate(
        a, b, c, state, cap, tol=tol, restart=restart, static_cap=static_cap
    )
    return (sol, out_state) if want_state else sol


def compile_cache_size() -> int:
    """XLA pdhg-driver executables compiled so far (cold + resume paths).

    The pdhg backend's hook behind ``SolveStats.compiles`` /
    ``SolveStats.cache_hits``.
    """
    return int(_solve_jit._cache_size()) + int(_resume_jit._cache_size())


def solve_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    *,
    tol: float = 0.0,
    restart: int = 0,
    max_iters: int = 0,
    want_state: bool = False,
    dynamic_cap: bool = True,
):
    """Solve a canonical batch with restarted PDHG (XLA driver).

    a: (B, m, n), b: (B, m), c: (B, n); returns :class:`LPSolution` like
    the simplex drivers (plus the dual iterate in ``LPSolution.y``).
    ``tol`` is the relative KKT tolerance (0 -> 1e-4), ``restart`` the
    fixed restart period (0 -> 64), ``max_iters`` the step cap
    (0 -> ``auto_cap_pdhg``, traced under ``dynamic_cap`` so every cap
    over one shape shares one executable).  ``want_state`` additionally
    returns the exact terminal :class:`PDHGResumeState` for
    :func:`resume_batched`.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b, a.dtype)
    c = jnp.asarray(c, a.dtype)
    bsz, m, n = a.shape
    cap = resolve_cap(max_iters, m, n)
    static_cap = None if dynamic_cap else int(cap)
    return _solve_jit(
        a, b, c, jnp.int32(cap),
        tol=resolve_tol(tol), restart=resolve_restart(restart),
        static_cap=static_cap, want_state=want_state,
    )


def resume_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    state: PDHGResumeState,
    *,
    tol: float = 0.0,
    restart: int = 0,
    max_iters: int = 0,
    want_state: bool = True,
    dynamic_cap: bool = True,
):
    """Continue a batch from a carried :class:`PDHGResumeState`.

    ``max_iters`` is the ADDITIONAL step budget, mirroring the simplex
    resume contract: rounds whose budgets sum to K replay one
    uninterrupted cap-K solve bit-for-bit (unlike the simplex resume,
    pdhg needs ``a`` back — the matvecs read it every step).
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b, a.dtype)
    c = jnp.asarray(c, a.dtype)
    bsz, m, n = a.shape
    cap = resolve_cap(max_iters, m, n)
    static_cap = None if dynamic_cap else int(cap)
    return _resume_jit(
        a, b, c, state, jnp.int32(cap),
        tol=resolve_tol(tol), restart=resolve_restart(restart),
        static_cap=static_cap, want_state=want_state,
    )


# ---------------------------------------------------------------------------
# certificate confirmation: oracle re-solve of the heuristically flagged rows
# ---------------------------------------------------------------------------


def confirm_certificates(
    batch: LPBatch, sol: LPSolution, options=None
) -> LPSolution:
    """Exactly confirm — or revoke — the loop's heuristic divergence flags.

    The in-loop certificates are trajectory heuristics: a BOUNDED LP whose
    optimum sits far from the origin (a "long valley") satisfies every
    pointwise ray condition while still ramping toward ``x*``, and no
    finite-time trajectory test can tell that ramp from a genuine
    recession ray.  So every ``UNBOUNDED``/``INFEASIBLE`` flag is
    re-derived exactly before it is reported: the flagged rows (a
    handful, host-side gather like :func:`crossover`) are re-solved by
    the sequential float64 oracle (``core/oracle.py`` — the repo's
    independent trust anchor, with exact pivoting and its own
    unbounded/infeasible detection), and the flag survives only if the
    oracle reproduces it.  Any other oracle outcome reverts the row to
    ``ITER_LIMIT`` ("undecided at this budget") — never a wrong
    certificate, at worst an honest non-answer.

    The oracle runs under a ``max(400, 2 (m + n))`` pivot budget.
    Genuine rays are cheap to reproduce — the oracle detects
    unboundedness in about m pivots — but a FALSE flag makes it grind
    all the way to optimality, which on a large degenerate valley can
    take tens of thousands of pivots (~25k, minutes of host time, on an
    m = n = 1000 instance).  Budgeted, that expensive case just fails to
    confirm inside the cap and reverts through the same honest
    ``ITER_LIMIT`` path, so confirmation stays O((m + n) m n) per
    flagged row instead of unbounded.

    The dispatch layer applies this as a post-pass on the FINAL merged
    solution — exactly once per row, after all resume rounds — so, like
    :func:`crossover`, it cannot perturb the compaction bit-stability
    contract: each row's confirmation depends only on that row's data.
    """
    from . import oracle as _oracle  # lazy: NumPy-only, test-grade path

    st = np.asarray(sol.status)
    flagged = np.nonzero((st == UNBOUNDED) | (st == INFEASIBLE))[0]
    if flagged.size == 0:
        return sol
    _, _, exact, _ = _oracle.solve_batch(
        np.asarray(batch.a[jnp.asarray(flagged)], np.float64),
        np.asarray(batch.b[jnp.asarray(flagged)], np.float64),
        np.asarray(batch.c[jnp.asarray(flagged)], np.float64),
        max_iters=max(400, 2 * (batch.m + batch.n)),
    )
    ok = exact == st[flagged]
    if np.all(ok):
        return sol
    status = sol.status.at[jnp.asarray(flagged[~ok])].set(ITER_LIMIT)
    return dataclasses.replace(sol, status=status)


# ---------------------------------------------------------------------------
# crossover: PDHG point -> simplex basis -> exact vertex
# ---------------------------------------------------------------------------

#: Fixed batch size for every crossover polish dispatch.  XLA picks
#: different contraction/reduction orders for different batch sizes, so
#: a warm-started polish of the same row inside a batch of 2 vs 6 can
#: differ at the ulp level.  Tiling the gathered OPTIMAL rows into
#: replica-padded tiles of this exact size makes each row's polished
#: bits a function of that row's data ALONE — the same whether crossover
#: runs once over a megabatch (``repro.solve``) or incrementally over
#: retired sub-batches (``serve/engine.py``) — and means polish compiles
#: exactly one executable per (m, n) class.
CROSSOVER_TILE = 8


def crossover_basis(
    a: jnp.ndarray, b: jnp.ndarray, x: jnp.ndarray
) -> jnp.ndarray:
    """Basis guess from a (near-)optimal point: top-m of [x | slacks].

    At a non-degenerate vertex exactly m of the n + m values
    ``[x, s = b - Ax]`` are positive and they identify the optimal basis;
    near one, the m LARGEST values are the right guess.  IDs follow the
    tableau column convention (variable j -> 1 + j, slack i -> 1 + n + i)
    so the result feeds ``LPBatch.basis0`` / ``build_tableau`` directly —
    whose warm-start path validates per LP and cold-starts the rows
    where the guess is singular or infeasible.
    """
    n = x.shape[-1]
    m = b.shape[-1]
    vals = jnp.concatenate([x, b - matvec(a, x)], axis=-1)
    _, idx = jax.lax.top_k(vals, m)
    return jnp.where(idx < n, 1 + idx, 1 + n + (idx - n)).astype(jnp.int32)


def crossover(
    batch: LPBatch, sol: LPSolution, options=None
) -> LPSolution:
    """Polish a PDHG solution's OPTIMAL rows into exact simplex vertices.

    Gathers the converged rows (host-side — crossover already syncs for
    the status read), derives a basis guess from each PDHG point, and
    warm-starts the existing lockstep simplex engine from it.  The
    returned rows carry the exact vertex objective/point and a reusable
    ``basis``; ``iterations`` adds the polish pivots on top of the PDHG
    step counts.  Non-OPTIMAL rows pass through untouched.

    The gathered rows are polished in replica-padded tiles of exactly
    :data:`CROSSOVER_TILE` rows, so each row's polished bits depend only
    on that row's data — never on which (or how many) other rows
    happened to converge alongside it.  That is what lets the continuous
    serve loop apply crossover per retired sub-batch and still return
    bits identical to a one-shot solve of the whole workload.
    """
    from . import simplex as _simplex  # lazy: avoid import cycle at init

    st = np.asarray(sol.status)
    opt = np.nonzero(st == OPTIMAL)[0]
    bsz, m = batch.batch, batch.m
    if opt.size == 0:
        return sol
    tol = getattr(options, "tolerance", 0.0) if options is not None else 0.0
    parts = []
    for start in range(0, opt.size, CROSSOVER_TILE):
        rows = opt[start : start + CROSSOVER_TILE]
        real = rows.size
        if real < CROSSOVER_TILE:
            rows = np.concatenate([rows, np.repeat(rows[:1], CROSSOVER_TILE - real)])
        tidx = jnp.asarray(rows)
        a, b, c = batch.a[tidx], batch.b[tidx], batch.c[tidx]
        guess = crossover_basis(a, b, sol.x[tidx])
        parts.append((_simplex.solve_batched(a, b, c, tol=tol, basis0=guess), real))
    polished = LPSolution(
        objective=jnp.concatenate([p.objective[:r] for p, r in parts]),
        x=jnp.concatenate([p.x[:r] for p, r in parts]),
        status=jnp.concatenate([p.status[:r] for p, r in parts]),
        iterations=jnp.concatenate([p.iterations[:r] for p, r in parts]),
        basis=jnp.concatenate([p.basis[:r] for p, r in parts]),
    )
    ok = np.asarray(polished.status) == OPTIMAL
    rows = jnp.asarray(opt[ok])
    sel = jnp.asarray(np.nonzero(ok)[0])
    basis = jnp.zeros((bsz, m), jnp.int32)
    if sol.basis is not None:
        basis = basis.at[:].set(sol.basis)
    return LPSolution(
        objective=sol.objective.at[rows].set(polished.objective[sel]),
        x=sol.x.at[rows].set(polished.x[sel]),
        status=sol.status,
        iterations=sol.iterations.at[rows].add(polished.iterations[sel]),
        basis=basis.at[rows].set(polished.basis[sel]),
        y=sol.y,
    )

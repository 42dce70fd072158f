"""Batched LP containers and tableau construction.

An LP batch is a struct-of-arrays over B independent LPs of identical shape:

    maximize    c . x
    subject to  A x <= b,   x >= 0

with ``A: (B, m, n)``, ``b: (B, m)``, ``c: (B, n)``.

The simplex tableau column map follows the paper (Sec. 3.1), with the
two auxiliary columns folded in:

    column 0                : b_i (bound column); objective row stores -z0
    columns 1 .. n          : original variables x_j
    columns n+1 .. n+m      : slack variables s_i
    columns n+m+1 .. n+2m   : artificial variables a_i  (dense layout only)
    row m (last)            : objective row (reduced costs; entering rule
                              picks the max positive coefficient)

Rows with b_i < 0 are negated so the RHS is non-negative and an artificial
variable becomes basic there (two-phase start); rows with b_i >= 0 start
with their slack basic.  Tableau construction happens device-side in jnp —
only (A, b, c) cross host->device, which transfers O(m n) bytes per LP
instead of the paper's O(m (n + 2m)) full-tableau copy.

Tableau STORAGE is owned by ``core/tableau.py``: a
:class:`~repro.core.tableau.TableauSpec` selects between the ``"dense"``
map above and the default ``"compact"`` layout, which drops the
write-only artificial block (``q = 1 + n + m``) without changing any
pivot arithmetic.  :func:`build_tableau` is re-exported here for
backward compatibility.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .tableau import TableauSpec, build_tableau  # noqa: F401  (re-exported API)

# Status codes shared by every solver in the library.
RUNNING = 0
OPTIMAL = 1
UNBOUNDED = 2
INFEASIBLE = 3
ITER_LIMIT = 4
# Retired by the numerical guardrails (core/dispatch.py:apply_guardrails):
# the row's solution or carried state went non-finite, so no
# OPTIMAL/UNBOUNDED/INFEASIBLE certificate can be trusted for it.  The
# opt-in quarantine lane (SolveOptions.quarantine) re-solves such rows on
# the float64 oracle and overwrites the verdict when one is reached.
NUMERICAL = 5

STATUS_NAMES = {
    RUNNING: "running",
    OPTIMAL: "optimal",
    UNBOUNDED: "unbounded",
    INFEASIBLE: "infeasible",
    ITER_LIMIT: "iter_limit",
    NUMERICAL: "numerical",
}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LPBatch:
    """A batch of B identical-shape LPs: max c.x s.t. Ax <= b, x >= 0.

    ``basis0`` optionally carries a warm-start basis per LP: tableau column
    indices (1..n originals, n+1..n+m slacks) of the variables basic at the
    start.  Backends that support warm starts rebuild the tableau for that
    basis and skip phase I when it is primal feasible; LPs whose basis is
    out of range, singular, or infeasible silently fall back to the cold
    two-phase start (see ``build_tableau``).
    """

    a: jnp.ndarray  # (B, m, n)
    b: jnp.ndarray  # (B, m)
    c: jnp.ndarray  # (B, n)
    basis0: Optional[jnp.ndarray] = None  # (B, m) int32 warm-start basis

    @property
    def batch(self) -> int:
        return self.a.shape[0]

    @property
    def m(self) -> int:
        return self.a.shape[1]

    @property
    def n(self) -> int:
        return self.a.shape[2]

    def astype(self, dtype) -> "LPBatch":
        return LPBatch(
            self.a.astype(dtype),
            self.b.astype(dtype),
            self.c.astype(dtype),
            self.basis0,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SharedLPBatch:
    """B LPs over ONE constraint matrix: max c_k.x s.t. A x <= b_k, x >= 0.

    The shared-structure counterpart of :class:`LPBatch` for the paper's
    headline workloads (support sweeps, reachability, scenario analysis),
    where thousands of LPs differ only in objective ``c`` and/or RHS
    ``b`` over the SAME ``A``.  Storing ``A`` once drops the stored
    problem data from O(m n) to O(m + n + m n / B) bytes per LP, and the
    revised-simplex engine (``core/revised.py``) keeps only O(m^2) basis
    state per LP — every pricing/ratio-test contraction reads ``A`` from
    the single broadcast buffer.

    ``basis0`` carries an optional warm-start basis with the same column
    convention as :class:`LPBatch` (1..n originals, n+1..n+m slacks).

    The container is a registered pytree and supports the dispatch
    layer's gather/pad/stage protocol via :meth:`take` (``a`` is shared,
    so only the per-LP arrays are gathered).  :meth:`densify` broadcasts
    back to a plain :class:`LPBatch` for backends that need per-LP
    tableaus (the reference oracle, pdhg).
    """

    a: jnp.ndarray  # (m, n) — ONE constraint matrix for the whole batch
    b: jnp.ndarray  # (B, m)
    c: jnp.ndarray  # (B, n)
    basis0: Optional[jnp.ndarray] = None  # (B, m) int32 warm-start basis

    @property
    def batch(self) -> int:
        return self.b.shape[0]

    @property
    def m(self) -> int:
        return self.a.shape[0]

    @property
    def n(self) -> int:
        return self.a.shape[1]

    def astype(self, dtype) -> "SharedLPBatch":
        return SharedLPBatch(
            self.a.astype(dtype),
            self.b.astype(dtype),
            self.c.astype(dtype),
            self.basis0,
        )

    def take(self, idx) -> "SharedLPBatch":
        """Gather per-LP rows; the shared ``A`` rides along untouched."""
        return SharedLPBatch(
            self.a,
            self.b[idx],
            self.c[idx],
            None if self.basis0 is None else self.basis0[idx],
        )

    def densify(self) -> LPBatch:
        """Materialize the per-LP-``A`` view for shared-blind backends."""
        return LPBatch(
            jnp.broadcast_to(self.a, (self.batch, self.m, self.n)),
            self.b,
            self.c,
            self.basis0,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ResumeState:
    """Mid-solve simplex state, carried between dispatch rounds.

    The simplex tableau is fully determined by its basis, but only up to
    floating-point rebuild error — so the resume path
    (``SolveOptions.resume="basis"``) carries the EXACT iteration state
    (tableau, basis, phase) between capped rounds instead of re-deriving
    it.  Continuing from a carried state replays the same arithmetic an
    uninterrupted solve would have performed, which is what makes
    round-resumed results bit-identical to a single full solve.

    Both accelerated drivers produce and accept this state: the XLA
    lockstep loop carries it through ``while_loop`` and the Pallas kernel
    writes it back as extra outputs (``want_state``).  All arrays are
    unpadded (true ``m``/``q``); drivers re-apply their own padding.

    The state is layout-self-describing: ``tab.shape[-1]`` recovers the
    :class:`~repro.core.tableau.TableauSpec` it was produced under
    (``TableauSpec.from_tableau``), so resumed rounds continue in the
    SAME layout regardless of the resuming call's options — which keeps
    a ``resume="basis"`` splice bit-identical in either layout.

    This is one of two implementations of the dispatch layer's resume
    protocol: any registered-pytree record with a ``batch`` property and
    a ``take(idx)`` gather works (the round scheduler handles padding,
    staging, and concatenation generically via ``jax.tree_util``).  The
    first-order counterpart is
    :class:`~repro.core.pdhg.PDHGResumeState`.
    """

    tab: jnp.ndarray  # (B, m+1, q) tableau at interruption
    basis: jnp.ndarray  # (B, m) int32 current basis
    phase: jnp.ndarray  # (B,) int32 simplex phase (1 or 2)

    @property
    def batch(self) -> int:
        return self.tab.shape[0]

    def take(self, idx) -> "ResumeState":
        """Gather state rows (compaction gather between rounds)."""
        return ResumeState(self.tab[idx], self.basis[idx], self.phase[idx])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LPSolution:
    """Result batch: objective, primal point, status, iterations used.

    ``basis`` is the final simplex basis (same column convention as
    ``LPBatch.basis0``) when the producing backend tracks one, else None.
    Feeding it back as the next solve's ``basis0`` is the warm-start path
    used by the reachability sweep (core/support.py).

    ``y`` is the dual point (one multiplier per constraint row) when the
    producing backend iterates in primal-dual space — the first-order
    ``pdhg`` backend reports its dual iterate here, which at ``OPTIMAL``
    is an approximate solution of ``min b.y  s.t.  A'y >= c, y >= 0``.
    The simplex backends leave it None (their duals live implicitly in
    the tableau's slack reduced costs).

    ``phase_rewrites`` comes from the Pallas tableau kernel: the number
    of iterations in which a tile ran the phase-I to phase-II objective
    rewrite, on the tile's first row and 0 on its other rows, so a sum
    over whole tiles counts each tile once.  Other backends leave it
    None, and so does a solution rebuilt from several dispatches:
    :attr:`~repro.core.backends.SolveStats.phase_rewrites` sums it.
    """

    objective: jnp.ndarray  # (B,)
    x: jnp.ndarray  # (B, n)
    status: jnp.ndarray  # (B,) int32, see STATUS_* above
    iterations: jnp.ndarray  # (B,) int32
    basis: Optional[jnp.ndarray] = None  # (B, m) int32 final basis
    y: Optional[jnp.ndarray] = None  # (B, m) dual point (first-order backends)
    phase_rewrites: Optional[jnp.ndarray] = None  # (B,) int32 per-tile count


def num_cols(m: int, n: int) -> int:
    """DENSE-layout tableau columns: b column + vars + slacks + artificials.

    Legacy helper, kept for the dense layout only — layout-aware code
    should read :attr:`repro.core.tableau.TableauSpec.q` instead.
    """
    return 1 + n + 2 * m


def auto_cap(m: int, n: int) -> int:
    """The library-wide auto iteration cap for ``max_iters <= 0``.

    Every built-in solver (oracle, lockstep simplex, Pallas kernel) and
    the compaction engine must agree on this rule — compaction's
    bit-identity guarantee relies on its final round using the same cap a
    plain solve would.
    """
    return 50 * (m + n)


def random_lp_batch(
    rng: np.random.Generator,
    batch: int,
    m: int,
    n: int,
    feasible_start: bool = True,
    dtype=np.float32,
) -> LPBatch:
    """Generate random bounded LPs in the style of the paper's benchmarks.

    feasible_start=True  -> all b >= 0 (origin feasible; single-phase).
    feasible_start=False -> a subset of constraints has b < 0 with row
                            coefficients arranged so the LP stays feasible
                            (x >= lo element-wise with box upper bounds),
                            forcing the two-phase path like the paper's
                            "infeasible initial basic solution" class.
    """
    if feasible_start:
        a = rng.uniform(-1.0, 1.0, size=(batch, m, n))
        # Diagonal-ish strengthening keeps the region bounded.
        for j in range(min(m, n)):
            a[:, j, j] = np.abs(a[:, j, j]) + 1.0
        row_caps = rng.uniform(1.0, 10.0, size=(batch, m))
        b = row_caps
        c = rng.uniform(0.1, 1.0, size=(batch, n))
        return LPBatch(
            jnp.asarray(a, dtype), jnp.asarray(b, dtype), jnp.asarray(c, dtype)
        )
    # Infeasible start: box  lo <= x <= hi  with 0 < lo < hi, written as
    #   x <= hi        (b >= 0)
    #  -x <= -lo       (b < 0)   -> needs artificials
    # plus random extra cover constraints to vary the active set.
    n_eff = n
    lo = rng.uniform(0.5, 1.0, size=(batch, n_eff))
    hi = lo + rng.uniform(0.5, 2.0, size=(batch, n_eff))
    extra = m - 2 * n_eff
    if extra < 0:
        raise ValueError(f"need m >= 2n for infeasible-start generator, got m={m} n={n}")
    a = np.zeros((batch, m, n_eff))
    b = np.zeros((batch, m))
    eye = np.eye(n_eff)
    a[:, :n_eff, :] = eye[None]
    b[:, :n_eff] = hi
    a[:, n_eff : 2 * n_eff, :] = -eye[None]
    b[:, n_eff : 2 * n_eff] = -lo
    if extra > 0:
        w = np.abs(rng.uniform(0.1, 1.0, size=(batch, extra, n_eff)))
        # Keep extras loose enough to preserve feasibility: w.hi + slack.
        a[:, 2 * n_eff :, :] = w
        b[:, 2 * n_eff :] = np.einsum("bkn,bn->bk", w, hi) + rng.uniform(
            0.1, 1.0, size=(batch, extra)
        )
    c = rng.uniform(0.1, 1.0, size=(batch, n_eff))
    return LPBatch(jnp.asarray(a, dtype), jnp.asarray(b, dtype), jnp.asarray(c, dtype))


def random_shared_lp_batch(
    rng: np.random.Generator,
    batch: int,
    m: int,
    n: int,
    feasible_start: bool = True,
    dtype=np.float32,
) -> SharedLPBatch:
    """Random LPs over ONE shared ``A`` — the scenario-analysis workload.

    The shared-structure twin of :func:`random_lp_batch`: the same two
    problem classes, but the constraint matrix is drawn once and only
    ``b``/``c`` vary per LP.  ``densify()`` recovers the per-LP-``A``
    batch the dense backends expect, so the two paths are directly
    comparable on identical problems.
    """
    if feasible_start:
        a = rng.uniform(-1.0, 1.0, size=(m, n))
        for j in range(min(m, n)):
            a[j, j] = np.abs(a[j, j]) + 1.0
        b = rng.uniform(1.0, 10.0, size=(batch, m))
        c = rng.uniform(0.1, 1.0, size=(batch, n))
        return SharedLPBatch(
            jnp.asarray(a, dtype), jnp.asarray(b, dtype), jnp.asarray(c, dtype)
        )
    # Infeasible start: the box  lo <= x <= hi  of random_lp_batch, with the
    # STRUCTURE [I; -I; W] shared and only the bound values per-LP.
    lo = rng.uniform(0.5, 1.0, size=(batch, n))
    hi = lo + rng.uniform(0.5, 2.0, size=(batch, n))
    extra = m - 2 * n
    if extra < 0:
        raise ValueError(f"need m >= 2n for infeasible-start generator, got m={m} n={n}")
    a = np.zeros((m, n))
    b = np.zeros((batch, m))
    eye = np.eye(n)
    a[:n, :] = eye
    b[:, :n] = hi
    a[n : 2 * n, :] = -eye
    b[:, n : 2 * n] = -lo
    if extra > 0:
        w = np.abs(rng.uniform(0.1, 1.0, size=(extra, n)))
        a[2 * n :, :] = w
        b[:, 2 * n :] = hi @ w.T + rng.uniform(0.1, 1.0, size=(batch, extra))
    c = rng.uniform(0.1, 1.0, size=(batch, n))
    return SharedLPBatch(
        jnp.asarray(a, dtype), jnp.asarray(b, dtype), jnp.asarray(c, dtype)
    )


def random_hyperbox_batch(
    rng: np.random.Generator,
    batch: int,
    n: int,
    dtype=np.float32,
):
    """Random box bounds and direction vectors for the hyperbox solver.

    Returns (lo, hi, directions) with lo <= hi, shapes (batch, n) each for
    lo/hi broadcastable — the paper's Table 1 setup uses ONE box and many
    directions; we allow both but default to per-LP boxes.
    """
    lo = rng.uniform(-2.0, 0.0, size=(batch, n))
    hi = lo + rng.uniform(0.5, 3.0, size=(batch, n))
    directions = rng.normal(size=(batch, n))
    return (
        jnp.asarray(lo, dtype),
        jnp.asarray(hi, dtype),
        jnp.asarray(directions, dtype),
    )

"""Plain reference solver for canonical LPs: a textbook two-phase tableau simplex.

    maximise c.x  subject to  A x <= b,  x >= 0

Written for the benchmark alone and independent of ``src/``: one LP at a
time in NumPy, Dantzig's rule (most negative reduced cost enters), the
minimum-ratio test, and phase I over artificial variables for the rows
with ``b_i < 0``.  ``precision`` selects the arithmetic:

- ``"float64"``: the reference that decides ``correct``;
- ``"bfloat16"``: the control, the nearest precision below the float32
  that the configurations state.  Every operation is done in float32 and
  rounded to bfloat16, which is bfloat16 arithmetic without fused
  multiply-adds.  The comparison must fail it.

Statuses follow the program's public codes: 1 optimal, 2 unbounded,
3 infeasible, 4 iteration limit.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy.linalg import blas

OPTIMAL, UNBOUNDED, INFEASIBLE, ITER_LIMIT = 1, 2, 3, 4

#: Per precision: working dtype, pivot/reduced-cost tolerance, and the
#: phase-I infeasibility tolerance relative to ``1 + max |b|``.
PRECISIONS = {
    "float64": (np.float64, 1e-9, 1e-7),
    "bfloat16": (np.float32, 1e-2, 1e-1),
}


def _rounder(precision: str):
    if precision == "bfloat16":
        return lambda v: np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
    return lambda v: v


def solve_one(a, b, c, precision: str = "float64", max_pivots: int = 0):
    """Solve one LP; returns ``(status, objective, x)`` in float64.

    ``max_pivots`` caps both phases together; 0 means ``50 (m + n)``.
    """
    dtype, tol, feas_tol = PRECISIONS[precision]
    rnd = _rounder(precision)
    a = rnd(np.asarray(a, dtype))
    b = rnd(np.asarray(b, dtype))
    c = rnd(np.asarray(c, dtype))
    m, n = a.shape
    cap = max_pivots or 50 * (m + n)
    neg = b < 0
    arts = np.nonzero(neg)[0]
    k = arts.size
    # Columns: x (n) | slacks (m) | artificials (k) | rhs.
    width = n + m + k
    t = np.zeros((m + 1, width + 1), dtype)
    sign = np.where(neg, -1.0, 1.0).astype(dtype)
    t[:m, :n] = a * sign[:, None]
    t[np.arange(m), n + np.arange(m)] = sign
    t[arts, n + m + np.arange(k)] = 1.0
    t[:m, -1] = b * sign
    basis = n + np.arange(m)
    basis[arts] = n + m + np.arange(k)
    allowed = np.ones(width, bool)

    def pivot(r, j):
        row = rnd(t[r] / t[r, j])
        if precision == "float64":
            # In-place rank-1 update t -= col row^T (BLAS on the transposed view).
            blas.dger(-1.0, row, t[:, j].copy(), a=t.T, overwrite_a=True)
        else:
            t[:] = rnd(t - rnd(np.outer(t[:, j], row)))
        t[r] = row
        basis[r] = j

    def run(budget):
        """Pivot on the objective row ``t[m]`` until optimal; returns (status, pivots)."""
        for used in range(budget):
            red = np.where(allowed, t[m, :width], np.inf)
            j = int(np.argmin(red))
            if red[j] >= -tol:
                return OPTIMAL, used
            col = t[:m, j]
            ok = col > tol
            if not ok.any():
                return UNBOUNDED, used
            ratio = np.where(ok, t[:m, -1] / np.where(ok, col, 1.0), np.inf)
            pivot(int(np.argmin(ratio)), j)
        return ITER_LIMIT, budget

    used = 0
    if k:
        # Phase I: maximise -sum(artificials), priced out of the basic rows.
        t[m, :] = 0.0
        t[m, n + m : width] = 1.0
        t[m] = rnd(t[m] - rnd(t[arts].sum(axis=0)))
        status, used = run(cap)
        if status == ITER_LIMIT:
            return ITER_LIMIT, -np.inf, np.zeros(n)
        if -t[m, -1] > feas_tol * (1.0 + float(np.max(np.abs(b)))):
            return INFEASIBLE, -np.inf, np.zeros(n)
        # Drive the artificials left basic (at level zero) out of the basis.
        for r in np.nonzero(basis >= n + m)[0]:
            cand = np.nonzero(np.abs(t[r, : n + m]) > tol)[0]
            if cand.size:
                pivot(int(r), int(cand[0]))
        allowed[n + m :] = False
    # Phase II: maximise c.x, priced out of the basic rows.
    t[m, :] = 0.0
    t[m, :n] = -c
    for r in range(m):
        j = basis[r]
        if j < n and t[m, j] != 0.0:
            t[m] = rnd(t[m] - rnd(t[m, j] * t[r]))
    status, _ = run(cap - used)
    x = np.zeros(n)
    rows = np.nonzero(basis < n)[0]
    x[basis[rows]] = t[rows, -1]
    if status != OPTIMAL:
        return status, -np.inf, np.zeros(n)
    return OPTIMAL, float(np.dot(np.asarray(c, np.float64), x)), x


def solve(a, b, c, precision: str = "float64"):
    """Solve a batch row by row; returns ``(status, objective, x)`` arrays."""
    out = [solve_one(a[i], b[i], c[i], precision) for i in range(len(b))]
    status = np.array([o[0] for o in out], np.int32)
    objective = np.array([o[1] for o in out], np.float64)
    x = np.stack([o[2] for o in out]) if out else np.zeros((0, np.shape(c)[-1]))
    return status, objective, x

"""General-form LP problems and canonicalization to the paper's standard form.

The paper's solver consumes one canonical shape —

    maximize  c . x   s.t.  A x <= b,  x >= 0

— but real workloads (cuPDLP-style libraries, reachability front-ends,
routing/allocation services) speak *general form*:

    minimize|maximize  c . x
    subject to         bl <= A x <= bu        (equality rows: bl == bu)
                       lo <= x  <= hi         (free vars: lo = -inf)

``LPProblem`` is a batched pytree holding that general form; ``canonicalize``
lowers it to an ``LPBatch`` with purely value-level masking (all structural
decisions — objective sense, whether any variable is free — are static pytree
metadata fixed at construction), so the lowering itself jits and batches.
``uncanonicalize`` maps an ``LPSolution`` on the canonical batch back to user
coordinates (primal shift/split undone, objective sign restored).

Lowering scheme (static shapes; rows/columns are *disabled*, never removed):

  * objective     max (s c) . x'   with s = +1 (maximize) / -1 (minimize)
  * shift         x = lo' + x_pos - x_neg, lo' = lo where finite else 0
  * upper rows    A x <= bu        ->  A x' <= bu - A lo'      (finite bu)
  * lower rows    bl <= A x        -> -A x' <= A lo' - bl      (finite bl)
  * bound rows    x_j <= hi_j      ->  x'_j <= hi_j - lo'_j    (finite hi)
  * free split    x_neg columns exist iff any lo_j = -inf (static flag);
                  per-variable the column is value-masked to all-zero when
                  the variable is not free, which keeps it permanently
                  non-basic (reduced cost 0 never enters).

A row whose bound is infinite becomes the trivially-satisfied row
``0 . x' <= 1`` — its slack starts basic and never pivots.  Canonical sizes
are therefore static: m' = 2 m + n worst case, n' = n (or 2 n with the
free split); the lower-row and bound-row blocks are skipped entirely
(static ``row_lower`` / ``var_upper`` flags) when no bound in them is
finite, so one-sided problems keep the paper's original tableau size.

Problems with *no* general rows and all-finite bounds carry the static
``boxlike`` flag: the front-end routes them to the closed-form hyperbox
solver (paper Sec. 6) instead of the simplex.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .lp import INFEASIBLE, LPBatch, LPSolution, NUMERICAL, OPTIMAL, SharedLPBatch


def _static(default):
    return dataclasses.field(metadata=dict(static=True), default=default)


#: Field -> whether ±inf is legitimate there.  Bounds use infinity to mean
#: "unbounded"; the objective and constraint coefficients must be finite.
_VALIDATE_FIELDS = (
    ("c", False),
    ("a", False),
    ("bl", True),
    ("bu", True),
    ("lo", True),
    ("hi", True),
)


def validate_problem(problem: "LPProblem", where: str = "LPProblem") -> None:
    """Reject NaN/Inf garbage up front, naming the offending field.

    NaN is rejected everywhere; Inf is rejected in ``c``/``a`` (where it
    can only poison the arithmetic) but legitimate in the bounds (where
    it means "unbounded").  Called by :meth:`LPProblem.make` (opt out
    with ``validate=False``) and ``LPEngine.submit`` — garbage is
    cheaper to reject at the host boundary than to burn a megabatch
    dispatch round before the device-side guardrails catch it.

    Raises
    ------
    ValueError
        Naming the first offending field, e.g. ``"LPProblem.c contains
        NaN"``.
    """
    for field, inf_ok in _VALIDATE_FIELDS:
        v = np.asarray(getattr(problem, field))
        if np.isnan(v).any():
            raise ValueError(f"{where}.{field} contains NaN")
        if not inf_ok and np.isinf(v).any():
            raise ValueError(
                f"{where}.{field} contains non-finite values (Inf)"
            )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LPProblem:
    """A batch of B general-form LPs of identical (m, n) shape.

    Build instances with :meth:`LPProblem.make`, which normalizes shapes,
    fills defaults (``lo = 0``, ``hi = +inf``, no rows), and derives the
    static structure flags from the concrete bound arrays.
    """

    c: jnp.ndarray  # (B, n) objective
    a: jnp.ndarray  # (B, m, n) general rows (m may be 0)
    bl: jnp.ndarray  # (B, m) row lower bounds (-inf = none)
    bu: jnp.ndarray  # (B, m) row upper bounds (+inf = none)
    lo: jnp.ndarray  # (B, n) variable lower bounds (-inf = free below)
    hi: jnp.ndarray  # (B, n) variable upper bounds (+inf = none)
    # Optional warm-start basis in CANONICAL column space (the space of the
    # LPBatch that `canonicalize` emits, whose final basis a previous
    # solve reports in LPSolution.basis).  A hint only: rows that are not
    # usable fall back to the cold two-phase start, and dropping it never
    # changes results.
    basis0: Optional[jnp.ndarray] = None  # (B, m') int32
    maximize: bool = _static(True)
    split: bool = _static(False)  # canonical form carries x_neg columns
    boxlike: bool = _static(False)  # no rows + finite box: hyperbox route
    # Structure flags gating canonical row blocks (True is always safe —
    # the blocks degrade to disabled rows; False skips them entirely so
    # one-sided problems keep the paper's original tableau size).
    row_lower: bool = _static(True)  # any finite bl: emit the -Ax <= -bl block
    var_upper: bool = _static(True)  # any finite hi: emit the x <= hi block

    @property
    def batch(self) -> int:
        return self.c.shape[0]

    @property
    def m(self) -> int:
        return self.a.shape[1]

    @property
    def n(self) -> int:
        return self.c.shape[-1]

    @property
    def dtype(self):
        return self.c.dtype

    # -- construction -------------------------------------------------------

    @classmethod
    def make(
        cls,
        c,
        a=None,
        bl=None,
        bu=None,
        lo=None,
        hi=None,
        maximize: bool = True,
        dtype=None,
        basis0=None,
        validate: bool = True,
    ) -> "LPProblem":
        """Normalize user inputs (host-side) into a batched ``LPProblem``.

        Parameters
        ----------
        c : array_like
            Objective, unbatched ``(n,)`` or batched ``(B, n)``.
        a : array_like, optional
            General constraint rows, ``(m, n)`` or ``(B, m, n)``; defaults
            to no rows.
        bl, bu : array_like, optional
            Row lower/upper bounds (equality rows: ``bl == bu``); default
            unbounded.  Broadcast over the batch.
        lo, hi : array_like, optional
            Variable bounds; default ``lo = 0``, ``hi = +inf`` (the
            paper's sign-restricted variables).  ``lo = -inf`` marks a
            free variable (canonical x+/x- split).
        maximize : bool, default True
            Objective sense (static pytree metadata).
        dtype : numpy dtype, optional
            Data dtype; inferred from ``c`` when omitted.
        basis0 : array_like, optional
            ``(B, m')`` int32 warm-start basis in canonical column space —
            feed a previous ``LPSolution.basis`` from a solve of a
            same-shaped problem (the support-function sweep pattern).
        validate : bool, default True
            Up-front NaN/Inf input validation (:func:`validate_problem`):
            NaN anywhere, or Inf in ``c``/``a``, raises ``ValueError``
            naming the field.  ``False`` skips the check — for callers
            that construct provably-finite data in a hot loop.

        Returns
        -------
        LPProblem
            Batched problem with the static structure flags (``split``,
            ``boxlike``, ...) derived from the concrete bounds — so call
            this outside jit.
        """
        c = np.asarray(c)
        if dtype is None:
            dtype = c.dtype if np.issubdtype(c.dtype, np.floating) else np.float64
        c = np.atleast_2d(np.asarray(c, dtype))  # (B, n)
        bsz, n = c.shape

        if a is None:
            a = np.zeros((bsz, 0, n), dtype)
        else:
            a = np.asarray(a, dtype)
            if a.ndim == 2:
                a = np.broadcast_to(a[None], (bsz, *a.shape))
            a = np.ascontiguousarray(a)
        m = a.shape[1]

        def row_bound(v, fill):
            if v is None:
                return np.full((bsz, m), fill, dtype)
            v = np.asarray(v, dtype)
            return np.ascontiguousarray(np.broadcast_to(np.atleast_1d(v), (bsz, m)))

        def var_bound(v, fill):
            if v is None:
                return np.full((bsz, n), fill, dtype)
            v = np.asarray(v, dtype)
            return np.ascontiguousarray(np.broadcast_to(np.atleast_1d(v), (bsz, n)))

        bl = row_bound(bl, -np.inf)
        bu = row_bound(bu, np.inf)
        lo = var_bound(lo, 0.0)
        hi = var_bound(hi, np.inf)

        split = bool(np.isneginf(lo).any())
        boxlike = m == 0 and bool(np.isfinite(lo).all() and np.isfinite(hi).all())
        if validate:
            # Arrays are already host-side numpy here — the check costs
            # no device sync.
            for field, arr, inf_ok in (
                ("c", c, False), ("a", a, False), ("bl", bl, True),
                ("bu", bu, True), ("lo", lo, True), ("hi", hi, True),
            ):
                if np.isnan(arr).any():
                    raise ValueError(f"LPProblem.{field} contains NaN")
                if not inf_ok and np.isinf(arr).any():
                    raise ValueError(
                        f"LPProblem.{field} contains non-finite values (Inf)"
                    )
        return cls(
            c=jnp.asarray(c),
            a=jnp.asarray(a),
            bl=jnp.asarray(bl),
            bu=jnp.asarray(bu),
            lo=jnp.asarray(lo),
            hi=jnp.asarray(hi),
            basis0=None if basis0 is None else jnp.asarray(basis0, jnp.int32),
            maximize=bool(maximize),
            split=split,
            boxlike=boxlike,
            row_lower=bool(np.isfinite(bl).any()),
            var_upper=bool(np.isfinite(hi).any()),
        )

    @classmethod
    def from_batch(cls, batch: LPBatch) -> "LPProblem":
        """Wrap an already-canonical ``LPBatch`` (max, Ax <= b, x >= 0).

        Parameters
        ----------
        batch : LPBatch
            Canonical batch; its ``basis0`` warm-start hint is preserved.

        Returns
        -------
        LPProblem
            General-form view with one-sided rows and default bounds.
        """
        bsz, m, _ = batch.a.shape
        neg_inf = jnp.full((bsz, m), -jnp.inf, batch.a.dtype)
        return cls(
            c=batch.c,
            a=batch.a,
            bl=neg_inf,
            bu=batch.b,
            lo=jnp.zeros_like(batch.c),
            hi=jnp.full_like(batch.c, jnp.inf),
            basis0=batch.basis0,
            maximize=True,
            split=False,
            boxlike=False,
            row_lower=False,
            var_upper=False,
        )

    # -- shape padding (bucketing support) ----------------------------------

    def pad_to(self, m_pad: int, n_pad: int) -> "LPProblem":
        """Grow to shape class (m_pad, n_pad) with *disabled* rows/columns.

        Padding rows get (-inf, +inf) bounds (lowered to no-op rows).
        Padding variables are dead columns — zero cost, zero constraint
        coefficients, lo = 0, hi = +inf — permanently non-basic (reduced
        cost stays 0), so they stay at 0 without forcing the canonical
        bound-row block onto problems that never had one.  Boxlike
        problems instead pin padding variables at lo = hi = 0: the
        closed-form hyperbox route needs finite bounds.
        """
        if m_pad < self.m or n_pad < self.n:
            raise ValueError(
                f"pad_to({m_pad}, {n_pad}) smaller than problem ({self.m}, {self.n})"
            )
        if (m_pad, n_pad) == (self.m, self.n):
            return self
        dm, dn = m_pad - self.m, n_pad - self.n
        pad_rows = [(0, 0), (0, dm)]
        pad_vars = [(0, 0), (0, dn)]
        boxlike_pad = self.boxlike and m_pad == 0
        hi_fill = 0.0 if boxlike_pad else jnp.inf
        return LPProblem(
            c=jnp.pad(self.c, pad_vars),
            a=jnp.pad(self.a, [(0, 0), (0, dm), (0, dn)]),
            bl=jnp.pad(self.bl, pad_rows, constant_values=-jnp.inf),
            bu=jnp.pad(self.bu, pad_rows, constant_values=jnp.inf),
            lo=jnp.pad(self.lo, pad_vars),
            hi=jnp.pad(self.hi, pad_vars, constant_values=hi_fill),
            # Padding changes the canonical column layout, so a carried
            # basis would point at the wrong columns; drop the hint
            # (semantically a cold start, never a wrong answer).
            basis0=None,
            maximize=self.maximize,
            split=self.split,
            boxlike=boxlike_pad,
            row_lower=self.row_lower,
            var_upper=self.var_upper or (dn > 0 and boxlike_pad),
        )


def stack_problems(problems: Sequence[LPProblem]) -> LPProblem:
    """Concatenate same-shape problems along the batch axis (one bucket).

    Parameters
    ----------
    problems : sequence of LPProblem
        Problems of one ``(m, n)`` shape class and one objective sense.
        Warm-start bases are stacked only when every problem carries one.

    Returns
    -------
    LPProblem
        One batched problem; structure flags are the union (a flag that is
        True for any member is True for the stack).

    Raises
    ------
    ValueError
        On an empty list, mixed shapes, or mixed objective senses.
    """
    if not problems:
        raise ValueError("cannot stack an empty problem list")
    shapes = {(p.m, p.n) for p in problems}
    senses = {p.maximize for p in problems}
    if len(shapes) > 1:
        raise ValueError(f"stack_problems needs one shape class, got {sorted(shapes)}")
    if len(senses) > 1:
        raise ValueError("stack_problems needs a uniform objective sense")
    cat = lambda f: jnp.concatenate([getattr(p, f) for p in problems], axis=0)
    return LPProblem(
        c=cat("c"),
        a=cat("a"),
        bl=cat("bl"),
        bu=cat("bu"),
        lo=cat("lo"),
        hi=cat("hi"),
        basis0=cat("basis0") if all(p.basis0 is not None for p in problems) else None,
        maximize=problems[0].maximize,
        split=any(p.split for p in problems),
        boxlike=all(p.boxlike for p in problems),
        row_lower=any(p.row_lower for p in problems),
        var_upper=any(p.var_upper for p in problems),
    )


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Canonicalized:
    """A canonical ``LPBatch`` plus the data needed to map solutions back."""

    batch: LPBatch
    c_user: jnp.ndarray  # (B, n) original objective
    shift: jnp.ndarray  # (B, n) lo' applied as x = lo' + x'
    n: int = _static(0)
    sign: int = _static(1)  # +1 maximize, -1 minimize
    split: bool = _static(False)


def canonicalize(problem: LPProblem) -> Canonicalized:
    """Lower general form to the paper's ``max c.x, Ax <= b, x >= 0``.

    Pure jnp value-masking over static shapes — jit/vmap friendly.

    Parameters
    ----------
    problem : LPProblem
        General-form batch.  A ``basis0`` warm-start hint is threaded onto
        the canonical batch unchanged (it already lives in canonical
        column space).

    Returns
    -------
    Canonicalized
        The canonical ``LPBatch`` plus the shift/sign/split data
        :func:`uncanonicalize` needs to map solutions back.

    Raises
    ------
    ValueError
        If ``basis0`` has a row count that cannot match the canonical
        form produced by the problem's structure flags.
    """
    p = problem
    bsz, m, n = p.a.shape
    dtype = p.a.dtype
    sign = 1 if p.maximize else -1

    lo0 = jnp.where(jnp.isfinite(p.lo), p.lo, 0.0).astype(dtype)  # shift
    free = jnp.isneginf(p.lo)  # (B, n)
    a_lo = jnp.einsum(
        "bmn,bn->bm", p.a, lo0, precision=jax.lax.Precision.HIGHEST
    )

    fin_u = jnp.isfinite(p.bu)
    a_blocks = [jnp.where(fin_u[:, :, None], p.a, 0.0)]
    b_blocks = [jnp.where(fin_u, p.bu - a_lo, 1.0)]
    if p.row_lower:
        fin_l = jnp.isfinite(p.bl)
        a_blocks.append(jnp.where(fin_l[:, :, None], -p.a, 0.0))
        b_blocks.append(jnp.where(fin_l, a_lo - p.bl, 1.0))
    if p.var_upper:
        fin_h = jnp.isfinite(p.hi)
        eye = jnp.broadcast_to(jnp.eye(n, dtype=dtype), (bsz, n, n))
        a_blocks.append(jnp.where(fin_h[:, :, None], eye, 0.0))
        b_blocks.append(jnp.where(fin_h, p.hi - lo0, 1.0))

    a_std = jnp.concatenate(a_blocks, axis=1)  # (B, m', n), m' <= 2m+n
    b_std = jnp.concatenate(b_blocks, axis=1)  # (B, m')
    if a_std.shape[1] == 0:
        # Constraint-free problems (m = 0, nothing bounded above): one
        # disabled row keeps the tableau well-formed; the simplex then
        # reports OPTIMAL at 0 or UNBOUNDED as the costs dictate.
        a_std = jnp.zeros((bsz, 1, n), dtype)
        b_std = jnp.ones((bsz, 1), dtype)
    c_std = (sign * p.c).astype(dtype)
    if p.split:
        a_neg = jnp.where(free[:, None, :], -a_std, 0.0)
        a_std = jnp.concatenate([a_std, a_neg], axis=2)  # (B, 2m+n, 2n)
        c_std = jnp.concatenate([c_std, jnp.where(free, -c_std, 0.0)], axis=1)

    basis0 = p.basis0
    if basis0 is not None and basis0.shape[-1] != a_std.shape[1]:
        raise ValueError(
            f"basis0 has {basis0.shape[-1]} rows but the canonical form has "
            f"{a_std.shape[1]} — feed a basis from a solve of a problem with "
            "the same structure flags"
        )

    return Canonicalized(
        batch=LPBatch(a_std, b_std, c_std, basis0=basis0),
        c_user=p.c,
        shift=lo0,
        n=n,
        sign=sign,
        split=p.split,
    )


def canonicalize_shared(
    problem: LPProblem, validate: bool = True
) -> Canonicalized:
    """Canonicalize a batch whose rows share ONE constraint system.

    The shared-structure entry into the canonical pipeline: runs
    :func:`canonicalize` and then collapses the batched constraint
    matrix to a single stored copy
    (:class:`~repro.core.lp.SharedLPBatch`), which the dispatch layer
    routes to the revised-simplex backends (``xla-shared`` /
    ``pallas-shared``) — O(m²) iteration state per LP instead of an
    O(m·n) tableau.  :func:`uncanonicalize` works unchanged on the
    result (it only reads the solution).

    Note the input ``LPProblem`` already replicates ``A`` B times in
    host/device memory — this helper removes the replication from the
    SOLVE side only.  Callers that never had a per-LP ``A`` to begin
    with should build the shared batch directly
    (``Polytope.to_shared_batch``, ``repro.SharedLPBatch``) and skip the
    broadcast entirely.

    Parameters
    ----------
    problem : LPProblem
        General-form batch whose per-LP constraint data (``a``, row
        bounds, box) is identical across the batch.  Per-LP ``c`` is the
        expected variation; per-LP ``lo`` shifts also canonicalize into
        ``b``, which the shared form carries per-LP anyway.
    validate : bool, default True
        Host-side checks: that the canonical constraint rows really are
        identical across the batch, and that the shared system is
        numerically sane — no NaN anywhere, no Inf in the stored ``A``
        (one poisoned coefficient in the SHARED matrix would corrupt
        every LP of every dispatch round).  With False the first LP's
        matrix is trusted — the caller's assertion.

    Raises
    ------
    ValueError
        If ``validate`` finds rows with differing canonical ``A``, or
        NaN/Inf where none is legal.
    """
    canon = canonicalize(problem)
    batch = canon.batch
    a0 = batch.a[0]
    if validate:
        if bool(jnp.any(batch.a != a0[None])):
            raise ValueError(
                "canonicalize_shared: canonical constraint matrices differ "
                "across the batch; solve as a plain LPBatch instead"
            )
        if not bool(jnp.all(jnp.isfinite(a0))):
            raise ValueError(
                "canonicalize_shared: the shared constraint matrix "
                "contains NaN/Inf — reject the input instead of "
                "poisoning every batched variant"
            )
        if bool(jnp.any(jnp.isnan(batch.b))) or bool(jnp.any(jnp.isnan(batch.c))):
            raise ValueError(
                "canonicalize_shared: canonical b/c contain NaN"
            )
    shared = SharedLPBatch(a0, batch.b, batch.c, basis0=batch.basis0)
    return dataclasses.replace(canon, batch=shared)


def uncanonicalize(canon: Canonicalized, sol: LPSolution) -> LPSolution:
    """Map a canonical-form solution back to user coordinates.

    Primal: x = shift + x_pos - x_neg.  Objective is re-evaluated as
    ``c_user . x`` (exact in user space, no sign algebra); non-optimal LPs
    report -inf when maximizing, +inf when minimizing — except
    guardrail-retired ``NUMERICAL`` rows, which report NaN ("no trusted
    answer", distinct from the honest infeasible/unbounded infinities).

    Parameters
    ----------
    canon : Canonicalized
        The record :func:`canonicalize` produced for the problem.
    sol : LPSolution
        Solution of ``canon.batch`` from any backend.

    Returns
    -------
    LPSolution
        User-coordinate solution.  ``basis`` stays in canonical column
        space: it is the warm-start currency for the next solve over the
        same canonical structure, not a user-facing quantity.
    """
    n = canon.n
    x = canon.shift + sol.x[:, :n]
    if canon.split:
        x = x - sol.x[:, n : 2 * n]
    ok = sol.status == OPTIMAL
    bad = -jnp.inf if canon.sign > 0 else jnp.inf
    objective = jnp.where(ok, jnp.sum(canon.c_user * x, axis=-1), bad)
    objective = jnp.where(sol.status == NUMERICAL, jnp.nan, objective)
    x = jnp.where(ok[:, None], x, 0.0)
    return LPSolution(
        objective=objective,
        x=x,
        status=sol.status,
        iterations=sol.iterations,
        # Canonical-space basis, preserved for warm-starting the next
        # solve over the same canonical structure (LPProblem.basis0).
        basis=sol.basis,
    )


def solve_box(problem: LPProblem) -> LPSolution:
    """Closed-form solve for ``boxlike`` problems (paper Sec. 6, signed).

    max/min of c.x over [lo, hi] decomposes coordinate-wise; empty boxes
    (lo > hi anywhere) are reported INFEASIBLE.

    Parameters
    ----------
    problem : LPProblem
        A problem whose static ``boxlike`` flag is True (no general rows,
        all-finite box).

    Returns
    -------
    LPSolution
        Exact solutions with 0 iterations per LP.

    Raises
    ------
    ValueError
        If the problem is not boxlike.
    """
    p = problem
    if not p.boxlike:
        raise ValueError("solve_box requires a boxlike problem (no rows, finite box)")
    sign = 1.0 if p.maximize else -1.0
    d = sign * p.c
    pick = jnp.where(d < 0, p.lo, p.hi)
    infeasible = jnp.any(p.lo > p.hi, axis=-1)
    bad = -jnp.inf if p.maximize else jnp.inf
    objective = jnp.where(infeasible, bad, jnp.sum(p.c * pick, axis=-1))
    x = jnp.where(infeasible[:, None], 0.0, pick)
    status = jnp.where(infeasible, INFEASIBLE, OPTIMAL).astype(jnp.int32)
    return LPSolution(
        objective=objective,
        x=x,
        status=status,
        iterations=jnp.zeros((p.batch,), jnp.int32),
    )

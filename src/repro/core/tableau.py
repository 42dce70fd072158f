"""Layout-polymorphic simplex tableau storage: the paper's memory layer.

The paper's central design constraint is tableau memory traffic (Sec.
4.3, memory-coalescent layout), and its follow-up (arXiv:1802.08557)
shows that shrinking per-LP tableau storage is what unlocks larger
batches and larger LPs on a fixed-memory device.  This module makes that
storage a first-class layer: a :class:`TableauSpec` names the column
layout ONCE, and every producer/consumer of tableaus — ``build_tableau``
here, the iteration engine (``core/engine.py``), both accelerated
drivers (``core/simplex.py``, ``kernels/simplex_pallas.py``), the Pallas
padding/BlockSpec logic (``kernels/ops.py``), and the sweep session
(``core/session.py``) — derives its column arithmetic from the spec
instead of hard-coding the dense map.

Two layouts exist:

``"dense"``
    The paper's explicit map: ``q = 1 + n + 2m`` columns — RHS,
    originals, slacks, and a dense artificial identity block.

``"compact"`` (the default)
    Drops the artificial block: ``q = 1 + n + m``.  The artificial
    columns are write-only lanes — ``eligible_mask`` bars them from ever
    entering the basis, so every pivot updates them but nothing ever
    reads them back: phase-I pricing happens in the objective row, the
    feasibility decision reads ``-z0`` (objective row, column 0), and
    the degenerate-artificial escape works off the basis vector and the
    RHS column.  Dropping them changes NO arithmetic on the remaining
    columns, so compact solves are bit-identical to dense solves — while
    spending ~33% less tableau memory, pivot-update flops, and VMEM
    footprint on square (m = n) LPs.

Basis encoding is IDENTICAL in both layouts: entries ``1..n`` are
originals, ``n+1..n+m`` slacks, and ``1+n+m+i`` denotes row ``i``'s
artificial.  In the compact layout the artificial entry is a pure ID —
no column of that index exists — which is all the engine ever needed
(``basis >= spec.art_start`` tests, never column reads).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: Valid tableau layouts (see module docstring).
LAYOUTS = ("dense", "compact")

#: The library-wide default layout.
DEFAULT_LAYOUT = "compact"


@dataclasses.dataclass(frozen=True)
class TableauSpec:
    """Static column-layout descriptor for one (m, n) tableau shape class.

    Frozen and hashable, so it can ride through ``jax.jit`` static
    arguments and into a Pallas kernel via ``functools.partial``.

    Parameters
    ----------
    m, n : int
        Constraint and variable counts of the canonical LP batch.
    layout : str
        ``"dense"`` | ``"compact"`` (see module docstring).
    """

    m: int
    n: int
    layout: str = DEFAULT_LAYOUT

    def __post_init__(self):
        if self.layout not in LAYOUTS:
            raise ValueError(
                f"unknown tableau layout {self.layout!r}; expected one of {LAYOUTS}"
            )

    # -- column map -------------------------------------------------------

    @property
    def q(self) -> int:
        """Total tableau columns under this layout."""
        base = 1 + self.n + self.m
        return base + self.m if self.layout == "dense" else base

    @property
    def rhs_col(self) -> int:
        """The RHS/bound column (objective row stores ``-z0`` there)."""
        return 0

    @property
    def var_start(self) -> int:
        """First original-variable column (columns ``1..n``)."""
        return 1

    @property
    def slack_start(self) -> int:
        """First slack column (columns ``n+1..n+m``)."""
        return 1 + self.n

    @property
    def art_start(self) -> int:
        """Basis-ID base of the artificial variables (``1+n+m``).

        In the dense layout this is also the first artificial COLUMN; in
        the compact layout no such column exists and the value is purely
        a basis-vector ID (``basis >= art_start`` <=> artificial basic).
        """
        return 1 + self.n + self.m

    @property
    def num_eligible(self) -> int:
        """Columns ever allowed to enter the basis (originals + slacks)."""
        return self.n + self.m

    # -- accounting -------------------------------------------------------

    def bytes_per_lp(self, dtype=jnp.float32) -> int:
        """Unpadded tableau bytes one LP occupies under this layout."""
        return (self.m + 1) * self.q * jnp.dtype(dtype).itemsize

    def with_layout(self, layout: str) -> "TableauSpec":
        """The same shape class under another layout."""
        return TableauSpec(self.m, self.n, layout)

    @classmethod
    def from_tableau(cls, m: int, n: int, q: int) -> "TableauSpec":
        """Recover the layout of an existing ``(B, m+1, q)`` tableau.

        The two layouts never collide for ``m >= 1`` (their ``q`` differ
        by exactly ``m``), so a carried :class:`~repro.core.lp.ResumeState`
        is self-describing — resumed rounds re-derive the layout from the
        state instead of trusting the caller's options to match.
        """
        for layout in LAYOUTS:
            spec = cls(m, n, layout)
            if spec.q == q:
                return spec
        raise ValueError(
            f"tableau with q={q} matches no layout for m={m}, n={n} "
            f"(dense q={1 + n + 2 * m}, compact q={1 + n + m})"
        )


def build_tableau(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    basis0: Optional[jnp.ndarray] = None,
    spec: Optional[TableauSpec] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Construct the batched two-phase simplex tableau (device-side, jit-able).

    Parameters
    ----------
    a, b, c : jnp.ndarray
        Canonical batch data, shapes ``(B, m, n)``, ``(B, m)``, ``(B, n)``.
    basis0 : jnp.ndarray, optional
        ``(B, m)`` int32 warm-start basis (tableau column indices,
        1..n originals / n+1..n+m slacks).  Where the basis is valid,
        nonsingular, and primal feasible the tableau is rebuilt for it
        (``B^-1 [b | A | I]``) and the LP starts directly in phase II;
        invalid rows fall back to the cold slack/artificial start.
    spec : TableauSpec, optional
        Target layout; defaults to ``TableauSpec(m, n)`` (the compact
        default).  Only the column count differs between layouts — all
        values on the shared columns are identical, which is the root of
        the layouts' bit-identical solve guarantee.

    Returns
    -------
    tab : jnp.ndarray
        (B, m+1, spec.q) tableau.  Objective row is the phase-I
        reduced-cost row for LPs with any b_i < 0, else the phase-II row
        (coefficients of c).
    basis : jnp.ndarray
        (B, m) int32 — basis ID of the basic variable per row (layout-
        independent encoding; artificials are IDs ``>= spec.art_start``).
    phase : jnp.ndarray
        (B,) int32 — 1 where phase I is required, else 2.
    """
    bsz, m, n = a.shape
    if spec is None:
        spec = TableauSpec(m, n)
    q = spec.q
    dtype = a.dtype

    neg = b < 0  # (B, m) rows needing an artificial
    sgn = jnp.where(neg, -1.0, 1.0).astype(dtype)  # (B, m)

    tab = jnp.zeros((bsz, m + 1, q), dtype)
    # b column (made non-negative by row negation).
    tab = tab.at[:, :m, 0].set(b * sgn)
    # Original variable coefficients (negated rows flip sign).
    tab = tab.at[:, :m, 1 : 1 + n].set(a * sgn[:, :, None])
    # Slack columns: +1 normally, -1 on negated rows.
    row_idx = jnp.arange(m)
    tab = tab.at[:, row_idx, 1 + n + row_idx].set(sgn)
    if spec.layout == "dense":
        # Artificial columns: +1 only on negated rows.  The compact
        # layout stores nothing — the columns are write-only lanes.
        tab = tab.at[:, row_idx, spec.art_start + row_idx].set(
            jnp.where(neg, 1.0, 0.0).astype(dtype)
        )

    need_phase1 = jnp.any(neg, axis=1)  # (B,)

    # Phase-II objective row: reduced costs = c (slack basis has cost 0).
    obj2 = jnp.zeros((bsz, q), dtype).at[:, 1 : 1 + n].set(c)
    # Phase-I objective row (maximize -sum of artificials): price out the
    # basic artificials => obj1_j = sum over artificial rows of tab[i, j];
    # column 0 then holds sum of RHS = -z0 >= 0, exactly the -z0 convention.
    obj1 = jnp.sum(tab[:, :m, :] * neg[:, :, None].astype(dtype), axis=1)
    # Artificial columns must never be entering; their own reduced cost
    # after pricing is 0 at start, eligibility mask handles the rest.
    obj = jnp.where(need_phase1[:, None], obj1, obj2)
    tab = tab.at[:, m, :].set(obj)

    # Initial basis: slack on normal rows, artificial on negated rows.
    basis = jnp.where(
        neg, spec.art_start + row_idx[None, :], 1 + n + row_idx[None, :]
    )
    basis = basis.astype(jnp.int32)
    phase = jnp.where(need_phase1, 1, 2).astype(jnp.int32)
    if basis0 is None:
        return tab, basis, phase
    warm_tab, warm_basis, ok = _warm_tableau(a, b, c, basis0, spec)
    tab = jnp.where(ok[:, None, None], warm_tab, tab)
    basis = jnp.where(ok[:, None], warm_basis, basis)
    phase = jnp.where(ok, 2, phase)
    return tab, basis, phase


def _warm_tableau(
    a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, basis0, spec: TableauSpec
):
    """Tableau for a caller-supplied basis: rows = B^-1 [b | A | I].

    Returns ``(tab, basis, ok)`` where ``ok`` is a (B,) bool mask of LPs
    whose warm basis is usable — indices in the var/slack range, basis
    matrix nonsingular (a singular or duplicated basis surfaces as
    non-finite solve output), and ``B^-1 b`` primal feasible.  Rows with
    ``ok`` False must use the cold start; the returned tableau is
    unspecified there.  A warm tableau carries nothing beyond column
    ``n + m``: a feasible warm basis starts in phase II, where
    artificials are both non-basic and ineligible to enter — the dense
    layout's artificial block stays all-zero and the compact layout
    simply has no lanes there.
    """
    bsz, m, n = a.shape
    q = spec.q
    dtype = a.dtype
    basis0 = jnp.asarray(basis0, jnp.int32)

    in_range = (basis0 >= 1) & (basis0 <= n + m)  # (B, m)
    safe = jnp.where(in_range, basis0, 1)

    eye = jnp.broadcast_to(jnp.eye(m, dtype=dtype), (bsz, m, m))
    ai = jnp.concatenate([a, eye], axis=2)  # (B, m, n+m) var+slack columns
    bmat = jnp.take_along_axis(ai, (safe - 1)[:, None, :], axis=2)  # (B, m, m)
    rhs_full = jnp.concatenate([b[:, :, None], ai], axis=2)  # (B, m, 1+n+m)
    body = jnp.linalg.solve(bmat, rhs_full)  # B^-1 [b | A | I]

    feas_tol = (1e-9 if dtype == jnp.float64 else 1e-6) * jnp.maximum(
        1.0, jnp.max(jnp.abs(b), axis=-1)
    )
    finite = jnp.all(jnp.isfinite(body), axis=(1, 2))
    feasible = jnp.all(body[:, :, 0] >= -feas_tol[:, None], axis=1)
    ok = jnp.all(in_range, axis=1) & finite & feasible
    # Guard the downstream arithmetic: non-finite entries from a singular
    # basis would poison jnp.where on some backends.
    body = jnp.where(jnp.isfinite(body), body, 0.0)
    # Restore the rhs >= 0 invariant the ratio test relies on (the accepted
    # bases are feasible only up to feas_tol).
    body = body.at[:, :, 0].set(jnp.maximum(body[:, :, 0], 0.0))

    c_full = jnp.zeros((bsz, 1 + n + m), dtype).at[:, 1 : 1 + n].set(c)
    cb = jnp.take_along_axis(c_full, safe, axis=1)  # (B, m) basic costs
    obj = c_full - jnp.einsum(  # col 0 holds -z0
        "bm,bmk->bk", cb, body, precision=jax.lax.Precision.HIGHEST
    )

    tab = jnp.zeros((bsz, m + 1, q), dtype)
    tab = tab.at[:, :m, : 1 + n + m].set(body)
    tab = tab.at[:, m, : 1 + n + m].set(obj)
    return tab, safe, ok

"""Layout-agnostic batched simplex iteration engine.

One implementation of the paper's pivot machinery (Sec. 3.1, 4.2-4.3),
shared by every accelerated backend.  ``core/simplex.py`` (XLA lockstep)
and ``kernels/simplex_pallas.py`` (VMEM-resident Mosaic kernel) are thin
drivers over the building blocks here; only the NumPy oracle
(``core/oracle.py``) stays independent, as the trusted cross-check.

Every function is pure ``jax.numpy`` over batched tableaus and is
formulated with ``broadcasted_iota`` + masked reductions — no scatters
or 1-D iota — so the SAME code lowers cleanly both through XLA and
through Mosaic inside a Pallas kernel body.  The pivot column is taken
in one-hot form by both drivers (:func:`take_col`); the other
single-element extractions (pivot row, basic costs) go through helpers
taking a static ``gather`` flag: ``gather=True`` uses
``take_along_axis`` (cheap under XLA — the XLA driver's choice),
``gather=False`` a one-hot multiply-reduction (the only form Mosaic
lowers — the Pallas kernel's choice).  Both forms extract the SAME
value exactly (a one-hot sum has a single non-zero term), so the XLA
and Pallas drivers agree bit-for-bit on pivot trajectories either way.

Tableau conventions (see ``core/tableau.py``): shape ``(B, M1, Q)`` with
``M1 >= m + 1`` and ``Q >= spec.q``; row ``m`` is the objective row,
column 0 the RHS/bound column.  The column map is owned by a static
:class:`~repro.core.tableau.TableauSpec` — every layout-sensitive block
below (pricing, the ratio test, the phase transition, the pivot update,
solution extraction) takes the spec instead of assuming the dense map,
so the same code runs the ``"dense"`` layout (explicit artificial block)
and the default ``"compact"`` layout (artificials are basis IDs only,
``q = 1 + n + m``) with bit-identical pivot trajectories.  Padding rows
and columns (Pallas lane/sublane alignment) must be zero — every block
below preserves that invariant, because a zero pivot-column entry leaves
its row unchanged and padded columns are never eligible to enter.

Pivot rules
-----------
``"lpc"``  largest positive coefficient (Dantzig; the paper's default).
``"rpc"``  random positive coefficient (the paper's Sec. 5 ablation) —
           a uniform choice among the eligible positive columns, driven
           by the stateless counter hash :func:`rpc_noise` so the rule
           runs identically under XLA and Mosaic.
``"bland"`` Bland's smallest-index anti-cycling rule (beyond paper).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .lp import INFEASIBLE, OPTIMAL, RUNNING
from .tableau import TableauSpec

LPC = "lpc"
RPC = "rpc"
BLAND = "bland"

#: Valid pivot rules, in paper order (lpc is the default everywhere).
RULES = (LPC, RPC, BLAND)

#: The paper's INT_MAX trick: masked-out ratios take this value so the
#: min-reduction stays branch-free; ``min_ratio >= BIG / 2`` <=> unbounded.
BIG = 1e30


def default_tolerance(dtype) -> float:
    """The library-wide reduced-cost/pivot tolerance for a tableau dtype."""
    return 1e-9 if dtype == jnp.float64 else 1e-5


def phase1_feasibility_tol(b: jnp.ndarray) -> jnp.ndarray:
    """Per-LP threshold under which the phase-I optimum counts as feasible.

    ``b``: (B, m) raw bounds.  Returns (B,) — ``1e-5 * max(1, max|b|)``,
    the scale-aware test both accelerated drivers apply to the phase-I
    objective value (``-z0``) when deciding feasible vs infeasible.
    """
    return 1e-5 * jnp.maximum(1.0, jnp.max(jnp.abs(b), axis=-1))


def column_ids(q: int) -> jnp.ndarray:
    """(1, 1, q) int32 column indices (a lane iota — the Mosaic-safe form)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, 1, q), 2)


def row_ids(r: int) -> jnp.ndarray:
    """(1, r, 1) int32 row indices (a sublane iota)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, r, 1), 1)


def eligible_mask(q_total: int, m: int, n: int) -> jnp.ndarray:
    """(1, 1, q_total) bool — columns allowed to enter the basis.

    Column 0 (the RHS), the artificial block (dense layout), and any lane
    padding beyond the true ``q`` are never eligible; only originals and
    slacks are — which is the same mask under BOTH layouts, since the
    eligible range ``1..n+m`` precedes everything layout-dependent.
    """
    ids = column_ids(q_total)
    return (ids >= 1) & (ids < 1 + n + m)


# ---------------------------------------------------------------------------
# RPC noise: stateless counter-based hash (SplitMix-style finalizer)
# ---------------------------------------------------------------------------


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """32-bit avalanche finalizer (lowbias32): uint32 -> well-mixed uint32."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def rpc_noise(
    seed, step, row_offset, bsz: int, q: int, dtype, col_offset: int = 0
) -> jnp.ndarray:
    """(bsz, 1, q) uniform noise in ``dtype`` for the RPC rule, counter-based.

    Keyed on (seed, iteration step, global LP row, column) so the draw is
    stateless — no PRNG key threading — and identical regardless of how
    the batch is tiled (``row_offset`` is the driver's global row base,
    e.g. ``program_id * tile_b`` in the Pallas kernel); ``col_offset``
    shifts the column key, so a segment of a row draws exactly the slice
    of the whole row's noise (:func:`select_entering_segments`).  Pure uint32
    shift/xor/multiply arithmetic, which lowers under both XLA and
    Mosaic; the float conversion happens in the objective-row ``dtype``.
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (bsz, 1, q), 0).astype(jnp.uint32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bsz, 1, q), 2) + col_offset
    cols = cols.astype(jnp.uint32)
    rows = rows + jnp.asarray(row_offset).astype(jnp.uint32)
    key = jnp.asarray(seed).astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    ctr = jnp.asarray(step).astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
    x = _mix32(rows * jnp.uint32(0xC2B2AE35) ^ cols ^ key ^ ctr)
    # Top 24 bits -> uniform in [0, 1); exact in float32 and float64.  The
    # shifted value fits int32, whose float conversion Mosaic lowers.
    top = (x >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(dtype) * jnp.asarray(1.0 / (1 << 24), dtype)


# ---------------------------------------------------------------------------
# layout conversions and single-element extraction
# ---------------------------------------------------------------------------
#
# Every per-LP quantity keeps the rank of the tableau it came from, so
# that Mosaic never has to move an axis between lanes and sublanes:
#
#   per-LP scalar    (B, 1, 1)   e.g. the entering column, status, phase
#   row vector       (B, 1, Q)   indexed by tableau column (objective row)
#   column vector    (B, R, 1)   indexed by tableau row (basis, pivot column)
#
# The ``gather`` flag (static) picks the formulation the target compiler
# handles well: ``True`` uses ``take_along_axis`` and reshapes (cheap
# under XLA — the XLA driver's choice), ``False`` one-hot selects and
# reductions (the only forms Mosaic lowers — the Pallas kernels'
# choice).  Both extract the SAME value exactly (a one-hot sum has a
# single non-zero term), so the drivers agree bit-for-bit either way.


def widen_rows(flag: jnp.ndarray, r: int) -> jnp.ndarray:
    """Per-LP flag (B, 1, 1) -> (B, r, 1), for masks that later meet lanes.

    Mosaic broadcasts a (B, 1, 1) value along sublanes, or a (B, r, 1)
    column along lanes, but not a (B, 1, 1) value along both at once —
    so a per-LP mask over a (B, r, Q) block is widened in two steps.
    """
    return flag & (row_ids(r) >= 0)


def first_index(mask: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Index of the first True along ``axis`` (keepdims), 0 if none.

    The ``argmax``-of-bool convention, written as a min over an iota so
    it lowers identically under XLA and Mosaic.
    """
    axis = axis % mask.ndim
    size = mask.shape[axis]
    shape = [1] * mask.ndim
    shape[axis] = size
    ids = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis)
    idx = jnp.min(jnp.where(mask, ids, size), axis=axis, keepdims=True)
    return jnp.where(idx == size, 0, idx)


def take_col(mat: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    """Column ``j`` per batch element: (B, R, Q), (B, 1, 1) -> (B, R, 1).

    One-hot under both drivers: XLA on a TPU v5e miscompiles the gather
    form (``take_along_axis`` along the lanes) at some batch sizes — every
    row of a 2,500-LP m = n = 100 batch came back wrong, while 2,048,
    4,096, 5,000 and 10,000 LPs were right.
    """
    oh = column_ids(mat.shape[2]) == j
    return jnp.sum(jnp.where(oh, mat, 0.0), axis=2, keepdims=True)


def take_row(mat: jnp.ndarray, i: jnp.ndarray, gather: bool) -> jnp.ndarray:
    """Row ``i`` per batch element: (B, R, Q), (B, 1, 1) -> (B, 1, Q)."""
    if gather:
        return jnp.take_along_axis(mat, i, axis=1)
    oh = row_ids(mat.shape[1]) == i
    return jnp.sum(jnp.where(oh, mat, 0.0), axis=1, keepdims=True)


def to_column(vec: jnp.ndarray, gather: bool) -> jnp.ndarray:
    """Row vector to column vector: (B, 1, K) -> (B, K, 1)."""
    if gather:
        return jnp.swapaxes(vec, 1, 2)
    k = vec.shape[2]
    eye = row_ids(k) == column_ids(k)
    return jnp.sum(jnp.where(eye, vec, 0), axis=2, keepdims=True)


def to_row(vec: jnp.ndarray, k_out: int, gather: bool) -> jnp.ndarray:
    """Column vector to a zero-padded row: (B, K, 1) -> (B, 1, k_out)."""
    k = vec.shape[1]
    if gather:
        row = jnp.swapaxes(vec, 1, 2)
        return jnp.pad(row, ((0, 0), (0, 0), (0, k_out - k)))
    eye = row_ids(k) == column_ids(k_out)
    return jnp.sum(jnp.where(eye, vec, 0), axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# iteration building blocks
# ---------------------------------------------------------------------------


def select_entering(
    obj: jnp.ndarray,
    elig: jnp.ndarray,
    rule: str,
    tol: float,
    noise: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pick the entering column per LP under the given pivot rule.

    Parameters
    ----------
    obj : (B, 1, Q) objective row (reduced costs).
    elig : (1, 1, Q) or (B, 1, Q) bool eligibility mask
        (:func:`eligible_mask`).
    rule : ``"lpc"`` | ``"rpc"`` | ``"bland"`` (static).
    tol : reduced-cost tolerance (static).
    noise : (B, 1, Q) uniform noise, required for ``"rpc"`` only
        (:func:`rpc_noise`).

    Returns
    -------
    e : (B, 1, 1) int32 entering column index.
    max_c : (B, 1, 1) the LARGEST eligible reduced cost (not necessarily
        at ``e`` for rpc/bland) — the optimality certificate:
        ``max_c <= tol`` means no improving column exists under ANY rule.
    """
    cand = jnp.where(elig, obj, -BIG)
    max_c = jnp.max(cand, axis=-1, keepdims=True)
    if rule == LPC:
        e = first_index(cand == max_c, axis=-1)
    elif rule == BLAND:
        e = first_index(elig & (obj > tol), axis=-1)
    elif rule == RPC:
        if noise is None:
            raise ValueError("rpc rule needs a noise array (engine.rpc_noise)")
        pos = elig & (obj > tol)
        score = jnp.where(pos, noise, -BIG)
        e = first_index(score == jnp.max(score, axis=-1, keepdims=True), axis=-1)
    else:
        raise ValueError(f"unknown pivot rule {rule!r}; expected one of {RULES}")
    return e, max_c


def select_entering_segments(
    rows: Sequence[jnp.ndarray],
    starts: Sequence[int],
    rule: str,
    tol: float,
    noises: Optional[Sequence[jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`select_entering` over a row held as separate segments.

    ``rows[k]`` (B, 1, K_k) holds columns ``starts[k] ..`` of the
    objective row, every one of them eligible; columns in no segment are
    not.  Returns the same ``(e, max_c)`` as :func:`select_entering` on
    the assembled row whenever an improving column exists (ties go to
    the lowest column, as there) — without assembling it, since Mosaic
    lays a lane concatenation out poorly.  ``noises`` are the matching
    segments of the RPC noise (``rpc_noise(..., col_offset=start)``).
    """
    if rule not in RULES:
        raise ValueError(f"unknown pivot rule {rule!r}; expected one of {RULES}")
    if rule == RPC and noises is None:
        raise ValueError("rpc rule needs noise arrays (engine.rpc_noise)")
    max_c = best = e = None
    for k, (row, start) in enumerate(zip(rows, starts)):
        if rule == LPC:
            score = row
        elif rule == BLAND:
            score = jnp.where(row > tol, 1.0, 0.0).astype(row.dtype)
        else:
            score = jnp.where(row > tol, noises[k], -BIG)
        top = jnp.max(score, axis=-1, keepdims=True)
        idx = start + first_index(score == top, axis=-1)
        seg_max = jnp.max(row, axis=-1, keepdims=True)
        if e is None:
            max_c, best, e = seg_max, top, idx
        else:
            take = top > best  # strict: earlier segments win ties
            max_c = jnp.maximum(max_c, seg_max)
            best = jnp.where(take, top, best)
            e = jnp.where(take, idx, e)
    return e, max_c


def phase2_objective(
    tab: jnp.ndarray,
    basis: jnp.ndarray,
    spec: TableauSpec,
    c_ext: jnp.ndarray,
    gather: bool = False,
) -> jnp.ndarray:
    """The phase-II objective row for the current basis: ``c_ext - c_B . rows``.

    ``basis``: (B, m, 1) column vector; ``c_ext``: (B, 1, Q) phase-II
    costs (zeros except columns 1..n).  Returns (B, 1, Q); column 0 holds
    ``-c_B . b = -z0`` (the ``-z0`` convention).  The pricing
    contraction is a batched ``dot_general`` at ``HIGHEST`` precision:
    on a TPU both XLA and Mosaic would otherwise contract float32 in a
    single bfloat16 pass, far too coarse for a pivoting tolerance.

    Layout note: a still-basic (degenerate) artificial appears as a basis
    ID ``>= spec.art_start``.  Its phase-II cost is 0 under either layout
    — in ``dense`` the gathered ``c_ext`` column is 0, in ``compact`` the
    ID lies beyond ``c_ext`` so the gather clamps onto a zero-cost lane
    (slack or padding) and the one-hot form matches nothing — so both
    layouts and both ``gather`` modes price it to the same 0.
    """
    m = spec.m
    if gather:
        qe = c_ext.shape[-1]
        cb = jnp.take_along_axis(c_ext, jnp.minimum(basis, qe - 1), axis=2)
    else:
        hit = basis == column_ids(c_ext.shape[-1])  # (B, m, Q)
        cb = jnp.sum(jnp.where(hit, c_ext, 0.0), axis=2, keepdims=True)
    priced = jax.lax.dot_general(
        cb,
        tab[:, :m, :],
        (((1,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=tab.dtype,
    )  # (B, 1, Q)
    return c_ext - priced


def phase_status(
    tab: jnp.ndarray,
    phase: jnp.ndarray,
    status: jnp.ndarray,
    at_opt: jnp.ndarray,
    feas_tol: jnp.ndarray,
    spec: TableauSpec,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The optimum bookkeeping of :func:`phase_transition`, without the rewrite.

    LPs at a phase-I optimum end INFEASIBLE unless ``-z0 <= feas_tol``;
    the feasible ones (``to_phase2``) move to phase II.  LPs at a
    phase-II optimum end OPTIMAL.  The feasibility test reads ``-z0``
    from the objective row — never the artificial columns, which is why
    the compact layout can drop them.  Every value is (B, 1, 1).
    Returns the updated ``(phase, status, to_phase2)``.
    """
    m = spec.m
    active = status == RUNNING
    p1_done = active & at_opt & (phase == 1)
    feasible = tab[:, m : m + 1, 0:1] <= feas_tol
    to_phase2 = p1_done & feasible
    status = jnp.where(p1_done & ~feasible, INFEASIBLE, status)
    status = jnp.where(active & at_opt & (phase == 2), OPTIMAL, status)
    phase = jnp.where(to_phase2, 2, phase)
    return phase, status, to_phase2


def phase2_row(
    tab: jnp.ndarray,
    basis: jnp.ndarray,
    to_phase2: jnp.ndarray,
    c_ext: jnp.ndarray,
    spec: TableauSpec,
    gather: bool = False,
) -> jnp.ndarray:
    """The objective row after the phase transition, (B, 1, Q).

    :func:`phase2_objective` for the ``to_phase2`` LPs ((B, 1, 1)), the
    current objective row for every other LP.
    """
    m = spec.m
    new_obj = phase2_objective(tab, basis, spec, c_ext, gather)
    return jnp.where(to_phase2, new_obj, tab[:, m : m + 1, :])


def phase_transition(
    tab: jnp.ndarray,
    basis: jnp.ndarray,
    phase: jnp.ndarray,
    status: jnp.ndarray,
    at_opt: jnp.ndarray,
    c_ext: jnp.ndarray,
    feas_tol: jnp.ndarray,
    spec: TableauSpec,
    gather: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Branch-free optimum bookkeeping: finish phase II, enter phase II.

    For LPs at a phase-I optimum: feasible ones get their objective row
    rewritten in place via :func:`phase2_objective` and continue into
    phase II (the paper does this with a host round-trip between two
    kernel launches; here it is a masked in-loop rewrite); infeasible
    ones terminate INFEASIBLE.  LPs at a phase-II optimum terminate
    OPTIMAL.  :func:`phase_status`, then :func:`phase2_row` written into
    row ``m``, every iteration; the Pallas kernel calls the two itself,
    to skip the rewrite where no LP of its tile needs it.

    ``phase``, ``status``, ``at_opt`` and ``feas_tol`` are (B, 1, 1).
    Returns the updated ``(tab, phase, status)``.
    """
    phase, status, to_phase2 = phase_status(tab, phase, status, at_opt, feas_tol, spec)
    row = phase2_row(tab, basis, to_phase2, c_ext, spec, gather)
    tab = jnp.where(row_ids(tab.shape[1]) == spec.m, row, tab)
    return tab, phase, status


def ratio_test(
    tab: jnp.ndarray,
    basis: jnp.ndarray,
    e: jnp.ndarray,
    spec: TableauSpec,
    tol: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Min-ratio leaving-row selection, branch-free (the INT_MAX trick).

    Ratios with a non-positive pivot-column entry are replaced by
    :data:`BIG` before the min-reduction; ``min_ratio >= BIG / 2`` then
    certifies unboundedness.

    Degenerate-artificial escape: after phase I a basic artificial can
    sit at value 0 on a degenerate row.  A pivot whose column entry is
    NEGATIVE there would make the artificial GROW — silently leaving the
    feasible region.  Such rows are forced out at ratio 0 (``zero_art``):
    a valid degenerate pivot on the negative element, since the RHS is 0.
    The artificial is recognized by its basis ID (``>= spec.art_start``)
    and handled via the RHS column alone — no artificial COLUMN is read,
    so the escape works identically under the compact layout.

    Returns
    -------
    l : (B, 1, 1) int32 leaving row.
    min_ratio : (B, 1, 1) the winning ratio (``>= BIG/2`` <=> unbounded).
    full_col : (B, R, 1) the full entering column incl. the objective row
        — reused by :func:`pivot_update`.
    """
    m = spec.m
    full_col = take_col(tab, e)  # (B, R, 1)
    col = full_col[:, :m, :]
    rhs = tab[:, :m, 0:1]
    ratios = jnp.where(col > tol, rhs / jnp.where(col > tol, col, 1.0), BIG)
    zero_art = (basis >= spec.art_start) & (rhs <= tol) & (col < -tol)
    ratios = jnp.where(zero_art, 0.0, ratios)
    min_ratio = jnp.min(ratios, axis=1, keepdims=True)
    l = first_index(ratios == min_ratio, axis=1)
    return l, min_ratio, full_col


def pivot_update(
    tab: jnp.ndarray,
    basis: jnp.ndarray,
    e: jnp.ndarray,
    l: jnp.ndarray,
    full_col: jnp.ndarray,
    do_pivot: jnp.ndarray,
    spec: TableauSpec,
    tol: float,
    gather: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked rank-1 Gauss-Jordan step around pivot ``(l, e)``.

    ``tab[l] /= tab[l, e]``; every other row subtracts its pivot-column
    multiple of the normalized row.  LPs with ``do_pivot`` False keep
    their tableau and basis unchanged (lockstep masking).  Zero padding
    rows/columns are preserved: their pivot-column entry is 0.
    ``full_col`` comes from :func:`ratio_test`; the pivot element is read
    out of it (``full_col[l] == tab[l, e]`` exactly) rather than
    re-extracted from the tableau.  The update sweeps whatever columns
    the layout stores — this is where the compact layout saves its ~33%
    of rank-1 flops on square LPs.
    """
    l_rows = row_ids(tab.shape[1]) == l  # (B, R, 1); l < m always
    pr = take_row(tab, l, gather)  # (B, 1, Q)
    pe = take_row(full_col, l, gather)  # (B, 1, 1)
    npr = pr / jnp.where(jnp.abs(pe) > tol, pe, 1.0)
    updated = tab - full_col * npr
    updated = jnp.where(l_rows, npr, updated)
    tab = jnp.where(widen_rows(do_pivot, tab.shape[1]), updated, tab)
    basis = jnp.where(do_pivot & (row_ids(spec.m) == l), e, basis)
    return tab, basis


def extract_solution(
    tab: jnp.ndarray,
    basis: jnp.ndarray,
    status: jnp.ndarray,
    spec: TableauSpec,
    n_out: int,
    fill: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Objective value and primal point from a terminal tableau.

    ``objective = -tab[:, m, 0]`` where OPTIMAL, else ``fill`` (the XLA
    driver uses ``-inf``; the Pallas kernel uses a finite sentinel and
    re-masks outside), as (B, 1, 1).  ``x``: (B, 1, n_out) one-hot
    scatter of the RHS into the original-variable slots (basis column
    ``j+1`` <-> ``x_j``); non-optimal LPs report 0.  Reads only the RHS
    column and the basis — layout-independent by construction.
    """
    m = spec.m
    opt = status == OPTIMAL
    objective = jnp.where(opt, -tab[:, m : m + 1, 0:1], fill)
    rhs = tab[:, :m, 0:1]  # (B, m, 1)
    hit = (basis == column_ids(n_out) + 1) & widen_rows(opt, m)  # (B, m, n_out)
    x = jnp.sum(jnp.where(hit, rhs, 0.0), axis=1, keepdims=True)
    return objective, x

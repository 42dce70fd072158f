"""Pallas TPU kernel: whole-solve-in-VMEM batched simplex.

TPU adaptation of the paper's memory-coalescing design (Sec. 4.3).  On the
GPU the tableau streams from global memory every iteration and the win is
*coalescing* those accesses.  On TPU the same algorithm is memory-bound at
~0.5 FLOP/byte if the tableau lives in HBM, so the kernel goes one step
further: a tile of TB complete tableaus is mapped into VMEM via BlockSpec
and the ENTIRE two-phase simplex loop runs inside the kernel — per-
iteration HBM traffic is zero, and the effective roofline moves from HBM
bandwidth (819 GB/s) to VMEM bandwidth (~an order of magnitude higher).

Layout: (TB, m+1, q_padded) per block with q padded to the 128-lane
boundary — the batch dim is the paper's "column-major" axis reborn: every
element-wise tableau op is contiguous across lanes.  ``q`` itself comes
from the static :class:`~repro.core.tableau.TableauSpec`: under the
default ``"compact"`` layout the artificial block is implicit (basis IDs
only), which shrinks the VMEM block per LP by ~m lanes-rows and is what
lets the auto-tiler (``kernels/ops.py``) fit more LPs per tile.

The iteration math itself — entering-column selection (all three pivot
rules), the min-ratio test with the degenerate-artificial escape, the
in-loop phase transition, and the rank-1 pivot — is NOT implemented here:
the kernel body drives ``core/engine.py``, the same building blocks the
XLA lockstep path uses.  The engine is written in broadcasted-iota +
one-hot form, which lowers to VPU-friendly selects under Mosaic, so the
kernel and the XLA path agree bit-for-bit under deterministic rules.

Gated phase transition: the kernel calls the engine's two halves of
``phase_transition`` itself.  The status bookkeeping
(``engine.phase_status``) runs every iteration on per-LP scalars.  The
new objective row (``engine.phase2_row``: the basic-cost gather and a
HIGHEST-precision pricing contraction) is computed under ``lax.cond``
only in iterations where some LP of the tile leaves phase I; otherwise
the cond hands back the current row.  The row goes back into the
tableau through the 8-row sublane tile that holds it (``_put_row``), so
no branch passes over the whole tableau (on a TPU v5e a cond that
returns the whole tableau saved about half as much time per pivot at
m = n = 100, and almost none at m = n = 200).  The skip is
exact: with the gate closed no LP of the tile has ``to_phase2`` set, so
``phase2_row`` would have returned the current row, and the kernel
yields the same tableau, basis, status and iteration counts as the
ungated XLA driver.  The gate stays closed through a single-phase solve
and opens at most once per phase-I LP; the kernel counts its openings
per tile (``LPSolution.phase_rewrites``).

Compile-once dispatch: the iteration cap enters the kernel as a SCALAR
INPUT (``cap_ref``, like ``feas_ref``), not a trace-time constant — the
compaction scheduler's geometric round caps all run the one compiled
kernel per tableau shape.  ``static_cap`` restores the old cap-specialized
lowering as a benchmark baseline, and ``want_state`` adds tableau/phase
outputs so an interrupted round can be resumed exactly
(``core/lp.py:ResumeState``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import engine
from ..core.lp import ITER_LIMIT, RUNNING, UNBOUNDED
from ..core.tableau import TableauSpec

_BIG = engine.BIG


def _put_row(tab, i: int, row):
    """``tab`` with row ``i`` (static) replaced by ``row``: (B, R, Q), (B, 1, Q).

    Selects inside the 8-row sublane tile that holds row ``i`` alone and
    splices it back at tile-aligned offsets, so the rest of the tableau
    is not passed over.  ``R`` is a multiple of 8 (``kernels/ops.py``
    pads the rows).
    """
    r0 = i - i % 8
    tile = jnp.where(engine.row_ids(8) == i - r0, row, tab[:, r0 : r0 + 8, :])
    parts = [p for p in (tab[:, :r0, :], tile, tab[:, r0 + 8 :, :]) if p.shape[1]]
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else tile


def _kernel(
    cap_ref,  # (2,) i32 SMEM — iteration cap (compile-once caps), first global row
    tab_ref,  # (TB, M1p, Qp) f32 VMEM — prebuilt tableau (padded)
    basis_ref,  # (TB, Mp) i32 VMEM
    phase_ref,  # (TB, 1) i32 VMEM
    cext_ref,  # (TB, Qp) f32 VMEM — phase-II costs
    feas_ref,  # (TB, 1) f32 VMEM — per-LP phase-I feasibility threshold
    obj_ref,  # out (TB, 1) f32
    x_ref,  # out (TB, Np) f32
    status_ref,  # out (TB, 1) i32
    iters_ref,  # out (TB, 1) i32
    basis_out_ref,  # out (TB, Mp) i32 — final basis (warm-start reuse)
    rewrites_ref,  # out (TB, 1) i32 — iterations in which the tile's gate opened
    *state_out_refs,  # want_state: out (TB, M1p, Qp) f32 tab, (TB, 1) i32 phase
    spec: TableauSpec,
    rule: str,
    seed: int,
    tol: float,
    static_cap: Optional[int],
    want_state: bool,
):
    m, n = spec.m, spec.n
    tb, _, qp = tab_ref.shape
    mp = basis_ref.shape[1]
    np_pad = x_ref.shape[1]

    # Per-LP scalars enter as (TB, 1) and rows as (TB, K) blocks; the
    # engine wants (TB, 1, 1) scalars, (TB, 1, K) rows and (TB, m, 1)
    # columns (see core/engine.py), so reshape / re-orient once here.
    tab = tab_ref[...]
    basis = engine.to_column(basis_ref[...].reshape(tb, 1, mp), gather=False)
    basis = basis[:, :m, :]
    phase = phase_ref[...].reshape(tb, 1, 1)
    c_ext = cext_ref[...].reshape(tb, 1, qp)
    feas_tol = feas_ref[...].reshape(tb, 1, 1)
    dtype = tab.dtype
    limit = static_cap if static_cap is not None else cap_ref[0]

    elig = engine.eligible_mask(qp, m, n)  # padded lanes never enter
    # Global row base of this tile: keys the RPC noise so the draw is
    # independent of the tiling (and bitwise-equal to the XLA driver's).
    row0 = cap_ref[1] + pl.program_id(0) * tb

    def body(state):
        tab, basis, phase, status, iters, rewrites, step = state
        active = status == RUNNING

        noise = (
            engine.rpc_noise(seed, step, row0, tb, qp, dtype)
            if rule == engine.RPC
            else None
        )
        e, max_c = engine.select_entering(
            tab[:, m : m + 1, :], elig, rule, tol, noise
        )
        at_opt = max_c <= tol

        phase, status, to_phase2 = engine.phase_status(
            tab, phase, status, at_opt, feas_tol, spec
        )
        # The objective rewrite only where an LP of the tile needs it; a
        # closed gate skips it, which is exact (see the module docstring).
        gate = jnp.any(to_phase2)
        row = jax.lax.cond(
            gate,
            lambda: engine.phase2_row(
                tab, basis, to_phase2, c_ext, spec,
                gather=False,  # Mosaic: one-hot reductions only
            ),
            lambda: tab[:, m : m + 1, :],
        )
        tab = _put_row(tab, m, row)
        rewrites = rewrites + gate.astype(jnp.int32)

        pivoting = active & ~at_opt
        l, min_ratio, full_col = engine.ratio_test(tab, basis, e, spec, tol)
        unbounded = pivoting & (min_ratio >= _BIG / 2)
        status = jnp.where(unbounded, UNBOUNDED, status)
        do_pivot = pivoting & ~unbounded

        tab, basis = engine.pivot_update(
            tab, basis, e, l, full_col, do_pivot, spec, tol, gather=False
        )
        iters = iters + do_pivot.astype(jnp.int32)
        return tab, basis, phase, status, iters, rewrites, step + 1

    def cond(state):
        _, _, _, status, _, _, step = state
        return jnp.logical_and(step < limit, jnp.any(status == RUNNING))

    status0 = jnp.full((tb, 1, 1), RUNNING, jnp.int32)
    iters0 = jnp.zeros((tb, 1, 1), jnp.int32)
    tab, basis, phase, status, iters, rewrites, _ = jax.lax.while_loop(
        cond, body,
        (tab, basis, phase, status0, iters0, jnp.int32(0), jnp.int32(0)),
    )
    status = jnp.where(status == RUNNING, ITER_LIMIT, status)

    # Finite sentinel instead of -inf inside the kernel; the wrapper
    # (kernels/ops.py) re-masks non-optimal objectives to -inf outside.
    objective, x = engine.extract_solution(
        tab, basis, status, spec, np_pad, fill=-_BIG
    )

    obj_ref[...] = objective.reshape(tb, 1)
    x_ref[...] = x.reshape(tb, np_pad)
    status_ref[...] = status.reshape(tb, 1)
    iters_ref[...] = iters.reshape(tb, 1)
    basis_out_ref[...] = engine.to_row(basis, mp, gather=False).reshape(tb, mp)
    rewrites_ref[...] = jnp.full((tb, 1), rewrites, jnp.int32)
    if want_state:
        tab_out_ref, phase_out_ref = state_out_refs
        tab_out_ref[...] = tab
        phase_out_ref[...] = phase.reshape(tb, 1)


def simplex_pallas(
    tab: jnp.ndarray,  # (B, M1p, Qp) padded tableau
    basis: jnp.ndarray,  # (B, Mp) int32 padded
    phase: jnp.ndarray,  # (B, 1) int32
    c_ext: jnp.ndarray,  # (B, Qp)
    feas_tol: jnp.ndarray,  # (B, 1) phase-I feasibility threshold
    cap: jnp.ndarray,  # (2,) int32 iteration cap (traced), first global row
    *,
    spec: TableauSpec,
    n_padded: int,
    rule: str = engine.LPC,
    seed: int = 0,
    tile_b: int = 8,
    tol: float = 1e-5,
    static_cap: Optional[int] = None,
    want_state: bool = False,
    vmem_limit_bytes: int,
    interpret: bool = False,
):
    """Launch the VMEM-resident simplex kernel over batch tiles.

    ``cap`` rides in SMEM as a (2,) scalar input shared by every tile —
    the iteration cap, then the batch's first global row (keying the RPC
    noise when a sharded batch launches per device);
    ``static_cap`` (a trace-time int) overrides it for the cap-specialized
    baseline.  With ``want_state`` the kernel also writes the terminal
    tableau and phase (padded) so a capped round can be resumed exactly.
    ``spec`` (static) fixes the tableau layout the padded blocks carry.
    Per-LP scalars travel as (B, 1) columns, so every block is 2-D or 3-D
    with a sublane extent Mosaic accepts when ``tile_b`` is a multiple of
    8 or the whole batch (``kernels/ops.py:auto_tile_b`` guarantees it).
    ``vmem_limit_bytes`` is the scoped-VMEM limit Mosaic compiles the
    kernel under — the same budget the tile rules planned against.

    A ``tile_b`` larger than the (padded) batch is clamped down to it —
    a small batch runs as one small tile instead of being padded up to a
    full-size tile.  A batch that is not a tile multiple is a caller bug
    and raises.
    """
    bsz, m1p, qp = tab.shape
    mp = basis.shape[1]
    tile_b = min(tile_b, bsz)
    if bsz % tile_b != 0:
        raise ValueError(
            f"batch {bsz} is not a multiple of tile_b {tile_b}; "
            "pad the batch to a tile multiple (see kernels/ops.py)"
        )
    grid = (bsz // tile_b,)

    kernel = functools.partial(
        _kernel,
        spec=spec,
        rule=rule,
        seed=seed,
        tol=tol,
        static_cap=static_cap,
        want_state=want_state,
    )
    per_lp = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))
    # The tableau block is single-buffered: one tile's solve is long
    # enough that prefetching the next tile would hide nothing, while a
    # second buffer would cost as much VMEM as the tableau itself.
    tab_block = pl.BlockSpec(
        (tile_b, m1p, qp), lambda i: (i, 0, 0), pipeline_mode=pl.Buffered(1)
    )
    out_specs = [
        per_lp,
        pl.BlockSpec((tile_b, n_padded), lambda i: (i, 0)),
        per_lp,
        per_lp,
        pl.BlockSpec((tile_b, mp), lambda i: (i, 0)),
        per_lp,
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bsz, 1), tab.dtype),
        jax.ShapeDtypeStruct((bsz, n_padded), tab.dtype),
        jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        jax.ShapeDtypeStruct((bsz, mp), jnp.int32),
        jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
    ]
    if want_state:
        out_specs += [tab_block, per_lp]
        out_shape += [
            jax.ShapeDtypeStruct((bsz, m1p, qp), tab.dtype),
            jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            tab_block,
            pl.BlockSpec((tile_b, mp), lambda i: (i, 0)),
            per_lp,
            pl.BlockSpec((tile_b, qp), lambda i: (i, 0)),
            per_lp,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(cap, tab, basis, phase, c_ext, feas_tol)

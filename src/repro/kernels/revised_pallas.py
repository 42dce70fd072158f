"""Pallas TPU kernel: shared-A revised simplex, basis state in VMEM.

The shared-structure twin of ``simplex_pallas.py``.  A ``SharedLPBatch``
carries ONE constraint matrix for thousands of ``c``/``b`` variants, so
the tableau kernel's per-LP O(m·(n+m)) VMEM block collapses to

* one (m, n) block of ``A`` mapped into VMEM ONCE per tile — its
  BlockSpec index map is ``lambda i: (0, 0)``, so every grid step reads
  the SAME block and Mosaic keeps it resident across tiles, and
* per-LP basis state only: ``binv`` (m, m), ``xb`` (m,), ``basis`` (m,)
  int32, ``phase`` — O(m²) per LP.

That is the whole point of the shared path (ISSUE 8): the auto-tiler
(``kernels/ops.py:revised_auto_tile_b``) budgets the shared block once
and then packs LPs by their O(m²) state, so a tile holds far more LPs
than the tableau kernel could at the same shape.

The iteration math is NOT implemented here: the kernel body drives
``core/revised.py:iteration_step`` / ``finalize`` — the exact functions
the XLA lockstep driver runs — with ``gather=False`` so every selection
lowers to broadcasted-iota one-hot form (same floats: one nonzero term
per reduction).  ``row0 = cap[1] + program_id * tile_b`` keys the RPC noise so
the tiled kernel draws bitwise the same noise as the untiled XLA path.

Compile-once dispatch as everywhere else: the iteration cap is a
scalar INPUT in SMEM shared by every tile, ``static_cap`` restores the
cap-specialized lowering, and ``want_state`` adds (binv, xb, phase)
outputs so a capped round resumes exactly
(``core/revised.py:RevisedResumeState``).

Padding contract (applied by ``kernels/ops.py:_revised_launch``): m to
the 8-sublane boundary, n to the 128-lane boundary, batch to a tile
multiple.  The kernel slices every block back to the LOGICAL (m, n)
before doing math — basis IDs encode the logical column layout
(1..n vars, n+1..n+m slacks, >n+m artificials), so padded shapes would
silently renumber them.  Padded batch rows ride in as empty phase-II
LPs (b = 0, c = 0, binv = 0, basis = 0) and go OPTIMAL on their first
pricing pass.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import engine, revised
from ..core.lp import RUNNING


def _kernel(
    cap_ref,  # (2,) i32 SMEM — iteration cap (compile-once caps), first global row
    a_ref,  # (Mp, Np) f32 VMEM — the ONE shared constraint matrix
    b_ref,  # (TB, Mp) f32 VMEM
    c_ref,  # (TB, Np) f32 VMEM
    binv_ref,  # (TB, Mp, Mp) f32 VMEM — basis inverse (signed system)
    basis_ref,  # (TB, Mp) i32 VMEM
    xb_ref,  # (TB, Mp) f32 VMEM
    phase_ref,  # (TB, 1) i32 VMEM
    feas_ref,  # (TB, 1) f32 VMEM — per-LP phase-I feasibility threshold
    x_ref,  # out (TB, Np) f32
    status_ref,  # out (TB, 1) i32
    iters_ref,  # out (TB, 1) i32
    basis_out_ref,  # out (TB, Mp) i32 — final basis (warm-start reuse)
    xb_out_ref,  # out (TB, Mp) f32 — terminal basic values (objective + resume)
    *refs,  # want_state: out (TB, Mp, Mp) f32 binv, (TB, 1) i32 phase; then
    # scratch: (TB, m, 1) i32 basis, f32 xb; (TB, 1, 1) i32 phase, status, iters
    m: int,
    n: int,
    rule: str,
    seed: int,
    tol: float,
    static_cap: Optional[int],
    want_state: bool,
):
    tb, mp = b_ref.shape
    np_pad = c_ref.shape[1]
    state_out_refs, (basis_s, xb_s, phase_s, status_s, iters_s) = refs[:-5], refs[-5:]

    def row(ref):  # (TB, K) block -> (TB, 1, K) row
        return ref[...].reshape(tb, 1, ref.shape[1])

    # Slice every block back to logical (m, n): basis IDs encode the
    # logical column layout, so the math must not see padded lanes.
    # Orientation as in core/revised.py:iteration_step — basis rows on
    # sublanes, constraints and variables on lanes.
    a = a_ref[...][:m, :n]
    c = row(c_ref)[:, :, :n]
    binv = binv_ref[...][:, :m, :m]
    basis = engine.to_column(row(basis_ref), gather=False)[:, :m, :]
    xb = engine.to_column(row(xb_ref), gather=False)[:, :m, :]
    phase = phase_ref[...].reshape(tb, 1, 1)
    feas_tol = feas_ref[...].reshape(tb, 1, 1)
    dtype = a.dtype
    limit = static_cap if static_cap is not None else cap_ref[0]

    sgn = revised._signs(row(b_ref)[:, :, :m], dtype)
    # Global row base of this tile: keys the RPC noise so the draw is
    # independent of the tiling (and bitwise-equal to the XLA driver's).
    row0 = cap_ref[1] + pl.program_id(0) * tb

    # The (TB, m, 1) columns and (TB, 1, 1) scalars of the loop state live
    # in VMEM scratch, not in the while_loop carry: Mosaic fixes a carry's
    # layout from its initial value, and a column built by a lane
    # reduction is lane-replicated there while the loop body yields it
    # lane-aligned — a relayout (lane broadcast) Mosaic does not
    # implement.  A ref load always has the plain layout.
    scalars = (basis_s, xb_s, phase_s, status_s, iters_s)
    basis_s[...] = basis
    xb_s[...] = xb
    phase_s[...] = phase
    status_s[...] = jnp.full((tb, 1, 1), RUNNING, jnp.int32)
    iters_s[...] = jnp.zeros((tb, 1, 1), jnp.int32)

    def load(binv, step):
        return revised._RState(binv, *(ref[...] for ref in scalars), step)

    def body(carry):
        s = revised.iteration_step(
            a, c, sgn, feas_tol, load(*carry),
            rule=rule, tol=tol, seed=seed, row0=row0,
            gather=False,  # Mosaic: one-hot reductions only
        )
        for ref, v in zip(scalars, (s.basis, s.xb, s.phase, s.status, s.iters)):
            ref[...] = v
        return s.binv, s.step

    def cond(carry):
        return jnp.logical_and(carry[1] < limit, jnp.any(status_s[...] == RUNNING))

    final = load(*jax.lax.while_loop(cond, body, (binv, jnp.int32(0))))

    # The objective is NOT computed here: the wrapper evaluates
    # core/revised.py:objective_value on the exact (basis, xb) outputs,
    # the same XLA function the XLA driver uses.  The x scatter below is
    # order-safe (one nonzero term per reduction).
    x, status = revised.finalize(final, c, m, n, gather=False)

    status_ref[...] = status.reshape(tb, 1)
    iters_ref[...] = final.iters.reshape(tb, 1)
    x_ref[...] = jnp.pad(x, ((0, 0), (0, 0), (0, np_pad - n))).reshape(tb, np_pad)
    basis_out_ref[...] = engine.to_row(final.basis, mp, gather=False).reshape(tb, mp)
    xb_out_ref[...] = engine.to_row(final.xb, mp, gather=False).reshape(tb, mp)
    if want_state:
        binv_out_ref, phase_out_ref = state_out_refs
        binv_out_ref[...] = jnp.pad(
            final.binv, ((0, 0), (0, mp - m), (0, mp - m))
        )
        phase_out_ref[...] = final.phase.reshape(tb, 1)


def revised_pallas(
    a: jnp.ndarray,  # (Mp, Np) padded shared constraint matrix
    b: jnp.ndarray,  # (B, Mp) padded RHS
    c: jnp.ndarray,  # (B, Np) padded costs
    binv: jnp.ndarray,  # (B, Mp, Mp) padded basis inverse
    basis: jnp.ndarray,  # (B, Mp) int32 padded
    xb: jnp.ndarray,  # (B, Mp) padded basic solution
    phase: jnp.ndarray,  # (B, 1) int32
    feas_tol: jnp.ndarray,  # (B, 1) phase-I feasibility threshold
    cap: jnp.ndarray,  # (2,) int32 iteration cap (traced), first global row
    *,
    m: int,
    n: int,
    rule: str = engine.LPC,
    seed: int = 0,
    tile_b: int = 8,
    tol: float = 1e-5,
    static_cap: Optional[int] = None,
    want_state: bool = False,
    vmem_limit_bytes: int,
    interpret: bool = False,
):
    """Launch the shared-A revised-simplex kernel over batch tiles.

    ``a`` is NOT batched: its BlockSpec maps block (0, 0) for every grid
    step, so one VMEM-resident copy serves all tiles.  ``m``/``n`` are
    the LOGICAL shape (static); the arrays arrive lane/sublane-padded,
    per-LP scalars as (B, 1) columns.  ``cap`` rides in SMEM as a (2,)
    scalar input shared by every tile — the iteration cap, then the
    batch's first global row (keying the RPC noise); ``static_cap`` (a trace-time int)
    overrides it for the cap-specialized baseline.  The terminal
    ``basis``/``xb`` are always written (the wrapper derives the
    objective from them, outside the kernel); ``want_state`` adds (binv,
    phase) so a capped round can be resumed exactly.
    ``vmem_limit_bytes`` is the scoped-VMEM limit the kernel compiles
    under.  Tile clamping mirrors ``simplex_pallas``: a ``tile_b`` larger
    than the batch is clamped down, a batch that is not a tile multiple
    is a caller bug and raises.
    """
    bsz, mp = b.shape
    np_pad = c.shape[1]
    tile_b = min(tile_b, bsz)
    if bsz % tile_b != 0:
        raise ValueError(
            f"batch {bsz} is not a multiple of tile_b {tile_b}; "
            "pad the batch to a tile multiple (see kernels/ops.py)"
        )
    grid = (bsz // tile_b,)

    kernel = functools.partial(
        _kernel,
        m=m,
        n=n,
        rule=rule,
        seed=seed,
        tol=tol,
        static_cap=static_cap,
        want_state=want_state,
    )
    per_lp = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))
    column = pltpu.VMEM((tile_b, m, 1), jnp.int32)
    scalar = pltpu.VMEM((tile_b, 1, 1), jnp.int32)
    scratch = [column, pltpu.VMEM((tile_b, m, 1), a.dtype), scalar, scalar, scalar]
    out_specs = [
        pl.BlockSpec((tile_b, np_pad), lambda i: (i, 0)),
        per_lp,
        per_lp,
        pl.BlockSpec((tile_b, mp), lambda i: (i, 0)),
        pl.BlockSpec((tile_b, mp), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bsz, np_pad), a.dtype),
        jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        jax.ShapeDtypeStruct((bsz, mp), jnp.int32),
        jax.ShapeDtypeStruct((bsz, mp), a.dtype),
    ]
    if want_state:
        out_specs += [
            pl.BlockSpec((tile_b, mp, mp), lambda i: (i, 0, 0)),
            per_lp,
        ]
        out_shape += [
            jax.ShapeDtypeStruct((bsz, mp, mp), a.dtype),
            jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # cap
            pl.BlockSpec((mp, np_pad), lambda i: (0, 0)),  # shared A
            pl.BlockSpec((tile_b, mp), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, np_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, mp, mp), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_b, mp), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, mp), lambda i: (i, 0)),
            per_lp,
            per_lp,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(cap, a, b, c, binv, basis, xb, phase, feas_tol)

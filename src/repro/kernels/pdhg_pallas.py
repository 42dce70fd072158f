"""Pallas TPU kernel: whole-solve-in-VMEM batched restarted PDHG.

The first-order counterpart of ``simplex_pallas.py``: a tile of TB
complete LPs — problem data (A, b, c) plus the PDHG iterate state — is
mapped into VMEM via BlockSpec and the ENTIRE restarted-PDHG loop runs
inside the kernel, so per-iteration HBM traffic is zero.  Where the
simplex kernel holds an O(m (n + m)) tableau per LP, this one holds only
the O(m n) data block plus a handful of length-m/n vectors, which is what
lets it serve the m, n >= 500 shapes the tableau cannot even allocate
(see ``kernels/ops.py:pdhg_fits_vmem``).

The iteration math is NOT implemented here: the kernel body drives
``core/pdhg.py:pdhg_step`` — the same step function the XLA driver runs —
with broadcast-multiply-reduce matvecs in place of ``einsum`` (Mosaic
lowers the former as exact float32 products on the VPU; both drivers
agree to float round-off of the reduction order).  Step sizes (tau, sigma, ||A||) ride in as per-LP
inputs, computed once by the wrapper via the shared
``core/pdhg.py:step_sizes`` — power iteration is pure matvec and COULD
run in-kernel, but hoisting it keeps the kernel a single while_loop and
guarantees both drivers use bit-identical step sizes.

Zero-padding is self-consistent for PDHG: lanes/sublanes padded with
zeros in A, b, c start at x = y = 0 and STAY zero through every prox
step (the update is ``relu(0 + tau * 0)``), padded batch rows are
all-zero LPs whose KKT residuals vanish at the origin (they go OPTIMAL
on step one and coast), and zero lanes contribute nothing to any norm or
reduction ``pdhg_step`` takes — so no masking is needed anywhere.

Compile-once dispatch: the iteration cap enters as a SCALAR INPUT in
SMEM (``cap_ref``), so the compaction scheduler's geometric round caps all
run the one compiled kernel per LP shape; ``static_cap`` restores the
cap-specialized lowering as a benchmark baseline.  Unlike the simplex
kernel there is no ``want_state`` flag — the PDHG iterate state IS the
natural output set, so the kernel always writes it and the wrapper
decides what to expose.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import engine, pdhg
from ..core.lp import ITER_LIMIT, RUNNING


def _mv(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Batched ``A @ x``: (TB, Mp, Np) x row (TB, 1, Np) -> column (TB, Mp, 1)."""
    return jnp.sum(a * x, axis=2, keepdims=True)


def _rmv(a: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Batched ``A' @ y``: (TB, Mp, Np) x column (TB, Mp, 1) -> row (TB, 1, Np)."""
    return jnp.sum(a * y, axis=1, keepdims=True)


def _kernel(
    cap_ref,  # (1,) i32 SMEM — iteration cap (scalar input: compile-once caps)
    a_ref,  # (TB, Mp, Np) f32 VMEM — constraint matrices (zero-padded)
    b_ref,  # (TB, Mp) f32 VMEM
    c_ref,  # (TB, Np) f32 VMEM
    x_ref,  # (TB, Np) f32 VMEM — primal iterate in
    y_ref,  # (TB, Mp) f32 VMEM — dual iterate in
    ax_ref,  # (TB, Mp) f32 VMEM — carried A @ x in
    xs_ref,  # (TB, Np) f32 VMEM — restart running sums in
    ys_ref,  # (TB, Mp) f32 VMEM
    axs_ref,  # (TB, Mp) f32 VMEM
    inner_ref,  # (TB, 1) i32 VMEM — steps since last restart
    xg_ref,  # (TB, 1) f32 VMEM — ||x|| at last restart boundary (growth gate)
    yg_ref,  # (TB, 1) f32 VMEM — ||y|| at last restart boundary
    tau_ref,  # (TB, 1) f32 — primal step (wrapper-computed, shared step_sizes)
    sigma_ref,  # (TB, 1) f32 — dual step
    anorm_ref,  # (TB, 1) f32 — ||A||_2 estimate (certificate scale)
    x_out_ref,  # out (TB, Np) f32
    y_out_ref,  # out (TB, Mp) f32
    ax_out_ref,  # out (TB, Mp) f32
    xs_out_ref,  # out (TB, Np) f32
    ys_out_ref,  # out (TB, Mp) f32
    axs_out_ref,  # out (TB, Mp) f32
    inner_out_ref,  # out (TB, 1) i32
    xg_out_ref,  # out (TB, 1) f32
    yg_out_ref,  # out (TB, 1) f32
    status_ref,  # out (TB, 1) i32
    iters_ref,  # out (TB, 1) i32
    *,
    tol: float,
    restart: int,
    static_cap: Optional[int],
):
    a = a_ref[...]
    tb, mp, np_pad = a.shape
    limit = static_cap if static_cap is not None else cap_ref[0]

    # Orientation (see core/engine.py): primal vectors are (TB, 1, Np)
    # rows, dual vectors (TB, Mp, 1) columns, per-LP scalars (TB, 1, 1),
    # so both matvecs are single-axis reductions and no axis ever moves
    # inside the loop.  Dual vectors arrive and leave as (TB, Mp) rows.
    def row(ref):
        return ref[...].reshape(tb, 1, ref.shape[1])

    def col(ref):
        return engine.to_column(row(ref), gather=False)

    def scalar(ref):
        return ref[...].reshape(tb, 1, 1)

    b = col(b_ref)
    c = row(c_ref)
    tau = scalar(tau_ref)
    sigma = scalar(sigma_ref)
    # bscale/cscale are one reduction each — cheaper to recompute on the
    # zero-padded tiles (padding contributes nothing to an L2 norm) than
    # to ship two more vector inputs.
    scales = (
        scalar(anorm_ref),
        1.0 + pdhg._lp_norm(b),
        1.0 + pdhg._lp_norm(c),
    )

    def body(state):
        x, y, ax, xs, ys, axs, inner, xg, yg, status, iters, step = state
        out = pdhg.pdhg_step(
            a, b, c, x, y, ax, xs, ys, axs, inner, xg, yg, status, iters,
            tau, sigma, scales, tol=tol, restart=restart, mv=_mv, rmv=_rmv,
        )
        return (*out, step + 1)

    def cond(state):
        status, step = state[-3], state[-1]
        return jnp.logical_and(step < limit, jnp.any(status == RUNNING))

    status0 = jnp.full((tb, 1, 1), RUNNING, jnp.int32)
    iters0 = jnp.zeros((tb, 1, 1), jnp.int32)
    carry0 = (
        row(x_ref), col(y_ref), col(ax_ref),
        row(xs_ref), col(ys_ref), col(axs_ref),
        scalar(inner_ref), scalar(xg_ref), scalar(yg_ref),
        status0, iters0, jnp.int32(0),
    )
    x, y, ax, xs, ys, axs, inner, xg, yg, status, iters, _ = jax.lax.while_loop(
        cond, body, carry0
    )
    status = jnp.where(status == RUNNING, ITER_LIMIT, status)

    for ref, v in ((x_out_ref, x), (xs_out_ref, xs)):
        ref[...] = v.reshape(tb, np_pad)
    for ref, v in ((y_out_ref, y), (ax_out_ref, ax), (ys_out_ref, ys), (axs_out_ref, axs)):
        ref[...] = engine.to_row(v, mp, gather=False).reshape(tb, mp)
    for ref, v in (
        (inner_out_ref, inner), (xg_out_ref, xg), (yg_out_ref, yg),
        (status_ref, status), (iters_ref, iters),
    ):
        ref[...] = v.reshape(tb, 1)


def pdhg_pallas(
    a: jnp.ndarray,  # (B, Mp, Np) zero-padded constraint matrices
    b: jnp.ndarray,  # (B, Mp)
    c: jnp.ndarray,  # (B, Np)
    x: jnp.ndarray,  # (B, Np) iterate state (padded)
    y: jnp.ndarray,  # (B, Mp)
    ax: jnp.ndarray,  # (B, Mp)
    x_sum: jnp.ndarray,  # (B, Np)
    y_sum: jnp.ndarray,  # (B, Mp)
    ax_sum: jnp.ndarray,  # (B, Mp)
    inner: jnp.ndarray,  # (B, 1) int32
    x_grow: jnp.ndarray,  # (B, 1) growth-gate norms at last restart boundary
    y_grow: jnp.ndarray,  # (B, 1)
    tau: jnp.ndarray,  # (B, 1) per-LP step sizes (shared step_sizes)
    sigma: jnp.ndarray,  # (B, 1)
    anorm: jnp.ndarray,  # (B, 1)
    cap: jnp.ndarray,  # (1,) int32 iteration cap (traced scalar input)
    *,
    tol: float,
    restart: int,
    tile_b: int = 8,
    static_cap: Optional[int] = None,
    vmem_limit_bytes: int,
    interpret: bool = False,
):
    """Launch the VMEM-resident PDHG kernel over batch tiles.

    All arrays arrive pre-padded (zero lanes/sublanes/rows — see module
    docstring for why zero-padding needs no masks); padding and stripping
    live in ``kernels/ops.py:pdhg_solve``/``pdhg_resume``.  Returns the 11
    per-LP outputs ``(x, y, ax, x_sum, y_sum, ax_sum, inner, x_grow,
    y_grow, status, iters)`` still padded, per-LP scalars as (B, 1).
    ``cap`` rides in SMEM as a (1,) scalar input shared by every tile;
    ``static_cap`` (a trace-time int) overrides it for the cap-specialized
    baseline.  ``vmem_limit_bytes`` is the scoped-VMEM limit the kernel
    compiles under.  Like the simplex kernel, a ``tile_b`` larger than
    the padded batch is clamped down; a batch that is not a tile
    multiple is a caller bug and raises.
    """
    bsz, mp, np_pad = a.shape
    tile_b = min(tile_b, bsz)
    if bsz % tile_b != 0:
        raise ValueError(
            f"batch {bsz} is not a multiple of tile_b {tile_b}; "
            "pad the batch to a tile multiple (see kernels/ops.py)"
        )
    grid = (bsz // tile_b,)

    kernel = functools.partial(
        _kernel, tol=tol, restart=restart, static_cap=static_cap
    )

    vec_m = pl.BlockSpec((tile_b, mp), lambda i: (i, 0))
    vec_n = pl.BlockSpec((tile_b, np_pad), lambda i: (i, 0))
    per_lp = pl.BlockSpec((tile_b, 1), lambda i: (i, 0))

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # cap
        # Single-buffered like the simplex tableau: a tile's solve is long,
        # and a second copy of A would cost as much VMEM as A itself.
        pl.BlockSpec(
            (tile_b, mp, np_pad), lambda i: (i, 0, 0), pipeline_mode=pl.Buffered(1)
        ),
        vec_m, vec_n,  # b, c
        vec_n, vec_m, vec_m,  # x, y, ax
        vec_n, vec_m, vec_m,  # x_sum, y_sum, ax_sum
        per_lp,  # inner
        per_lp, per_lp,  # x_grow, y_grow
        per_lp, per_lp, per_lp,  # tau, sigma, anorm
    ]
    out_specs = [
        vec_n, vec_m, vec_m,  # x, y, ax
        vec_n, vec_m, vec_m,  # x_sum, y_sum, ax_sum
        per_lp, per_lp, per_lp,  # inner, x_grow, y_grow
        per_lp, per_lp,  # status, iters
    ]
    dtype = a.dtype
    out_shape = [
        jax.ShapeDtypeStruct((bsz, np_pad), dtype),
        jax.ShapeDtypeStruct((bsz, mp), dtype),
        jax.ShapeDtypeStruct((bsz, mp), dtype),
        jax.ShapeDtypeStruct((bsz, np_pad), dtype),
        jax.ShapeDtypeStruct((bsz, mp), dtype),
        jax.ShapeDtypeStruct((bsz, mp), dtype),
        jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        jax.ShapeDtypeStruct((bsz, 1), dtype),
        jax.ShapeDtypeStruct((bsz, 1), dtype),
        jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
        jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(
        cap, a, b, c, x, y, ax, x_sum, y_sum, ax_sum, inner, x_grow, y_grow,
        tau, sigma, anorm,
    )

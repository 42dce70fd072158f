"""Jitted wrappers for the Pallas kernels: padding, tiling, CPU fallback.

``interpret`` defaults to True off-TPU so the kernels execute (and are
tested) on CPU; on a TPU backend the same calls compile through Mosaic.

The simplex wrappers follow the compile-once dispatch contract: the
iteration cap is a traced kernel input (see ``simplex_pallas.py``), so
:func:`simplex_solve` calls with different ``max_iters`` over one shape
share one executable, and :func:`simplex_resume` continues a carried
``ResumeState`` exactly (padding re-applied here, stripped on the way
out).

This is also where the tableau storage layer (``core/tableau.py``) meets
the hardware: all padded shapes derive from a ``TableauSpec``, the VMEM
cost of one LP inside the kernel is estimated by
:func:`kernel_vmem_bytes_per_lp`, and the batch tile is sized from that
estimate and from Mosaic's compile time (:func:`auto_tile_b`): small
LPs share a tile by the dozen, large ones run 8 to a tile.  Shapes
whose smallest legal tile (8 LPs) exceeds the budget report
``fits_vmem() == False``; the ``pallas`` backend (``core/backends.py``)
routes those to ``xla`` instead of failing.

A batch sharded across a mesh launches each kernel once per device over
its own rows (:func:`_launch_split`): Mosaic kernels have no GSPMD
partitioning rule.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core import engine, pdhg, revised
from ..core.bucketing import next_pow2
from ..core.lp import LPSolution, ResumeState, build_tableau
from ..core.tableau import DEFAULT_LAYOUT, TableauSpec
from ..core.simplex import resolve_cap
from .hyperbox_pallas import hyperbox_pallas
from .pdhg_pallas import pdhg_pallas
from .revised_pallas import revised_pallas
from .simplex_pallas import simplex_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


#: Scoped-VMEM limit every kernel is compiled under
#: (``pltpu.CompilerParams(vmem_limit_bytes=...)``) and the capacity the
#: tile rules below plan against — one number, so a tile the planner
#: accepts is a tile Mosaic is allowed to allocate.  96 MiB is three
#: quarters of a v5e TensorCore's 128 MiB of VMEM.  Overridable for tests
#: / other parts.
VMEM_BUDGET_BYTES = int(os.environ.get("REPRO_VMEM_BUDGET_BYTES", 96 * 2**20))

#: Fraction of the budget the per-LP estimates may fill — headroom for
#: what they do not count (the small per-tile blocks, semaphores) and
#: for their own calibration error.
VMEM_TILE_FRACTION = 0.8

#: Smallest batch tile Mosaic accepts when a tile covers only part of
#: the batch: per-LP data rides in 2-D blocks whose batch axis is the
#: sublane axis, and a partial block must span whole 8-sublane tiles.
MIN_TILE_B = 8

#: Most bytes of per-LP blocks one batch tile may span (see
#: :func:`_budget_tile`): about 256 vregs per elementwise op, which keeps
#: each kernel's Mosaic compile within seconds (m = n = 100 tableau:
#: 3 s at a tile of 8, 38 s at 64, compiled for a v5e).
TILE_BLOCK_BYTES = 1 << 20


def legal_tile_b(tile_b: int, bsz: int) -> int:
    """The nearest tile >= ``tile_b`` that Mosaic accepts for ``bsz`` LPs.

    A tile covering the whole batch is always legal (its blocks equal
    the padded array); any other tile is rounded up to a multiple of
    :data:`MIN_TILE_B`.  Results never depend on the tile, so this only
    ever changes how the batch is cut.
    """
    tile_b = max(1, int(tile_b))
    if tile_b >= bsz:
        return tile_b
    return _round_up(tile_b, MIN_TILE_B)


def _budget_tile(bsz: int, per_lp: int, block: int, fixed: int = 0) -> int:
    """Largest legal power-of-two tile (<= 128) that fits both budgets.

    ``fixed`` bytes of VMEM are charged once per tile (a shared block),
    ``per_lp`` bytes per LP.  ``block`` is the VMEM size of one LP's
    largest block (tableau, ``A`` or ``B^-1``): a tile spans at most
    :data:`TILE_BLOCK_BYTES` of it, because Mosaic unrolls every
    elementwise op over the tile's vregs and its compile time grows
    faster than linearly with that count.  Clamped down to the
    pow-2-padded batch, and up to the smallest legal tile — shapes where
    even that busts the VMEM budget are the router's problem
    (:func:`fits_vmem` and its twins), not the tiler's.
    """
    budget = int(VMEM_BUDGET_BYTES * VMEM_TILE_FRACTION) - fixed
    fit = max(1, min(budget // max(per_lp, 1), TILE_BLOCK_BYTES // max(block, 1)))
    tile = 1 << (fit.bit_length() - 1)  # largest power of two <= fit
    return legal_tile_b(min(tile, 128, next_pow2(bsz)), bsz)


def _fits(per_lp: int, fixed: int = 0) -> bool:
    """Whether the smallest partial tile (:data:`MIN_TILE_B` LPs) fits."""
    return fixed + MIN_TILE_B * per_lp <= int(VMEM_BUDGET_BYTES * VMEM_TILE_FRACTION)


# ---------------------------------------------------------------------------
# batch-sharded launches: one kernel per device over its own rows
# ---------------------------------------------------------------------------


def _batch_split(x):
    """``(mesh, axes)`` when ``x``'s batch axis is split across a mesh, else None."""
    sh = getattr(x, "sharding", None)
    if not isinstance(sh, jax.sharding.NamedSharding) or not sh.spec:
        return None
    axes = sh.spec[0]
    return None if axes is None else (sh.mesh, axes)


def _shards(split) -> int:
    """Number of pieces ``split`` cuts the batch into (1 when unsplit)."""
    if split is None:
        return 1
    mesh, axes = split
    names = axes if isinstance(axes, tuple) else (axes,)
    return int(np.prod([mesh.shape[a] for a in names]))


#: Per-shard launchers built so far, keyed by entry, mesh, axes and the
#: entry's static arguments — built once, so a repeat launch reuses its
#: compiled program.
_SHARDED: dict = {}


def _launch_split(entry, split, cap, shared, batched, **statics):
    """Call a jitted kernel entry, once per device when the batch is split.

    Mosaic kernels have no rule for GSPMD to partition them by, so a
    batch sharded across a mesh runs under ``shard_map``: each device
    launches the kernel over its own rows, with no cross-device work.
    ``entry`` is called as ``entry(*shared, *batched, cap, **statics)``;
    ``shared`` arguments are replicated, ``batched`` ones split along
    their first axis.  Where ``cap`` has a second entry (the first
    global row of the launch, keying the RPC noise), each shard sets it
    to its own first row, so results match the unsplit launch.
    """
    if split is None:
        return entry(*shared, *batched, cap, **statics)
    mesh, axes = split
    key = (entry, mesh, axes, tuple(sorted(statics.items())))
    fn = _SHARDED.get(key)
    if fn is None:
        rows = jax.sharding.PartitionSpec(axes)
        whole = jax.sharding.PartitionSpec()

        def local(cap, shared, batched):
            size = jax.tree_util.tree_leaves(batched)[0].shape[0]
            base = jax.lax.axis_index(axes) * size
            cap = jnp.concatenate([cap[:1], cap[1:] + base])
            return entry(*shared, *batched, cap, **statics)

        fn = _SHARDED[key] = jax.jit(
            jax.shard_map(
                local, mesh=mesh, in_specs=(whole, whole, rows), out_specs=rows,
                check_vma=False,
            )
        )
    return fn(cap, shared, batched)


def _split_cache_size(entries) -> int:
    """Compiled per-shard launchers of the given entries."""
    return sum(int(fn._cache_size()) for key, fn in _SHARDED.items() if key[0] in entries)



def _pad_shapes(bsz: int, spec: TableauSpec, tile_b: int):
    return (
        _round_up(spec.q, 128),
        _round_up(spec.m + 1, 8),
        _round_up(spec.m, 8),
        _round_up(spec.n, 128),
        _round_up(bsz, tile_b),
    )


def _tiled_bytes(rows: int, cols: int, item: int) -> int:
    """VMEM bytes of a (rows, cols) value stored in (8, 128) vreg tiles."""
    return _round_up(rows, 8) * _round_up(cols, 128) * item


def kernel_vmem_bytes_per_lp(
    spec: TableauSpec, dtype=jnp.float32, want_state: bool = False
) -> int:
    """Estimated VMEM bytes ONE LP occupies inside the simplex kernel.

    Calibrated against Mosaic's scoped-VMEM allocation for the v5e
    (compiled for a described chip, ~20% above the smallest limit that
    compiles at m = n = 28, 100, 200): four tableau-sized blocks (the
    single-buffered input plus the pivot's working copies; two more with
    the ``want_state`` output), six (rows, 1) column vectors (pivot
    column, ratios, basis, masks — each pads to whole (8, 128) tiles)
    and eight (1, 1) per-LP scalars, one vreg tile each.
    """
    qp, m1p, _, _, _ = _pad_shapes(1, spec, 1)
    item = jnp.dtype(dtype).itemsize
    tabs = 6 if want_state else 4
    return (
        tabs * _tiled_bytes(m1p, qp, item)
        + 6 * _tiled_bytes(m1p, 1, item)
        + 8 * _tiled_bytes(1, 1, item)
    )


def fits_vmem(
    m: int,
    n: int,
    dtype=jnp.float32,
    layout: str = DEFAULT_LAYOUT,
    want_state: bool = False,
) -> bool:
    """Whether a tile of :data:`MIN_TILE_B` LPs of this shape fits VMEM.

    The routing predicate the ``pallas`` backend consults before
    launching: a shape that cannot fit the smallest legal tile is
    dispatched to the ``xla`` backend instead of failing inside Mosaic.
    """
    return _fits(kernel_vmem_bytes_per_lp(TableauSpec(m, n, layout), dtype, want_state))


def auto_tile_b(
    bsz: int, spec: TableauSpec, dtype=jnp.float32, want_state: bool = False
) -> int:
    """VMEM-budget-aware batch tile: largest power of two that fits.

    The tile is sized so ``tile_b * kernel_vmem_bytes_per_lp`` stays
    within the tile's share of VMEM and the tile's tableaus within
    :data:`TILE_BLOCK_BYTES`, capped at 128 and clamped down to the
    (power-of-two-padded) batch so small batches run as one small tile
    rather than padding up to a full-size tile (:func:`_budget_tile`).
    Never returns a tile Mosaic refuses
    (:func:`legal_tile_b`) — un-fittable shapes are the backend router's
    problem (:func:`fits_vmem`), not the tiler's.

    A MEASURED winning tile from the autotuner's cache
    (``runtime/autotune.py:cached_tile_b``) overrides the heuristic when
    one exists for this shape class — the tuner's lookup itself enforces
    the same VMEM budget, so the override can never launch a tile the
    heuristic would have rejected.  Predicted-only entries never pin a
    tile (prediction reproduces this heuristic anyway).
    """
    from ..runtime import autotune as _autotune  # lazy: avoid import cycle

    tuned = _autotune.cached_tile_b(bsz, spec.m, spec.n, dtype, spec.layout)
    if tuned is not None:
        return legal_tile_b(tuned, bsz)
    qp, m1p, _, _, _ = _pad_shapes(1, spec, 1)
    return _budget_tile(
        bsz,
        kernel_vmem_bytes_per_lp(spec, dtype, want_state),
        _tiled_bytes(m1p, qp, jnp.dtype(dtype).itemsize),
    )


def _pad_launch_inputs(tab, basis, phase, b, c, spec: TableauSpec, tile_b: int):
    """Tile/lane-pad an unpadded (tableau, basis, phase) triple + costs.

    Shared by the cold and resume entry points so a resumed round re-pads
    the carried state exactly the way the cold launch padded its tableau:
    padded batch entries are trivially optimal empty LPs (phase 2, zero
    objective row), padded lanes/sublanes are zero.
    """
    bsz = tab.shape[0]
    m, n, q = spec.m, spec.n, spec.q
    dtype = tab.dtype
    qp, m1p, mp, np_pad, bp = _pad_shapes(bsz, spec, tile_b)

    tab_p = jnp.zeros((bp, m1p, qp), dtype)
    # Keep the objective row at index m (kernel uses static m); padding rows
    # sit AFTER it and stay zero (never selected: their pivot column is 0).
    tab_p = tab_p.at[:bsz, : m + 1, :q].set(tab)
    basis_p = jnp.zeros((bp, mp), jnp.int32).at[:bsz, :m].set(basis)
    phase_p = jnp.full((bp, 1), 2, jnp.int32).at[:bsz, 0].set(phase)
    c_ext = jnp.zeros((bp, qp), dtype).at[:bsz, 1 : 1 + n].set(c)
    feas = engine.phase1_feasibility_tol(b).astype(dtype)
    feas_p = jnp.ones((bp, 1), dtype).at[:bsz, 0].set(feas)
    return tab_p, basis_p, phase_p, c_ext, feas_p, np_pad


def _launch(
    tab_p, basis_p, phase_p, c_ext, feas_p, cap, *,
    bsz, spec, np_pad, rule, seed, tile_b, tol, static_cap, want_state, interpret,
):
    """Run the kernel and strip the padding off every output."""
    m, n = spec.m, spec.n
    outs = simplex_pallas(
        tab_p,
        basis_p,
        phase_p,
        c_ext,
        feas_p,
        cap,
        spec=spec,
        n_padded=np_pad,
        rule=rule,
        seed=seed,
        tile_b=tile_b,
        tol=tol,
        static_cap=static_cap,
        want_state=want_state,
        vmem_limit_bytes=VMEM_BUDGET_BYTES,
        interpret=interpret,
    )
    obj, x, status, iters, basis_out, rewrites = outs[:6]
    dtype = tab_p.dtype
    neg_inf = jnp.asarray(-jnp.inf, dtype)
    status = status[:bsz, 0]
    objective = jnp.where(status == 1, obj[:bsz, 0], neg_inf)
    # Every row of a tile carries the tile's count; keep it on the tile's
    # first row only, so a sum over rows counts each tile once.
    first = jax.lax.broadcasted_iota(jnp.int32, rewrites.shape, 0) % tile_b == 0
    sol = LPSolution(
        objective=objective,
        x=x[:bsz, :n],
        status=status,
        iterations=iters[:bsz, 0],
        basis=basis_out[:bsz, :m],
        phase_rewrites=jnp.where(first, rewrites, 0)[:bsz, 0],
    )
    if not want_state:
        return sol
    tab_out, phase_out = outs[6:]
    state = ResumeState(
        tab=tab_out[:bsz, : m + 1, : spec.q],
        basis=basis_out[:bsz, :m],
        phase=phase_out[:bsz, 0],
    )
    return sol, state


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "rule", "seed", "tol", "tile_b", "static_cap", "want_state",
        "interpret",
    ),
)
def _solve_jit(
    a, b, c, basis0, cap, *,
    spec, rule, seed, tol, tile_b, static_cap, want_state, interpret,
):
    bsz = a.shape[0]
    tab, basis, phase = build_tableau(a, b, c, basis0, spec)
    tab_p, basis_p, phase_p, c_ext, feas_p, np_pad = _pad_launch_inputs(
        tab, basis, phase, b, c, spec, tile_b
    )
    return _launch(
        tab_p, basis_p, phase_p, c_ext, feas_p, cap,
        bsz=bsz, spec=spec, np_pad=np_pad, rule=rule, seed=seed, tile_b=tile_b,
        tol=tol, static_cap=static_cap, want_state=want_state, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "rule", "seed", "tol", "tile_b", "static_cap", "want_state",
        "interpret",
    ),
)
def _resume_jit(
    b, c, state, cap, *,
    spec, rule, seed, tol, tile_b, static_cap, want_state, interpret,
):
    bsz = state.basis.shape[0]
    tab_p, basis_p, phase_p, c_ext, feas_p, np_pad = _pad_launch_inputs(
        state.tab, state.basis, state.phase, b, c, spec, tile_b
    )
    return _launch(
        tab_p, basis_p, phase_p, c_ext, feas_p, cap,
        bsz=bsz, spec=spec, np_pad=np_pad, rule=rule, seed=seed, tile_b=tile_b,
        tol=tol, static_cap=static_cap, want_state=want_state, interpret=interpret,
    )


def compile_cache_size() -> int:
    """Pallas-driver executables compiled so far (cold + resume paths).

    The ``pallas`` backend's hook behind ``SolveStats.compiles`` /
    ``SolveStats.cache_hits``.
    """
    return (
        int(_solve_jit._cache_size())
        + int(_resume_jit._cache_size())
        + _split_cache_size((_solve_jit, _resume_jit))
    )


def simplex_solve(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    rule: str = engine.LPC,
    max_iters: int = 0,
    seed: int = 0,
    tol: float = 0.0,
    tile_b: int | None = None,
    interpret: bool | None = None,
    basis0: jnp.ndarray | None = None,
    want_state: bool = False,
    dynamic_cap: bool = True,
    layout: str = DEFAULT_LAYOUT,
):
    """Solve a batch of LPs with the VMEM-resident Pallas kernel.

    a: (B, m, n), b: (B, m), c: (B, n); returns LPSolution like the core
    solver.  Batch is padded to a tile multiple; tableau columns pad to the
    128-lane boundary; rows pad to the 8-sublane boundary.  ``rule`` is any
    of ``core.engine.RULES`` ("lpc" | "rpc" | "bland"), ``seed`` drives the
    RPC noise, and ``tol`` is the reduced-cost/pivot tolerance (0 = dtype
    default) — the same knobs, honored identically, as the XLA lockstep
    path, since both drive ``core/engine.py``.  ``basis0`` is an optional
    (B, m) warm-start basis — handled host-of-kernel in ``build_tableau``,
    so warm rows enter the kernel already in phase II; the final basis
    comes back in ``LPSolution.basis`` for reuse.

    ``layout`` selects the tableau storage (``"compact"`` default /
    ``"dense"``; see ``core/tableau.py``) — results are bit-identical,
    VMEM cost is not.  ``tile_b`` is the batch tile; None (default) sizes
    it from the VMEM budget (:func:`auto_tile_b`) — the compact layout's
    smaller tableau yields a LARGER auto tile.  Results never depend on
    the tiling.

    ``max_iters`` is a traced kernel scalar: calls with different caps over
    one shape share one executable (``dynamic_cap=False`` restores the
    cap-specialized baseline).  ``want_state`` additionally returns the
    exact terminal :class:`ResumeState` for :func:`simplex_resume`.
    """
    if interpret is None:
        interpret = not _on_tpu()
    bsz, m, n = a.shape
    split = _batch_split(b)
    bsz //= _shards(split)
    spec = TableauSpec(m, n, layout)
    if tile_b is None:
        tile_b = auto_tile_b(bsz, spec, a.dtype, want_state)
    tile_b = legal_tile_b(tile_b, bsz)
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(a.dtype)
    static_cap = None if dynamic_cap else int(cap)
    cap_arr = jnp.array([cap if dynamic_cap else 0, 0], jnp.int32)
    return _launch_split(
        _solve_jit, split, cap_arr, (), (a, b, c, basis0),
        spec=spec, rule=rule, seed=seed, tol=tol, tile_b=tile_b,
        static_cap=static_cap, want_state=want_state, interpret=interpret,
    )


def simplex_resume(
    b: jnp.ndarray,
    c: jnp.ndarray,
    state: ResumeState,
    rule: str = engine.LPC,
    max_iters: int = 0,
    seed: int = 0,
    tol: float = 0.0,
    tile_b: int | None = None,
    interpret: bool | None = None,
    want_state: bool = True,
    dynamic_cap: bool = True,
):
    """Continue a batch from a carried :class:`ResumeState` in the kernel.

    The state round-trips through the same padding the cold launch uses,
    so a sequence of resumed rounds whose step budgets sum to ``K`` is
    bit-identical to one uninterrupted kernel run with cap ``K``.  The
    layout is recovered from the carried tableau itself
    (``TableauSpec.from_tableau``) — a resume continues in whatever
    layout the interrupted solve used.
    """
    if interpret is None:
        interpret = not _on_tpu()
    bsz, m = state.basis.shape
    split = _batch_split(b)
    bsz //= _shards(split)
    n = c.shape[-1]
    spec = TableauSpec.from_tableau(m, n, state.tab.shape[-1])
    if tile_b is None:
        tile_b = auto_tile_b(bsz, spec, state.tab.dtype, want_state)
    tile_b = legal_tile_b(tile_b, bsz)
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(state.tab.dtype)
    static_cap = None if dynamic_cap else int(cap)
    cap_arr = jnp.array([cap if dynamic_cap else 0, 0], jnp.int32)
    return _launch_split(
        _resume_jit, split, cap_arr, (), (b, c, state),
        spec=spec, rule=rule, seed=seed, tol=tol, tile_b=tile_b,
        static_cap=static_cap, want_state=want_state, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# PDHG kernel wrappers — same padding/tiling contract, no tableau anywhere
# ---------------------------------------------------------------------------


def _pdhg_pad_shapes(bsz: int, m: int, n: int, tile_b: int):
    return _round_up(m, 8), _round_up(n, 128), _round_up(bsz, tile_b)


def pdhg_vmem_bytes_per_lp(m: int, n: int, dtype=jnp.float32) -> int:
    """Estimated VMEM bytes ONE LP occupies inside the PDHG kernel.

    Calibrated like :func:`kernel_vmem_bytes_per_lp` (~20% above what
    Mosaic allocates for the v5e at m = n = 28, 100, 500): three
    A-sized blocks (the single-buffered input and the matvec products),
    twenty (m, 1) dual-side column vectors — iterates, running sums and
    their temporaries, each padded to whole (8, 128) tiles — and
    forty-eight (1, 1) per-LP scalars.  The first-order counterpart of
    the tableau estimate: O(m n) where the tableau is O(m (n + m)),
    which is exactly why large shapes route here (see
    ``core/backends.py:route_shape``).
    """
    mp, np_pad, _ = _pdhg_pad_shapes(1, m, n, 1)
    item = jnp.dtype(dtype).itemsize
    return (
        3 * _tiled_bytes(mp, np_pad, item)
        + 20 * _tiled_bytes(mp, 1, item)
        + 48 * _tiled_bytes(1, 1, item)
    )


def pdhg_fits_vmem(m: int, n: int, dtype=jnp.float32) -> bool:
    """Whether a :data:`MIN_TILE_B`-LP tile of this shape fits the PDHG kernel."""
    return _fits(pdhg_vmem_bytes_per_lp(m, n, dtype))


def pdhg_auto_tile_b(bsz: int, m: int, n: int, dtype=jnp.float32) -> int:
    """VMEM-budget-aware batch tile for the PDHG kernel (pow-2, <= 128, legal)."""
    mp, np_pad, _ = _pdhg_pad_shapes(1, m, n, 1)
    return _budget_tile(
        bsz,
        pdhg_vmem_bytes_per_lp(m, n, dtype),
        _tiled_bytes(mp, np_pad, jnp.dtype(dtype).itemsize),
    )


def _pdhg_launch(a, b, c, state, cap, *, tol, restart, tile_b, static_cap,
                 want_state, interpret):
    """Pad, run the PDHG kernel, strip padding off every output.

    Zero-padding is the whole story (see ``pdhg_pallas.py``): padded
    lanes stay exactly zero through every prox step and padded batch
    rows are all-zero LPs that go OPTIMAL at the origin, so nothing
    needs masking.  Step sizes come from the UNPADDED arrays via the
    shared ``core/pdhg.py:step_sizes`` — bit-identical to the XLA
    driver's (zero-padded rows get tau = sigma = 0, which is inert).
    """
    bsz, m, n = a.shape
    dtype = a.dtype
    tau, sigma, (anorm, _, _) = pdhg.step_sizes(a, b, c)
    mp, np_pad, bp = _pdhg_pad_shapes(bsz, m, n, tile_b)

    def pad_m(v):
        return jnp.zeros((bp, mp), dtype).at[:bsz, :m].set(v)

    def pad_n(v):
        return jnp.zeros((bp, np_pad), dtype).at[:bsz, :n].set(v)

    def pad_b(v):  # per-LP scalars travel as (B, 1) columns
        return jnp.zeros((bp, 1), v.dtype).at[:bsz, 0].set(v)

    a_p = jnp.zeros((bp, mp, np_pad), dtype).at[:bsz, :m, :n].set(a)
    outs = pdhg_pallas(
        a_p, pad_m(b), pad_n(c),
        pad_n(state.x), pad_m(state.y), pad_m(state.ax),
        pad_n(state.x_sum), pad_m(state.y_sum), pad_m(state.ax_sum),
        pad_b(state.inner), pad_b(state.x_grow), pad_b(state.y_grow),
        pad_b(tau), pad_b(sigma), pad_b(anorm), cap,
        tol=tol, restart=restart, tile_b=tile_b,
        static_cap=static_cap, vmem_limit_bytes=VMEM_BUDGET_BYTES,
        interpret=interpret,
    )
    x, y, ax, xs, ys, axs, inner, xg, yg, status, iters = outs
    x, status, iters = x[:bsz, :n], status[:bsz, 0], iters[:bsz, 0]
    pobj = jnp.sum(c * x, axis=-1)
    objective = jnp.where(status == 1, pobj, jnp.asarray(-jnp.inf, dtype))
    sol = LPSolution(
        objective=objective, x=x, status=status, iterations=iters, y=y[:bsz, :m]
    )
    if not want_state:
        return sol
    out_state = pdhg.PDHGResumeState(
        x=x, y=y[:bsz, :m], ax=ax[:bsz, :m],
        x_sum=xs[:bsz, :n], y_sum=ys[:bsz, :m], ax_sum=axs[:bsz, :m],
        inner=inner[:bsz, 0], x_grow=xg[:bsz, 0], y_grow=yg[:bsz, 0],
    )
    return sol, out_state


@functools.partial(
    jax.jit,
    static_argnames=(
        "tol", "restart", "tile_b", "static_cap", "want_state", "interpret"
    ),
)
def _pdhg_solve_jit(a, b, c, cap, *, tol, restart, tile_b, static_cap,
                    want_state, interpret):
    bsz, m, n = a.shape
    return _pdhg_launch(
        a, b, c, pdhg.init_state(bsz, m, n, a.dtype), cap,
        tol=tol, restart=restart, tile_b=tile_b, static_cap=static_cap,
        want_state=want_state, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tol", "restart", "tile_b", "static_cap", "want_state", "interpret"
    ),
)
def _pdhg_resume_jit(a, b, c, state, cap, *, tol, restart, tile_b, static_cap,
                     want_state, interpret):
    return _pdhg_launch(
        a, b, c, state, cap,
        tol=tol, restart=restart, tile_b=tile_b, static_cap=static_cap,
        want_state=want_state, interpret=interpret,
    )


def pdhg_compile_cache_size() -> int:
    """PDHG-kernel executables compiled so far (cold + resume paths)."""
    return (
        int(_pdhg_solve_jit._cache_size())
        + int(_pdhg_resume_jit._cache_size())
        + _split_cache_size((_pdhg_solve_jit, _pdhg_resume_jit))
    )


def pdhg_solve(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    *,
    tol: float = 0.0,
    restart: int = 0,
    max_iters: int = 0,
    tile_b: int | None = None,
    interpret: bool | None = None,
    want_state: bool = False,
    dynamic_cap: bool = True,
):
    """Solve a canonical batch with the VMEM-resident PDHG kernel.

    Same signature family as ``core/pdhg.py:solve_batched`` (the XLA
    driver) and same padding/tiling conventions as :func:`simplex_solve`:
    batch pads to a tile multiple, n to the 128-lane boundary, m to the
    8-sublane boundary, ``tile_b=None`` sizes the tile from the VMEM
    budget, and ``max_iters`` is a traced kernel scalar under
    ``dynamic_cap`` so every cap over one shape shares one executable.
    """
    if interpret is None:
        interpret = not _on_tpu()
    bsz, m, n = a.shape
    split = _batch_split(b)
    bsz //= _shards(split)
    if tile_b is None:
        tile_b = pdhg_auto_tile_b(bsz, m, n, a.dtype)
    tile_b = legal_tile_b(tile_b, bsz)
    cap = pdhg.resolve_cap(max_iters, m, n)
    static_cap = None if dynamic_cap else int(cap)
    cap_arr = jnp.full((1,), cap if dynamic_cap else 0, jnp.int32)
    return _launch_split(
        _pdhg_solve_jit, split, cap_arr, (), (a, b, c),
        tol=pdhg.resolve_tol(tol), restart=pdhg.resolve_restart(restart),
        tile_b=tile_b, static_cap=static_cap, want_state=want_state,
        interpret=interpret,
    )


def pdhg_resume(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    state: pdhg.PDHGResumeState,
    *,
    tol: float = 0.0,
    restart: int = 0,
    max_iters: int = 0,
    tile_b: int | None = None,
    interpret: bool | None = None,
    want_state: bool = True,
    dynamic_cap: bool = True,
):
    """Continue a batch from a carried ``PDHGResumeState`` in the kernel.

    ``max_iters`` is the ADDITIONAL step budget; the state round-trips
    through the same zero-padding the cold launch uses, so resumed
    rounds replay one uninterrupted kernel run bit-for-bit — the same
    contract as :func:`simplex_resume` (but like the XLA pdhg driver, a
    resume needs ``a`` back: the matvecs read it every step).
    """
    if interpret is None:
        interpret = not _on_tpu()
    bsz, m, n = a.shape
    split = _batch_split(b)
    bsz //= _shards(split)
    if tile_b is None:
        tile_b = pdhg_auto_tile_b(bsz, m, n, a.dtype)
    tile_b = legal_tile_b(tile_b, bsz)
    cap = pdhg.resolve_cap(max_iters, m, n)
    static_cap = None if dynamic_cap else int(cap)
    cap_arr = jnp.full((1,), cap if dynamic_cap else 0, jnp.int32)
    return _launch_split(
        _pdhg_resume_jit, split, cap_arr, (), (a, b, c, state),
        tol=pdhg.resolve_tol(tol), restart=pdhg.resolve_restart(restart),
        tile_b=tile_b, static_cap=static_cap, want_state=want_state,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Shared-A revised-simplex kernel wrappers — one A block per tile, O(m²)/LP
# ---------------------------------------------------------------------------


def _revised_pad_shapes(bsz: int, m: int, n: int, tile_b: int):
    return _round_up(m, 8), _round_up(n, 128), _round_up(bsz, tile_b)


def revised_shared_vmem_bytes(m: int, n: int, dtype=jnp.float32) -> int:
    """VMEM bytes the ONE shared ``A`` block claims per tile (not per LP).

    Counted twice: the pipeline's two buffers for the input block.  Paid
    once per tile regardless of ``tile_b`` — the amortization that lets
    :func:`revised_auto_tile_b` pack far more LPs per tile than the
    tableau kernel at the same shape.
    """
    mp, np_pad, _ = _revised_pad_shapes(1, m, n, 1)
    return 2 * _tiled_bytes(mp, np_pad, jnp.dtype(dtype).itemsize)


def revised_vmem_bytes_per_lp(m: int, n: int, dtype=jnp.float32) -> int:
    """Estimated VMEM bytes ONE LP occupies inside the revised kernel.

    O(m²), not O(m·n): four (m, m) basis-inverse blocks (the input
    block's two buffers, the loop's working copy and the update), twelve
    (m, 1) columns (the scratch state, the entering column, ratios — each
    padded to whole (8, 128) tiles), six rows over ``n`` or ``m`` lanes
    and eight per-LP scalars.  The shared ``A`` block is NOT included —
    see :func:`revised_shared_vmem_bytes`.
    """
    mp, np_pad, _ = _revised_pad_shapes(1, m, n, 1)
    item = jnp.dtype(dtype).itemsize
    return (
        4 * _tiled_bytes(mp, mp, item)
        + 12 * _tiled_bytes(mp, 1, item)
        + 6 * _tiled_bytes(1, max(np_pad, mp), item)
        + 8 * _tiled_bytes(1, 1, item)
    )


def revised_fits_vmem(m: int, n: int, dtype=jnp.float32) -> bool:
    """Whether the shared block plus a :data:`MIN_TILE_B`-LP tile fits the budget.

    The routing predicate ``route_shape(shared=True)`` and the
    ``pallas-shared`` backend consult: a shape that cannot fit the
    shared ``A`` block and the smallest legal tile's basis state runs
    the XLA revised driver instead (bit-identical results).
    """
    return _fits(
        revised_vmem_bytes_per_lp(m, n, dtype), revised_shared_vmem_bytes(m, n, dtype)
    )


def revised_auto_tile_b(bsz: int, m: int, n: int, dtype=jnp.float32) -> int:
    """VMEM-budget-aware batch tile for the revised kernel (pow-2, <= 128).

    The shared ``A`` block is charged once off the top; the remainder is
    packed with O(m²) per-LP state.  Same pow-2/128-cap/batch-clamp
    conventions as :func:`auto_tile_b`.
    """
    mp, _, _ = _revised_pad_shapes(1, m, n, 1)
    return _budget_tile(
        bsz,
        revised_vmem_bytes_per_lp(m, n, dtype),
        _tiled_bytes(mp, mp, jnp.dtype(dtype).itemsize),
        revised_shared_vmem_bytes(m, n, dtype),
    )


def _revised_launch(a, b, c, state, cap, *, rule, seed, tol, tile_b,
                    static_cap, want_state, interpret):
    """Pad, run the revised kernel, strip padding off every output.

    The kernel slices back to the logical (m, n) internally (basis IDs
    encode the logical column layout), so padding here only has to be
    inert at the batch level: padded batch rows are empty phase-II LPs
    (b = 0, c = 0, binv = 0, basis = 0) whose first pricing pass finds
    every reduced cost at zero and stops OPTIMAL with objective 0.
    """
    bsz, m = b.shape
    n = a.shape[1]
    dtype = a.dtype
    feas = engine.phase1_feasibility_tol(b).astype(dtype)
    mp, np_pad, bp = _revised_pad_shapes(bsz, m, n, tile_b)

    a_p = jnp.zeros((mp, np_pad), dtype).at[:m, :n].set(a)
    b_p = jnp.zeros((bp, mp), dtype).at[:bsz, :m].set(b)
    c_p = jnp.zeros((bp, np_pad), dtype).at[:bsz, :n].set(c)
    binv_p = jnp.zeros((bp, mp, mp), dtype).at[:bsz, :m, :m].set(state.binv)
    basis_p = jnp.zeros((bp, mp), jnp.int32).at[:bsz, :m].set(state.basis)
    xb_p = jnp.zeros((bp, mp), dtype).at[:bsz, :m].set(state.xb)
    phase_p = jnp.full((bp, 1), 2, jnp.int32).at[:bsz, 0].set(state.phase)
    feas_p = jnp.ones((bp, 1), dtype).at[:bsz, 0].set(feas)

    outs = revised_pallas(
        a_p, b_p, c_p, binv_p, basis_p, xb_p, phase_p, feas_p, cap,
        m=m, n=n, rule=rule, seed=seed, tile_b=tile_b, tol=tol,
        static_cap=static_cap, want_state=want_state,
        vmem_limit_bytes=VMEM_BUDGET_BYTES, interpret=interpret,
    )
    x, status, iters, basis_out, xb_out = outs[:5]
    status, basis_l, xb_l = status[:bsz, 0], basis_out[:bsz, :m], xb_out[:bsz, :m]
    # Objective OUTSIDE the kernel, by the XLA driver's own function on
    # the exact terminal (basis, xb) — see revised_pallas.py.
    objective = revised.objective_value(basis_l, xb_l, status, c, m, n)
    sol = LPSolution(
        objective=objective,
        x=x[:bsz, :n],
        status=status,
        iterations=iters[:bsz, 0],
        basis=basis_l,
    )
    if not want_state:
        return sol
    binv_out, phase_out = outs[5:]
    out_state = revised.RevisedResumeState(
        binv=binv_out[:bsz, :m, :m],
        basis=basis_l,
        xb=xb_l,
        phase=phase_out[:bsz, 0],
    )
    return sol, out_state


@functools.partial(
    jax.jit,
    static_argnames=(
        "rule", "seed", "tol", "tile_b", "static_cap", "want_state",
        "interpret",
    ),
)
def _revised_solve_jit(
    a, b, c, basis0, cap, *,
    rule, seed, tol, tile_b, static_cap, want_state, interpret,
):
    state = revised.init_traced(a, b, basis0)
    return _revised_launch(
        a, b, c, state, cap,
        rule=rule, seed=seed, tol=tol, tile_b=tile_b,
        static_cap=static_cap, want_state=want_state, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "rule", "seed", "tol", "tile_b", "static_cap", "want_state",
        "interpret",
    ),
)
def _revised_resume_jit(
    a, b, c, state, cap, *,
    rule, seed, tol, tile_b, static_cap, want_state, interpret,
):
    return _revised_launch(
        a, b, c, state, cap,
        rule=rule, seed=seed, tol=tol, tile_b=tile_b,
        static_cap=static_cap, want_state=want_state, interpret=interpret,
    )


def revised_compile_cache_size() -> int:
    """Revised-kernel executables compiled so far (cold + resume paths)."""
    return (
        int(_revised_solve_jit._cache_size())
        + int(_revised_resume_jit._cache_size())
        + _split_cache_size((_revised_solve_jit, _revised_resume_jit))
    )


def revised_solve(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    rule: str = engine.LPC,
    max_iters: int = 0,
    seed: int = 0,
    tol: float = 0.0,
    tile_b: int | None = None,
    interpret: bool | None = None,
    basis0: jnp.ndarray | None = None,
    want_state: bool = False,
    dynamic_cap: bool = True,
):
    """Solve a shared-A batch with the VMEM-resident revised kernel.

    a: (m, n) stored ONCE, b: (B, m), c: (B, n); returns LPSolution like
    ``core/revised.py:solve_batched`` (the XLA driver) — same knobs,
    honored identically, since both drive ``revised.iteration_step``.
    ``basis0`` warm-starts via the same ``init_traced`` overlay the XLA
    path uses (factorization happens host-of-kernel; warm rows enter the
    kernel already in phase II).  ``tile_b=None`` sizes the tile from
    the VMEM budget net of the shared ``A`` block
    (:func:`revised_auto_tile_b`); ``max_iters`` is a traced kernel
    scalar under ``dynamic_cap`` so every cap over one shape shares one
    executable.
    """
    if interpret is None:
        interpret = not _on_tpu()
    m, n = a.shape
    split = _batch_split(b)
    bsz = b.shape[0] // _shards(split)
    if tile_b is None:
        tile_b = revised_auto_tile_b(bsz, m, n, a.dtype)
    tile_b = legal_tile_b(tile_b, bsz)
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(a.dtype)
    static_cap = None if dynamic_cap else int(cap)
    cap_arr = jnp.array([cap if dynamic_cap else 0, 0], jnp.int32)
    return _launch_split(
        _revised_solve_jit, split, cap_arr, (a,), (b, c, basis0),
        rule=rule, seed=seed, tol=tol, tile_b=tile_b,
        static_cap=static_cap, want_state=want_state, interpret=interpret,
    )


def revised_resume(
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    state: revised.RevisedResumeState,
    rule: str = engine.LPC,
    max_iters: int = 0,
    seed: int = 0,
    tol: float = 0.0,
    tile_b: int | None = None,
    interpret: bool | None = None,
    want_state: bool = True,
    dynamic_cap: bool = True,
):
    """Continue a shared-A batch from a carried ``RevisedResumeState``.

    Like the pdhg resume (and unlike the tableau one), ``a`` must be
    passed back in — the state deliberately does not replicate it.  The
    state round-trips through the same padding the cold launch uses, so
    capped rounds summing to ``K`` replay one uninterrupted cap-``K``
    kernel run bit-for-bit.
    """
    if interpret is None:
        interpret = not _on_tpu()
    m, n = a.shape
    split = _batch_split(b)
    bsz = b.shape[0] // _shards(split)
    if tile_b is None:
        tile_b = revised_auto_tile_b(bsz, m, n, a.dtype)
    tile_b = legal_tile_b(tile_b, bsz)
    cap = resolve_cap(max_iters, m, n)
    if tol <= 0.0:
        tol = engine.default_tolerance(a.dtype)
    static_cap = None if dynamic_cap else int(cap)
    cap_arr = jnp.array([cap if dynamic_cap else 0, 0], jnp.int32)
    return _launch_split(
        _revised_resume_jit, split, cap_arr, (a,), (b, c, state),
        rule=rule, seed=seed, tol=tol, tile_b=tile_b,
        static_cap=static_cap, want_state=want_state, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def hyperbox_support(
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    directions: jnp.ndarray,
    tile_b: int = 8192,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Box support values via the streaming Pallas kernel. (B, n) -> (B,).

    The kernel streams the batch along lanes (see ``hyperbox_pallas.py``):
    ``lo``/``hi``/``directions`` are broadcast to (B, n), transposed to
    (n, B) and zero-padded to 8 sublanes and to a whole number of tiles of
    ``tile_b`` directions (rounded to a multiple of 128, or the whole
    128-padded batch when that is smaller).
    """
    if interpret is None:
        interpret = not _on_tpu()
    bsz, n = directions.shape
    tile = min(_round_up(tile_b, 128), _round_up(bsz, 128))
    bp = _round_up(bsz, tile)
    np_pad = _round_up(n, 8)

    def pad_t(x):
        x = jnp.broadcast_to(x, directions.shape).T
        return jnp.zeros((np_pad, bp), x.dtype).at[:n, :bsz].set(x)

    out = hyperbox_pallas(
        pad_t(lo), pad_t(hi), pad_t(directions), tile_b=tile,
        vmem_limit_bytes=VMEM_BUDGET_BYTES, interpret=interpret,
    )
    return out[0, :bsz]

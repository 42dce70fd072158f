"""Round-scheduled, chunked, overlapped, mesh-aware dispatch of LP batches.

This is the substrate under every front-end path (paper Sec. 4).  All of
it is organized as ONE round-scheduler: a solve is a *round plan* — a
short list of per-round iteration caps — executed by a single
gather/dispatch/scatter loop (:func:`solve_canonical`).  Round 0 always
dispatches the full batch; each later round gathers the LPs that hit the
previous round's cap (``ITER_LIMIT``) into a dense sub-batch,
re-dispatches only those, and scatters the results back in input order.

The four historical execution paths are now just round plans
(:func:`_round_plan`):

  * plain chunked solving            -> one round at the full cap;
  * legacy adaptive two-pass
    (``SolveOptions.first_cap``)     -> rounds ``[first_cap, full]`` with
    iteration counts carried across rounds (the historical semantics);
  * ``compaction="chunked"``         -> rounds ``[k, full]``;
  * ``compaction="every_k"``         -> geometric rounds
    ``[k, 2k, 4k, ..., full]``.

The compaction modes come in two resume flavors
(``SolveOptions.resume``): ``"scratch"`` re-solves survivors from
iteration 0 each round (each cap is a from-scratch cap), while
``"basis"`` CONTINUES survivors from the exact solver state the previous
round stopped at (each cap is an *incremental* step budget; the budgets
sum to one full solve), carried as :class:`~repro.core.lp.ResumeState`
through the backend state protocol.  Both are bit-identical to
``compaction="off"`` under the deterministic pivot rules.

Compile-once discipline, end to end:

  * iteration caps are traced scalars inside every backend
    (``SolveOptions.dynamic_caps``), so the geometric caps ``[k, 2k,
    4k, ...]`` all hit ONE executable per tableau shape;
  * every gathered sub-batch after round 0 is rounded up to a power-of-two
    size class (``core/bucketing.py:next_pow2``), so round r reuses round
    r-1's compiled executable instead of minting one per active-set size;
  * the status read-back is the single host sync per round;
  * ``SolveStats.compiles`` / ``cache_hits`` observe the contract through
    the backends' compile-cache hooks.

Each round goes through the one dispatch primitive
(:func:`dispatch_round`), which owns — exactly once — the paper's
per-round machinery:

  * split the (sub-)batch into device-sized chunks (the paper's
    global-memory capacity bound, eq. 5; here ``SolveOptions.chunk_size``);
  * overlap host->device staging of chunk k+1 with the solve of chunk k
    (the paper's CUDA streams; here: JAX async dispatch + early device_put);
  * shard the batch dimension across a mesh's data axes when a mesh is
    supplied (one LP never spans devices — same invariant as one LP per
    CUDA block);
  * pad the batch (and any carried resume state) to the round's size
    class and the mesh multiple, trimming the padding replicas off every
    result;
  * thread warm-start bases (``LPBatch.basis0``) through gather/stage;
  * record ``SolveStats`` counters per dispatch.

The actual per-chunk solve is delegated to the registered backend
(core/backends.py); empty batches short-circuit to an empty solution.

Robustness layer (PR 9): every scheduler round goes through
:func:`dispatch_round_safe`, which retries a transiently-failed round
from its carried ``ResumeState`` — on the routed fallback backend
(:func:`repro.core.backends.fault_fallback`), with capped exponential
backoff — so healthy LPs recover bit-identically with zero new compiles;
:func:`apply_guardrails` retires rows whose solution or carried state
went non-finite with the ``NUMERICAL`` status at the existing per-round
status read-back, and the opt-in quarantine lane
(``SolveOptions.quarantine``) re-solves flagged rows on the float64
oracle.  Fault injection for all of it lives in ``runtime/chaos.py``.
"""

from __future__ import annotations

import math
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import chaos as _chaos
from ..runtime import trace as _trace
from . import pdhg as _pdhg
from . import revised as _revised
from .backends import (
    SHARED_BACKENDS,
    Backend,
    SolveOptions,
    SolveStats,
    fault_fallback,
    get_backend,
    read_back,
    route_shape,
)
from .bucketing import next_pow2
from .engine import LPC
from .lp import (
    ITER_LIMIT,
    NUMERICAL,
    OPTIMAL,
    LPBatch,
    LPSolution,
    ResumeState,
    SharedLPBatch,
    auto_cap,
)
from .tableau import TableauSpec

#: Ceiling on the fault-recovery backoff sleep (seconds): retry k of a
#: round sleeps ``min(retry_backoff * 2**k, RETRY_BACKOFF_CAP)``.
RETRY_BACKOFF_CAP = 1.0


def empty_solution(n: int, dtype=jnp.float32) -> LPSolution:
    """The solution of a zero-LP batch (shape-correct, no device work).

    Parameters
    ----------
    n : int
        Number of variables (fixes the width of the empty primal block).
    dtype : jnp dtype, default float32
        Dtype of the objective/primal arrays.

    Returns
    -------
    LPSolution
        All result arrays with batch dimension 0.
    """
    return LPSolution(
        objective=jnp.zeros((0,), dtype),
        x=jnp.zeros((0, n), dtype),
        status=jnp.zeros((0,), jnp.int32),
        iterations=jnp.zeros((0,), jnp.int32),
    )


def _trim_solution(sol: LPSolution, k: int) -> LPSolution:
    """First k rows of a solution batch (drop padding replicas)."""
    return LPSolution(
        objective=sol.objective[:k],
        x=sol.x[:k],
        status=sol.status[:k],
        iterations=sol.iterations[:k],
        basis=None if sol.basis is None else sol.basis[:k],
        y=None if sol.y is None else sol.y[:k],
        phase_rewrites=None if sol.phase_rewrites is None else sol.phase_rewrites[:k],
    )


def _concat_solutions(parts: Sequence[LPSolution]) -> LPSolution:
    bases = [p.basis for p in parts]
    ys = [p.y for p in parts]
    with _trace.span("dispatch.concat"):
        return LPSolution(
            objective=jnp.concatenate([p.objective for p in parts]),
            x=jnp.concatenate([p.x for p in parts]),
            status=jnp.concatenate([p.status for p in parts]),
            iterations=jnp.concatenate([p.iterations for p in parts]),
            basis=jnp.concatenate(bases) if all(b is not None for b in bases) else None,
            y=jnp.concatenate(ys) if all(y is not None for y in ys) else None,
        )


def _concat_states(parts: Sequence):
    # Any resume-state flavor (simplex ResumeState, PDHGResumeState, a
    # plug-in backend's record): both are registered dataclass pytrees,
    # so leaf-wise concatenation rebuilds the same record type.
    with _trace.span("dispatch.concat"):
        return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *parts)


def _resolve_axes(
    mesh: Optional[jax.sharding.Mesh], batch_axes: Sequence[str]
) -> Tuple[str, ...]:
    return tuple(ax for ax in batch_axes if mesh and ax in mesh.axis_names)


def _batch_sharding(mesh, axes, ndim: int):
    if not mesh or not axes:
        return None
    spec = [None] * ndim
    spec[0] = axes if len(axes) > 1 else axes[0]
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec))


def _stage(arr: jnp.ndarray, mesh, axes) -> jnp.ndarray:
    sh = _batch_sharding(mesh, axes, arr.ndim)
    if sh is None:
        return jax.device_put(arr)
    return jax.device_put(arr, sh)


def _stage_batch(batch, lo: int, hi: int, mesh, axes):
    if isinstance(batch, SharedLPBatch):
        # The shared A has no batch dimension — staged whole (replicated
        # on every device of the mesh, not sharded) while the per-LP c/b
        # rows slice and shard as usual.
        whole = (
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            if mesh and axes
            else None
        )
        return SharedLPBatch(
            jax.device_put(batch.a, whole),
            _stage(batch.b[lo:hi], mesh, axes),
            _stage(batch.c[lo:hi], mesh, axes),
            None
            if batch.basis0 is None
            else _stage(batch.basis0[lo:hi], mesh, axes),
        )
    return LPBatch(
        _stage(batch.a[lo:hi], mesh, axes),
        _stage(batch.b[lo:hi], mesh, axes),
        _stage(batch.c[lo:hi], mesh, axes),
        None if batch.basis0 is None else _stage(batch.basis0[lo:hi], mesh, axes),
    )


def _stage_state(state, lo: int, hi: int, mesh, axes):
    return jax.tree_util.tree_map(
        lambda v: _stage(v[lo:hi], mesh, axes), state
    )


def _gather_batch(batch, idx: jnp.ndarray):
    if isinstance(batch, SharedLPBatch):
        return batch.take(idx)  # A is row-invariant: gather only c/b/basis0
    return LPBatch(
        batch.a[idx],
        batch.b[idx],
        batch.c[idx],
        None if batch.basis0 is None else batch.basis0[idx],
    )


def _scatter_solution(
    full: LPSolution,
    idx: jnp.ndarray,
    part: LPSolution,
    iter_offset: int = 0,
    accumulate: bool = False,
) -> LPSolution:
    """Overwrite rows ``idx`` of ``full`` with ``part`` (compaction scatter).

    ``accumulate`` adds the part's iteration counts onto the rows' prior
    totals instead of replacing them — resumed rounds report only their
    own incremental pivots, and the sum over rounds is the true per-LP
    count (bit-identical to an uninterrupted solve's).
    """
    basis = full.basis
    if basis is not None and part.basis is not None:
        basis = basis.at[idx].set(part.basis)
    elif part.basis is not None:
        basis = None  # mixed provenance: drop rather than fabricate
    y = full.y
    if y is not None and part.y is not None:
        y = y.at[idx].set(part.y)
    elif part.y is not None:
        y = None  # mixed provenance: drop rather than fabricate
    if accumulate:
        iterations = full.iterations.at[idx].add(part.iterations)
    else:
        iterations = full.iterations.at[idx].set(part.iterations + iter_offset)
    return LPSolution(
        objective=full.objective.at[idx].set(part.objective),
        x=full.x.at[idx].set(part.x),
        status=full.status.at[idx].set(part.status),
        iterations=iterations,
        basis=basis,
        y=y,
    )


def _pad_rows(x: jnp.ndarray, pad: int) -> jnp.ndarray:
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, mode="edge")


def _pad_batch_to(batch, size: int) -> Tuple[object, int]:
    """Edge-pad the batch dimension up to ``size`` (replica rows, trimmed
    off every output)."""
    bsz = batch.batch
    if size <= bsz:
        return batch, bsz
    pad = size - bsz
    if isinstance(batch, SharedLPBatch):
        return SharedLPBatch(
            batch.a,  # no batch dimension to pad
            _pad_rows(batch.b, pad),
            _pad_rows(batch.c, pad),
            None if batch.basis0 is None else _pad_rows(batch.basis0, pad),
        ), bsz
    return LPBatch(
        _pad_rows(batch.a, pad),
        _pad_rows(batch.b, pad),
        _pad_rows(batch.c, pad),
        None if batch.basis0 is None else _pad_rows(batch.basis0, pad),
    ), bsz


def _pad_state_to(state, size: int):
    pad = size - state.batch
    if pad <= 0:
        return state
    return jax.tree_util.tree_map(lambda v: _pad_rows(v, pad), state)


def _full_cap(
    batch: LPBatch, options: SolveOptions, backend: Optional[Backend] = None
) -> int:
    """The effective iteration cap — the backend's 0 -> auto rule.

    The auto rule comes from the backend's ``auto_cap`` hook when it has
    one (the first-order ``pdhg`` backend budgets ~40 (m + n) cheap
    steps) and the library-wide simplex rule ``50 (m + n)`` otherwise;
    the round scheduler and a plain solve MUST agree on it, which is
    what keeps compaction results identical to ``compaction="off"``.
    """
    if options.max_iters > 0:
        return options.max_iters
    cap_fn = (backend.auto_cap if backend is not None else None) or auto_cap
    return cap_fn(batch.m, batch.n)


def _round_cap(
    batch: LPBatch, options: SolveOptions, backend: Optional[Backend] = None
) -> int:
    """Per-round compaction budget (``compact_every``, 0 -> auto 8*(m+n))."""
    k = options.compact_every if options.compact_every > 0 else 8 * (batch.m + batch.n)
    return min(k, _full_cap(batch, options, backend))


def _round_plan(
    batch: LPBatch,
    options: SolveOptions,
    incremental: bool = False,
    backend: Optional[Backend] = None,
) -> Tuple[Sequence[int], bool]:
    """Lower ``options`` to a round plan: per-round iteration caps.

    Returns ``(caps, carry_iters)``.  Round 0 dispatches the whole batch
    with ``caps[0]``; round r > 0 re-dispatches the LPs that hit round
    r-1's cap, with ``caps[r]``.

    With ``incremental`` False (scratch resume) each cap is a
    from-scratch cap: the compaction modes re-solve survivors from
    iteration 0, so any LP's final result comes from one uninterrupted
    solve (the bit-identical-to-``"off"`` argument).  With ``incremental``
    True (basis resume) each cap is the round's ADDITIONAL step budget
    and the budgets sum exactly to the full cap — the cumulative budget
    after round r matches the scratch plan's cap for round r, and the
    exact carried state makes the spliced rounds replay one uninterrupted
    solve arithmetic-for-arithmetic.

    ``carry_iters`` is True only for the legacy adaptive two-pass, whose
    historical contract *continues* counting iterations across rounds.
    """
    full_cap = _full_cap(batch, options, backend)
    if options.compaction == "chunked":
        cap = _round_cap(batch, options, backend)
        if cap >= full_cap:
            return [cap], False
        return ([cap, full_cap - cap] if incremental else [cap, full_cap]), False
    if options.compaction == "every_k":
        cap = _round_cap(batch, options, backend)
        caps = [cap]
        cum = cap
        while cum < full_cap:
            inc = min(cum, full_cap - cum)  # doubling cumulative budget
            caps.append(inc if incremental else cum + inc)
            cum += inc
        return caps, False
    if options.first_cap is not None:
        first = options.first_cap or 8 * (batch.m + batch.n)
        return [first, full_cap], True
    return [full_cap], False


def resolve_backend(
    m: int,
    n: int,
    dtype,
    options: SolveOptions,
    shared: bool = False,
    batch: Optional[int] = None,
    stats: Optional[SolveStats] = None,
) -> SolveOptions:
    """Resolve the open config knobs to concrete values for one shape.

    The single implementation shared by :func:`solve_canonical` (which
    resolves ONCE up front, so every round, chunk, and resume of a solve
    runs the same backend — mixing drivers mid-solve would break the
    resume-state contract) and the continuous-batching serve loop (which
    resolves once per shape class at admission, for the same reason).

    With ``options.autotune`` active (the default ``"predict"``), the
    cost-model autotuner (``runtime/autotune.py``) fills EVERY open knob
    — ``backend="auto"``, ``layout=None``, ``tile_b=None`` — and records
    the decision into ``stats`` (``SolveStats.autotuned`` /
    ``autotune_log``); ``batch`` keys the decision's shape class.  With
    ``autotune="off"`` only ``backend="auto"`` is resolved, through the
    static routing table, and concrete backends pass through unchanged.
    Either way explicit pins always survive, and a shape routed to
    ``pdhg`` resets ``rule``/``layout`` to their defaults: those knobs
    configure the simplex leg and are rejected by validation on the
    first-order side.

    ``shared=True`` resolves for a :class:`~repro.core.lp.SharedLPBatch`:
    ``"auto"`` routes through the shared leg of the table and the
    tableau simplex names promote to their shared counterparts
    (``"xla"`` -> ``"xla-shared"``, ``"pallas"`` -> ``"pallas-shared"``)
    — the caller asked for a simplex driver and the revised engine IS
    the simplex driver for this container.  ``pdhg``/``reference``
    pass through (the caller densifies for them).

    The call is one ``dispatch.resolve`` span, autotuning included; its
    ``backend`` attribute is the resolved backend.
    """
    with _trace.span("dispatch.resolve") as sp:
        options = _resolve(m, n, dtype, options, shared, batch, stats)
        sp.set(backend=options.backend)
    return options


def _resolve(m, n, dtype, options, shared, batch, stats) -> SolveOptions:
    """:func:`resolve_backend` without its span."""
    name = options.backend
    if shared:
        if name == "xla":
            options = options.replace(backend="xla-shared")
        elif name == "pallas":
            options = options.replace(backend="pallas-shared")
    if options.autotune != "off":
        from ..runtime import autotune as _autotune

        return _autotune.resolve(
            m, n, dtype, options, shared=shared, batch=batch, stats=stats
        )
    if shared:
        if options.backend == "auto":
            return options.replace(
                backend=route_shape(m, n, dtype, options, shared=True)
            )
        return options
    if options.backend != "auto":
        return options
    resolved = route_shape(m, n, dtype, options)
    if resolved == "pdhg":
        return options.replace(backend=resolved, rule=LPC, layout=None)
    return options.replace(backend=resolved)


def admission_order(
    requests: Sequence[Tuple[int, Optional[float], int, int]],
    now: int = 0,
    starvation_rounds: int = 8,
) -> list:
    """Admission order for the serve loop: EDF with a starvation bound.

    The round planner's answer to "which pending requests join the next
    dispatch round first".  Each request is a tuple ``(ticket, deadline,
    priority, submitted_round)``: ``deadline`` is an absolute time (any
    monotone clock; None = no deadline, sorts last), larger ``priority``
    wins among equal deadlines, and ``submitted_round`` is the scheduler
    round the request arrived in.

    Ordering: requests that have waited at least ``starvation_rounds``
    scheduler rounds are *aged* and outrank every non-aged request,
    draining FIFO among themselves — so under an adversarial stream of
    ever-earlier deadlines, a request waits at most ``starvation_rounds``
    rounds before it precedes all later arrivals (the starvation bound:
    with per-round admission capacity ``c >= 1``, it is admitted within
    ``starvation_rounds + ceil(older_pending / c)`` rounds of submission).
    Non-aged requests order by earliest deadline first, then descending
    priority, then ticket (FIFO tie-break).

    Returns the indices into ``requests`` in admission order.
    """

    def key(i):
        ticket, deadline, priority, submitted = requests[i]
        aged = (now - submitted) >= starvation_rounds
        deadline = math.inf if deadline is None else float(deadline)
        return (
            0 if aged else 1,
            submitted if aged else 0,
            deadline,
            -priority,
            ticket,
        )

    return sorted(range(len(requests)), key=key)


def _finite_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Per-row all-finite mask over the trailing axes: ``(B, ...) -> (B,)``."""
    return jnp.all(jnp.isfinite(x.reshape(x.shape[0], -1)), axis=-1)


def state_health(state) -> Optional[jnp.ndarray]:
    """Per-row finite-ness of a carried resume state (device-side, lazy).

    Reduces every floating leaf of the state pytree — the tableau rows of
    a simplex :class:`~repro.core.lp.ResumeState`, ``x_B``/``B^-1`` of
    the revised record, iterates/residual accumulators of the PDHG one —
    to one ``(B,)`` bool mask.  Returns None for a state with no floating
    leaves (nothing to check).
    """
    ok = None
    for leaf in jax.tree_util.tree_leaves(state):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        f = _finite_rows(leaf)
        ok = f if ok is None else ok & f
    return ok


def apply_guardrails(sol: LPSolution, state=None) -> LPSolution:
    """Retire non-finite rows with the ``NUMERICAL`` status.

    The per-round numerical health mask (``SolveOptions.guardrails``).
    A row is flagged when

    * it claims ``OPTIMAL`` but its objective or primal point is not
      finite (a poisoned certificate — the one thing that must never
      escape), or
    * its carried resume state has any non-finite value (``state`` row-
      aligned with ``sol``), so every later round would iterate on
      garbage.

    The scoping matters: non-``OPTIMAL`` rows legitimately carry ±inf
    objectives (``extract_solution`` fills them), so the solution-side
    check applies to ``OPTIMAL`` rows only — honest
    UNBOUNDED/INFEASIBLE/ITER_LIMIT verdicts pass through untouched.
    Flagged rows report status ``NUMERICAL``, objective NaN, and a zero
    primal point.  On a healthy batch the ``where``-selects are row-wise
    identities, so results are bit-identical with the guardrails on or
    off.  The whole mask is one jitted call (cached per shape class like
    the round executables themselves), so the clean-path cost is a
    single fused kernel per round, not a chain of eager dispatches.
    """
    return _apply_guardrails_jit(sol, state)


@jax.jit
def _apply_guardrails_jit(sol: LPSolution, state) -> LPSolution:
    bad = (sol.status == OPTIMAL) & ~(
        jnp.isfinite(sol.objective) & _finite_rows(sol.x)
    )
    if state is not None:
        healthy = state_health(state)
        if healthy is not None:
            bad = bad | ~healthy
    status = jnp.where(bad, jnp.int32(NUMERICAL), sol.status)
    objective = jnp.where(bad, jnp.nan, sol.objective)
    x = jnp.where(bad[:, None], jnp.zeros_like(sol.x), sol.x)
    return LPSolution(
        objective=objective,
        x=x,
        status=status,
        iterations=sol.iterations,
        basis=sol.basis,
        y=sol.y,
    )


def dispatch_round_safe(
    batch: LPBatch,
    options: SolveOptions,
    mesh,
    batch_axes: Sequence[str],
    stats: Optional[SolveStats] = None,
    state: Optional[ResumeState] = None,
    want_state: bool = False,
    size_class: Optional[int] = None,
) -> Tuple[LPSolution, Optional[ResumeState]]:
    """:func:`dispatch_round` with retry-from-``ResumeState`` recovery.

    ``dispatch_round`` is functional — its ``batch``/``state`` arguments
    are never mutated — so on a transient failure (an injected
    :class:`~repro.runtime.chaos.ChaosError`, a device runtime error)
    the SAME round simply re-dispatches from the same carried state: the
    exact-resume protocol makes the retry bit-identical to an
    uninterrupted round, and the pow-2 ``size_class`` means it lands on
    an already-compiled executable.  Retries route through
    :func:`repro.core.backends.fault_fallback` — ``pallas`` retries on
    its bit-identical ``xla`` twin (warn-once), twin-less backends retry
    in place — with capped exponential backoff
    (``options.retry_backoff``, ceiling :data:`RETRY_BACKOFF_CAP`).
    After ``options.retry_budget`` failed retries, or on a non-transient
    error (:func:`repro.runtime.chaos.is_transient`: bad arguments, and
    kernel lowering or compile failures, which no retry or twin may
    hide), the exception propagates.

    The clean path is one ``try`` — no extra dispatches, no syncs.
    Note ``SolveStats`` counters recorded by an aborted attempt's
    completed chunks are not rolled back (stats are diagnostics; results
    are unaffected).
    """
    budget = options.retry_budget
    opts = options
    for attempt in range(budget + 1):
        try:
            return dispatch_round(
                batch,
                opts,
                mesh,
                batch_axes,
                stats,
                state=state,
                want_state=want_state,
                size_class=size_class,
            )
        except Exception as exc:
            if attempt >= budget or not _chaos.is_transient(exc):
                raise
            if stats is not None:
                stats.retries += 1
                if isinstance(exc, _chaos.ChaosError):
                    stats.faults_injected += 1
            target = fault_fallback(opts.backend)
            if target != opts.backend:
                opts = opts.replace(backend=target)
            delay = min(
                opts.retry_backoff * (2**attempt), RETRY_BACKOFF_CAP
            )
            if delay > 0:
                time.sleep(delay)
    raise AssertionError("unreachable")  # pragma: no cover


def _quarantine_resolve(
    batch,
    sol: LPSolution,
    options: SolveOptions,
    stats: Optional[SolveStats] = None,
) -> LPSolution:
    """Re-solve guardrail-flagged rows on the float64 oracle (opt-in).

    The recovery lane behind ``SolveOptions.quarantine``, reusing the
    pdhg certificate-confirmation pattern
    (``core/pdhg.py:confirm_certificates``): gather the ``NUMERICAL``
    rows host-side, drop any whose INPUTS are non-finite (garbage in —
    no verdict possible), and run the survivors through the sequential
    float64 oracle under the same ``max(400, 2 (m + n))`` pivot budget.
    Rows where the oracle reaches a certificate
    (OPTIMAL/UNBOUNDED/INFEASIBLE) take the oracle's verdict; rows it
    cannot finish stay ``NUMERICAL`` — a wrong certificate is never
    fabricated.
    """
    status = read_back(sol.status, "quarantine", stats)
    flagged = np.nonzero(status == NUMERICAL)[0]
    if flagged.size == 0:
        return sol
    from . import oracle as _oracle

    sub = _gather_batch(batch, jnp.asarray(flagged))
    if isinstance(sub, SharedLPBatch):
        sub = sub.densify()
    with _trace.span("dispatch.sync", site="quarantine.inputs"):
        a = np.asarray(sub.a, np.float64)
        b = np.asarray(sub.b, np.float64)
        c = np.asarray(sub.c, np.float64)
    if stats is not None:
        stats.host_syncs += 1
    finite = (
        np.isfinite(a).all(axis=(1, 2))
        & np.isfinite(b).all(axis=1)
        & np.isfinite(c).all(axis=1)
    )
    keep = np.nonzero(finite)[0]
    if keep.size == 0:
        return sol
    budget = max(400, 2 * (batch.m + batch.n))
    obj, xs, ostatus, iters = _oracle.solve_batch(
        a[keep], b[keep], c[keep], max_iters=budget
    )
    if stats is not None:
        stats.quarantined += int(keep.size)
    confirmed = np.nonzero(ostatus != ITER_LIMIT)[0]
    if confirmed.size == 0:
        return sol
    rows = flagged[keep[confirmed]]
    part = LPSolution(
        objective=jnp.asarray(obj[confirmed], sol.objective.dtype),
        x=jnp.asarray(xs[confirmed], sol.x.dtype),
        status=jnp.asarray(ostatus[confirmed], jnp.int32),
        iterations=jnp.asarray(iters[confirmed], jnp.int32),
    )
    return _scatter_solution(sol, jnp.asarray(rows), part)


def solve_canonical(
    batch: LPBatch,
    options: Optional[SolveOptions] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axes: Sequence[str] = ("data",),
    stats: Optional[SolveStats] = None,
) -> LPSolution:
    """Solve a canonical batch: one round-scheduler over dispatch rounds.

    The configured mode — plain chunked solve, legacy adaptive two-pass
    (``options.first_cap``), or convergence compaction
    (``options.compaction``, scratch or basis-resumed per
    ``options.resume``) — is lowered by :func:`_round_plan` to a list of
    per-round iteration caps, then executed by the single
    gather/dispatch/scatter loop below.  Round 0 dispatches every LP;
    each later round reads the status vector on the host (the one host
    sync per round), gathers the LPs that hit the previous cap
    (``ITER_LIMIT``) into a dense sub-batch padded up to a power-of-two
    size class, re-dispatches only those — continuing their carried
    solver state in basis-resume mode — and scatters the results back in
    input order.  One plain round at the full cap never examines the
    status vector at all (no host sync).

    Parameters
    ----------
    batch : LPBatch or SharedLPBatch
        Canonical problems (``max c.x, Ax <= b, x >= 0``), optionally
        carrying a warm-start basis in ``batch.basis0``.  A
        :class:`~repro.core.lp.SharedLPBatch` (one A, batched c/b) runs
        on the shared revised-simplex backends; an explicit non-shared
        backend densifies it first.
    options : SolveOptions, optional
        Pipeline + backend configuration; defaults to ``SolveOptions()``.
        ``options.compaction`` selects the convergence-compaction mode
        and ``options.resume`` its scratch/continue flavor (see
        :class:`repro.core.backends.SolveOptions`); compaction takes
        precedence over the legacy ``options.first_cap`` two-pass solve.
        ``options.backend="auto"`` resolves to a concrete backend here,
        once per solve, through the shape-routing table
        (:func:`repro.core.backends.route_shape`); with the ``pdhg``
        backend, ``options.crossover`` polishes the final solution's
        OPTIMAL rows into exact simplex vertices as a post-pass.
    mesh : jax.sharding.Mesh, optional
        When given, the batch dimension is sharded across the mesh axes
        named in ``batch_axes``.
    batch_axes : sequence of str, default ("data",)
        Mesh axis names eligible to shard the batch dimension.
    stats : SolveStats, optional
        Counters to accumulate per-dispatch iteration totals into
        (opt-in; forces a host sync per dispatch).

    Returns
    -------
    LPSolution
        One result row per input LP, in input order.  ``basis`` carries
        the final simplex basis when the backend reports one.
    """
    options = options or SolveOptions()
    if batch.batch == 0:
        return empty_solution(batch.n, batch.a.dtype)
    shared = isinstance(batch, SharedLPBatch)
    options = resolve_backend(
        batch.m, batch.n, batch.a.dtype, options, shared=shared,
        batch=batch.batch, stats=stats,
    )
    if shared and options.backend not in SHARED_BACKENDS:
        # An explicit non-shared backend (pdhg, reference, a plug-in) on a
        # shared batch: honor the request by densifying — correctness
        # over the memory win, and the caller said so by name.
        batch = batch.densify()
    elif not shared and options.backend in SHARED_BACKENDS:
        raise ValueError(
            f"backend {options.backend!r} consumes SharedLPBatch (one A, "
            "batched c/b); this batch carries a per-LP constraint matrix "
            "— solve it on a tableau backend, or build a SharedLPBatch"
        )
    backend = get_backend(options.backend)
    # unroll > 1 groups loop steps in blocks of `unroll`; a mid-round
    # split would re-align the grouping and change the total step count,
    # so basis-resume falls back to scratch rounds there.
    use_resume = (
        options.resume == "basis"
        and options.compaction != "off"
        and options.unroll <= 1
        and backend.supports_resume
    )
    caps, carry_iters = _round_plan(
        batch, options, incremental=use_resume, backend=backend
    )
    base = options.replace(compaction="off", first_cap=None, resume="scratch")

    sol: Optional[LPSolution] = None
    state: Optional[ResumeState] = None
    state_idx: Optional[np.ndarray] = None  # global rows held in `state`
    iter_offset = 0
    for r, cap in enumerate(caps):
        want_state = use_resume and r < len(caps) - 1
        if sol is None:
            idx = None  # round 0: the whole batch
            sub = batch
            sub_state = None
            size_class = None
        else:
            status = read_back(sol.status, "round_status", stats)
            active = np.nonzero(status == ITER_LIMIT)[0]
            if active.size == 0:
                break
            idx = jnp.asarray(active)
            sub = _gather_batch(batch, idx)
            if state is not None:
                # Survivors are a subset of the rows the previous round
                # dispatched, so their state rows are found by position.
                local = active if state_idx is None else np.searchsorted(
                    state_idx, active
                )
                sub_state = state.take(jnp.asarray(local))
            else:
                sub_state = None
            size_class = next_pow2(int(active.size))
        with _trace.span("dispatch.round", round=r, rows=sub.batch, cap=cap):
            part, part_state = dispatch_round_safe(
                sub,
                base.replace(max_iters=cap),
                mesh,
                batch_axes,
                stats,
                state=sub_state,
                want_state=want_state,
                size_class=size_class,
            )
        if options.guardrails:
            # Checked at the existing one-host-sync-per-round status
            # read-back below: a poisoned row retires NUMERICAL here and
            # leaves the active set instead of iterating on garbage.
            part = apply_guardrails(part, part_state)
        if stats is not None and sub_state is not None:
            stats.resumed += sub.batch
        if idx is None:
            sol = part
        else:
            sol = _scatter_solution(
                sol, idx, part, iter_offset=iter_offset, accumulate=use_resume
            )
            state_idx = active
        state = part_state
        if carry_iters:
            iter_offset += cap
    if options.backend == "pdhg":
        # Both pdhg post-passes run on the FINAL merged solution (not per
        # round): each row is confirmed/polished exactly once, from the
        # same terminal point regardless of how the rounds were sliced,
        # so compaction modes stay results-identical to "off".
        # Confirmation first — it may revoke a heuristic divergence flag
        # (-> ITER_LIMIT), and crossover must only polish real optima.
        sol = _pdhg.confirm_certificates(batch, sol, options)
        if options.crossover:
            sol = _pdhg.crossover(batch, sol, options)
    if options.quarantine:
        # Last: the lane only touches NUMERICAL rows, which neither pdhg
        # post-pass reads (confirmation gathers divergence flags,
        # crossover polishes OPTIMAL rows).
        sol = _quarantine_resolve(batch, sol, options, stats)
    return sol


def dispatch_round(
    batch: LPBatch,
    options: SolveOptions,
    mesh,
    batch_axes: Sequence[str],
    stats: Optional[SolveStats] = None,
    state: Optional[ResumeState] = None,
    want_state: bool = False,
    size_class: Optional[int] = None,
) -> Tuple[LPSolution, Optional[ResumeState]]:
    """One dispatch round: pad, shard, chunk, overlap, solve, trim, record.

    The only place in the pipeline that talks to a backend.  Splits the
    (sub-)batch into ``options.chunk_size`` chunks and stages chunk k+1
    to the device while chunk k solves — the paper's CUDA-streams
    discipline (Sec. 4.4).  ``size_class`` rounds the batch up to the
    scheduler's power-of-two class (executable reuse across rounds);
    ``state``/``want_state`` thread the exact-resume protocol.  Padding
    replica rows are trimmed off the solution, the carried state, AND the
    stats before anything leaves this function.

    Callers: the round scheduler above (:func:`solve_canonical`) and the
    continuous-batching serve loop (``serve/engine.py`` via
    ``SolveSession.resume_round``), which drives one capped round per
    scheduler step over each shape class's spliced in-flight batch.
    ``options.max_iters`` must already be the round's concrete budget
    (``options.backend`` concrete, not ``"auto"``).

    Fault injection (``runtime/chaos.py``): an active
    :class:`~repro.runtime.chaos.ChaosMonkey` is consulted before the
    round (delay / backend exception), before each chunk (shard crash),
    and on the outgoing carried state (NaN poisoning) — the hooks the
    recovery wrapper (:func:`dispatch_round_safe`) and the guardrails
    are tested against.  With ``options.speculation`` a multi-chunk
    unsharded round dispatches its chunks through
    ``runtime/straggler.py:run_with_speculation`` instead of the serial
    staging loop.
    """
    monkey = _chaos.active()
    chaos_round = (
        monkey.on_round(options.backend) if monkey is not None else None
    )
    axes = _resolve_axes(mesh, batch_axes)
    mesh_div = 1
    if mesh and axes:
        mesh_div = int(np.prod([mesh.shape[a] for a in axes]))
    target = max(batch.batch, size_class or 0)
    target = math.ceil(target / max(mesh_div, 1)) * max(mesh_div, 1)
    batch, true_bsz = _pad_batch_to(batch, target)
    if state is not None:
        state = _pad_state_to(state, target)

    backend = get_backend(options.backend)

    bsz = batch.batch
    chunk = options.chunk_size or bsz
    chunk = max(mesh_div, (chunk // mesh_div) * mesh_div)
    if stats is not None:
        # Peak LOGICAL solver footprint of this round: the largest chunk
        # dispatched (batch-padding replica rows count — they occupy real
        # storage) at the backend's unpadded bytes/LP — the tableau for
        # the simplex backends, problem data + iterate vectors for the
        # first-order pdhg backend (no tableau exists there at all).
        # Backend-internal padding is NOT included: exact for the xla
        # drivers' logical arrays; Pallas lane/sublane padding sits on
        # top of this number.
        if backend.name == "pdhg":
            per_lp = _pdhg.state_bytes_per_lp(batch.m, batch.n, batch.a.dtype)
        elif backend.name in SHARED_BACKENDS:
            per_lp = _revised.state_bytes_per_lp(
                batch.m, batch.n, batch.a.dtype
            )
        else:
            spec = TableauSpec(batch.m, batch.n, options.effective_layout)
            per_lp = spec.bytes_per_lp(batch.a.dtype)
        stats.record_tableau(min(chunk, bsz) * per_lp)
    if options.speculation and not axes and bsz > chunk:
        parts, state_parts = _speculative_chunks(
            batch, state, options, backend, want_state, stats,
            chunk, bsz, true_bsz, monkey, chaos_round,
        )
    else:
        parts = []
        state_parts = []
        # Stage chunk 0, then for each chunk: kick off the solve (async
        # under XLA) and immediately stage chunk k+1 so transfer overlaps
        # compute — the CUDA-streams discipline from paper Sec. 4.4.
        staged = None
        for k, lo in enumerate(range(0, bsz, chunk)):
            if monkey is not None:
                monkey.on_chunk(chaos_round, k)
            hi = min(lo + chunk, bsz)
            cur = staged or _stage_round_inputs(
                batch, state, lo, hi, mesh, axes, k, stats
            )
            out, out_state = _solve_chunk(backend, cur, options, want_state, stats, k)
            nxt_lo, nxt_hi = hi, min(hi + chunk, bsz)
            staged = (
                _stage_round_inputs(
                    batch, state, nxt_lo, nxt_hi, mesh, axes, k + 1, stats
                )
                if nxt_lo < bsz
                else None
            )
            if stats is not None:
                # Don't let padding replica rows (edge-mode duplicates in
                # the trailing chunk) inflate the counters.
                valid = min(hi, true_bsz) - lo
                if valid > 0:
                    stats.record(out if valid == hi - lo else _trim_solution(out, valid))
            parts.append(out)
            if out_state is not None:
                state_parts.append(out_state)
    sol = parts[0] if len(parts) == 1 else _concat_solutions(parts)
    if want_state:
        out_state = (
            state_parts[0] if len(state_parts) == 1 else _concat_states(state_parts)
        )
    else:
        out_state = None
    if true_bsz != bsz:
        sol = _trim_solution(sol, true_bsz)
        if out_state is not None:
            out_state = out_state.take(slice(None, true_bsz))
    if monkey is not None and out_state is not None:
        # NaN-poison scheduled rows of the OUTGOING carried state — the
        # corruption the next guardrail check must catch.
        out_state, poisoned = monkey.poison_state(chaos_round, out_state)
        if poisoned and stats is not None:
            stats.faults_injected += poisoned
    return sol, out_state


def _speculative_chunks(
    batch,
    state,
    options: SolveOptions,
    backend: Backend,
    want_state: bool,
    stats: Optional[SolveStats],
    chunk: int,
    bsz: int,
    true_bsz: int,
    monkey,
    chaos_round,
):
    """Straggler-mitigated chunk dispatch (``SolveOptions.speculation``).

    Each chunk of the round becomes a work unit of
    ``runtime/straggler.py:run_with_speculation``: worker threads solve
    the chunks, and a chunk exceeding the deadline ``alpha * median(done
    chunk times)`` is speculatively re-executed on an idle worker — first
    result wins, which is safe because solves are deterministic (the twin
    computes bit-identical output).  Compile-cache deltas are attributed
    once for the whole round (per-chunk attribution would race across
    threads); results and counters match the serial staging loop.
    """
    from ..runtime.straggler import run_with_speculation

    ranges = [(lo, min(lo + chunk, bsz)) for lo in range(0, bsz, chunk)]
    before = (
        backend.cache_size()
        if stats is not None and backend.cache_size
        else None
    )

    def solve_unit(payload, worker):
        k, (lo, hi) = payload
        if monkey is not None:
            monkey.on_chunk(chaos_round, k)
        # Worker threads count into their own record, merged below.
        counts = SolveStats()
        cur = _stage_round_inputs(batch, state, lo, hi, None, (), k, counts)
        out, out_state = _solve_chunk(backend, cur, options, want_state, None, k)
        # Block here so the scheduler's per-unit elapsed times measure
        # the solve, not the async dispatch — the straggler deadline
        # needs real durations.
        with _trace.span("dispatch.sync", site="speculation"):
            jax.block_until_ready(out.status)
        counts.host_syncs += 1
        return out, out_state, counts

    report = run_with_speculation(
        list(enumerate(ranges)), solve_unit, n_workers=min(4, len(ranges))
    )
    parts, state_parts = [], []
    for (lo, hi), unit in zip(ranges, report.results):
        out, out_state, counts = unit.value
        if stats is not None:
            stats.bytes_staged += counts.bytes_staged
            stats.host_syncs += counts.host_syncs
            valid = min(hi, true_bsz) - lo
            if valid > 0:
                stats.record(out if valid == hi - lo else _trim_solution(out, valid))
        parts.append(out)
        if out_state is not None:
            state_parts.append(out_state)
    if before is not None:
        stats.record_cache(before, backend.cache_size())
    return parts, state_parts


def _stage_round_inputs(batch, state, lo, hi, mesh, axes, chunk, stats):
    """Stage rows ``lo:hi`` (and their carried state): one ``dispatch.stage`` span.

    The bytes handed to ``jax.device_put`` are the span's ``bytes`` and
    add to ``stats.bytes_staged``.
    """
    with _trace.span("dispatch.stage", chunk=chunk) as sp:
        cur = (
            _stage_batch(batch, lo, hi, mesh, axes),
            None if state is None else _stage_state(state, lo, hi, mesh, axes),
        )
        nbytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(cur))
        sp.set(bytes=nbytes)
    if stats is not None:
        stats.bytes_staged += nbytes
    return cur


def _solve_chunk(
    backend: Backend,
    cur: Tuple[LPBatch, Optional[ResumeState]],
    options: SolveOptions,
    want_state: bool,
    stats: Optional[SolveStats],
    chunk: int,
) -> Tuple[LPSolution, Optional[ResumeState]]:
    """Run one chunk through the backend (a ``dispatch.enqueue`` span),
    attributing compiles vs hits."""
    cur_batch, cur_state = cur
    before = backend.cache_size() if stats is not None and backend.cache_size else None
    with _trace.span("dispatch.enqueue", chunk=chunk):
        if cur_state is not None:
            out, out_state = backend.resume_canonical(cur_batch, cur_state, options)
        elif want_state:
            out, out_state = backend.start_canonical(cur_batch, options)
        else:
            out, out_state = backend.solve_canonical(cur_batch, options), None
    if before is not None:
        stats.record_cache(before, backend.cache_size())
    return out, out_state


def solve_hyperbox(
    lo,
    hi,
    directions,
    options: Optional[SolveOptions] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axes: Sequence[str] = ("data",),
    stats: Optional[SolveStats] = None,
) -> LPSolution:
    """Closed-form box-LP batch through the selected backend.

    Parameters
    ----------
    lo, hi : array_like
        Box bounds, broadcastable to ``directions``' shape ``(B, n)``.
    directions : array_like
        Objective directions, one LP per row.
    options : SolveOptions, optional
        Backend selection (the box path needs no iteration knobs).
    mesh, batch_axes
        As for :func:`solve_canonical`.
    stats : SolveStats, optional
        Counters to accumulate into (box LPs record 0 iterations) — the
        paper-style "No. of LPs" accounting counts hyperbox LPs too.

    Returns
    -------
    LPSolution
        Support values in ``objective``, maximizing vertices in ``x``.
    """
    options = options or SolveOptions()
    if options.backend == "auto":
        # Box LPs are closed-form on every backend; the routing question
        # (simplex vs first-order iteration cost) does not exist here.
        options = options.replace(backend="xla")
    backend = get_backend(options.backend)
    directions = jnp.asarray(directions)
    if directions.shape[0] == 0:
        return empty_solution(directions.shape[-1], directions.dtype)
    axes = _resolve_axes(mesh, batch_axes)
    sol = backend.solve_hyperbox(
        _stage(jnp.asarray(lo), mesh, axes),
        _stage(jnp.asarray(hi), mesh, axes),
        _stage(directions, mesh, axes),
        options,
    )
    if stats is not None:
        stats.record(sol)
    return sol

"""Backend registry: named solver implementations behind one protocol.

Every backend solves the *canonical* form only (``max c.x, Ax <= b,
x >= 0``) — canonicalization happens above this layer (core/problem.py),
chunking/sharding happens beside it (core/dispatch.py).  A backend is a
pair of callables:

    solve_canonical(LPBatch, SolveOptions)      -> LPSolution
    solve_hyperbox(lo, hi, dirs, SolveOptions)  -> LPSolution

Built-ins:

  * ``xla``       — the lockstep batched simplex (core/simplex.py), jitted
                    through XLA; the default and the paper-faithful path.
  * ``pallas``    — the VMEM-resident Pallas kernels (kernels/ops.py);
                    Mosaic on TPU, interpret mode on CPU.
  * ``reference`` — the sequential float64 NumPy oracle (core/oracle.py);
                    slow, trustworthy, used for cross-checking.

``register_backend`` lets deployments plug in new implementations (e.g. a
first-order PDLP backend) without touching the front-end; ``repro.solve``
selects by ``SolveOptions.backend`` name.

Two pipeline-level extensions ride on this protocol:

  * warm starts — the canonical batch may carry ``LPBatch.basis0``; the
    ``xla`` and ``pallas`` backends rebuild the tableau for that basis and
    skip phase I where it is feasible, and report the final basis in
    ``LPSolution.basis`` (the ``reference`` oracle ignores the hint);
  * convergence compaction — ``SolveOptions.compaction`` makes the
    dispatch layer drop converged LPs between rounds and re-dispatch the
    dense still-active set; it composes with any backend because it lives
    entirely above this protocol (core/dispatch.py).

``SolveStats`` is the opt-in instrumentation record both features report
into.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..runtime import trace as _trace
from . import engine as _engine
from . import hyperbox as _hyperbox
from . import pdhg as _pdhg
from . import revised as _revised
from . import simplex as _simplex
from .lp import LPBatch, LPSolution, ResumeState, SharedLPBatch
from .tableau import DEFAULT_LAYOUT, LAYOUTS, TableauSpec


#: Valid values of :attr:`SolveOptions.compaction`.
COMPACTION_MODES = ("off", "chunked", "every_k")

#: Valid values of :attr:`SolveOptions.resume`.
RESUME_MODES = ("scratch", "basis")

#: Valid ``SolveOptions.autotune`` modes (see ``runtime/autotune.py``).
AUTOTUNE_MODES = ("off", "predict", "trial")

#: Backends that consume :class:`~repro.core.lp.SharedLPBatch` natively —
#: one ``(m, n)`` constraint matrix read-shared by every LP in the batch,
#: per-LP state limited to the revised-simplex basis record
#: (``core/revised.py``).  The dispatch layer densifies a shared batch
#: before handing it to any backend NOT in this tuple.
SHARED_BACKENDS = ("xla-shared", "pallas-shared")

#: Shape frontier for ``backend="auto"``: LPs with ``max(m, n)`` at or
#: above it route to the first-order ``pdhg`` backend, smaller ones to a
#: simplex backend.  The default matches the measured simplex/pdhg
#: crossover (``benchmarks/fig_frontier.py``) and the regime the paper's
#: tableau method explicitly cedes (m, n >= 500); override per solve via
#: :attr:`SolveOptions.route_frontier`.
DEFAULT_ROUTE_FRONTIER = 500


@dataclasses.dataclass(frozen=True)
class SolveOptions:
    """Solver configuration — one frozen record instead of loose knobs.

    Parameters
    ----------
    backend : str, default "xla"
        Registered backend name (``"xla"`` | ``"pallas"`` | ``"pdhg"`` |
        ``"xla-shared"`` | ``"pallas-shared"`` | ``"reference"`` | a name
        added via :func:`register_backend`), or ``"auto"`` — not a
        registered backend but a routing directive: the dispatch layer
        resolves it per shape through :func:`route_shape` (simplex below
        :attr:`route_frontier`, the first-order ``pdhg`` backend at or
        above it).  On a :class:`~repro.core.lp.SharedLPBatch` the
        simplex names promote to their shared counterparts
        (:data:`SHARED_BACKENDS`) and ``"auto"`` routes shared; the
        shared names on a plain :class:`LPBatch` are an error.
    rule : str, default "lpc"
        Pivot rule: ``"lpc"`` (largest positive coefficient, the paper
        default), ``"rpc"`` (randomized), or ``"bland"`` (anti-cycling).
        Honored by every backend that iterates — the ``xla`` and
        ``pallas`` paths drive the same ``core/engine.py`` blocks, so a
        rule behaves identically on both (the ``reference`` oracle is
        LPC-only by design and ignores this knob).
    max_iters : int, default 0
        Simplex iteration cap across both phases; 0 means the auto cap
        ``50 * (m + n)``.
    tolerance : float, default 0.0
        Reduced-cost/pivot tolerance; 0 means the dtype default (1e-9 for
        float64, 1e-5 for float32).  Honored by the ``xla`` and ``pallas``
        backends alike (both resolve it through
        ``core/engine.py:default_tolerance``); the float64 ``reference``
        oracle keeps its own fixed 1e-9.
    unroll : int, default 1
        ``lax.while_loop`` body unroll factor (xla perf knob).
    chunk_size : int, optional
        Megabatch chunk size for the overlapped dispatch pipeline
        (None = whole batch in one chunk).
    first_cap : int, optional
        Legacy adaptive two-pass cap.  None disables the two-pass solve; 0
        enables it with the auto cap ``8 * (m + n)``; a positive value is
        the explicit pass-1 iteration cap.  Subsumed by (and ignored when
        combined with) ``compaction``.
    compaction : str, default "off"
        Convergence compaction mode for the dispatch pipeline:

        * ``"off"`` — lockstep to the bitter end: every LP in a dispatch
          pays the slowest LP's iteration count (the paper's lockstep
          trade-off).
        * ``"chunked"`` — each chunk runs with a small iteration cap; LPs
          still running afterwards are pooled across chunks, compacted
          into one dense sub-batch, and re-dispatched with the full cap.
        * ``"every_k"`` — the whole batch is iterated in rounds with a
          geometrically doubling cap (k, 2k, 4k, ...); after each round
          the converged LPs are dropped and the survivors are compacted
          into a dense sub-batch for the next round.

        Both active modes return results identical to ``"off"`` under the
        deterministic pivot rules (lpc/bland) — per-LP pivot trajectories
        do not depend on batch composition — and are honored by every
        registered backend, since compaction lives above the backend
        protocol (core/dispatch.py).
    compact_every : int, default 0
        Iteration budget per compaction round (the cap ``k`` above);
        0 means the auto budget ``8 * (m + n)``.
    resume : str, default "scratch"
        How compaction rounds treat the LPs that survive a capped round:

        * ``"scratch"`` — round r+1 re-solves survivors from iteration 0
          with a doubled cap (the historical behavior; re-work grows with
          the round count).
        * ``"basis"`` — round r+1 CONTINUES each survivor from the exact
          simplex state (tableau/basis/phase) round r stopped at, so the
          per-round step budgets sum to one full solve and no pivot is
          ever repeated.  Because the carried state is exact, results —
          including per-LP iteration counts — are bit-identical to
          ``compaction="off"`` under the deterministic pivot rules
          (lpc/bland; the rpc rule keys its noise on the loop step and
          batch row, which any compaction mode perturbs).  Honored by
          backends that implement the state protocol (``xla``,
          ``pallas``); others — and solves with ``unroll > 1``, whose
          step grouping cannot be split mid-round — silently fall back
          to ``"scratch"``.
    dynamic_caps : bool, default True
        When True (the compile-once contract) the iteration cap is a
        traced scalar: every round cap over one tableau shape runs ONE
        compiled executable.  False re-specializes the executable on each
        concrete cap — the pre-compile-once behavior, kept as a benchmark
        baseline (``benchmarks/fig_dispatch.py``).
    layout : str, optional
        Tableau storage layout (``core/tableau.py``):

        * ``None`` (default) — let the resolution path pick: the
          autotuner (``runtime/autotune.py``) when ``autotune`` is
          active, else :data:`DEFAULT_LAYOUT`.  Consumers read the
          concrete value via :attr:`effective_layout`.
        * ``"compact"`` — the artificial block is implicit (basis IDs
          only); ``q = 1 + n + m`` columns.  ~25–33% less tableau
          memory and pivot-update work on square LPs, larger Pallas
          tiles per VMEM budget.
        * ``"dense"`` — the paper's explicit column map with the
          artificial identity block (``q = 1 + n + 2m``); kept
          selectable so the compact win stays benchmarkable.

        Both layouts produce BIT-IDENTICAL objectives, statuses, bases,
        and per-LP iteration counts on the ``xla`` and ``pallas``
        backends under every pivot rule: the artificial columns are
        write-only lanes that no pricing/ratio/feasibility decision ever
        reads.  The float64 ``reference`` oracle ignores the knob.
    seed : int, default 0
        PRNG seed for the randomized (RPC) pivot rule.
    pdhg_tol : float, default 0.0
        Relative KKT tolerance for the first-order ``pdhg`` backend
        (primal/dual residuals and duality gap); 0 means the backend
        default (1e-4, PDLP's "moderate accuracy").  Ignored by the
        simplex backends, whose ``tolerance`` knob is a pivot threshold,
        not a convergence target.
    pdhg_restart : int, default 0
        Fixed restart-to-average period of the ``pdhg`` backend; 0 means
        the backend default (64).  The period is per-LP and fixed (not
        adaptive) so compaction cannot perturb trajectories.
    crossover : bool, default False
        Polish the ``pdhg`` backend's OPTIMAL rows into EXACT vertices:
        after the first-order solve converges, a basis guess is read off
        each point (top-m of ``[x | slacks]``) and handed to the simplex
        engine's warm-start path, which returns the exact vertex
        objective/point plus a reusable ``LPSolution.basis``
        (``core/pdhg.py:crossover``).  Requires ``backend`` ``"pdhg"``
        or ``"auto"`` — simplex output is already a vertex.
    route_frontier : int, default 0
        The ``backend="auto"`` shape frontier: shapes with ``max(m, n)``
        at or above it route to ``pdhg``, below it to a simplex backend
        (see :func:`route_shape`).  0 means
        :data:`DEFAULT_ROUTE_FRONTIER`.
    guardrails : bool, default True
        Per-round numerical health mask
        (``core/dispatch.py:apply_guardrails``): rows whose solution or
        carried resume state went non-finite retire with the
        ``NUMERICAL`` status instead of spinning to ``ITER_LIMIT`` or
        reporting a poisoned certificate.  Costs a handful of lazy
        ``isfinite`` reductions folded into the existing per-round
        status read-back (measured < 3% wall-clock,
        ``benchmarks/fig_faults.py``).
    quarantine : bool, default False
        Opt-in recovery lane for guardrail-flagged rows: after the round
        loop, ``NUMERICAL`` rows with finite INPUTS are re-solved on the
        float64 reference oracle under a ``max(400, 2 (m + n))`` pivot
        budget (the pdhg certificate-confirmation budget rule) and the
        oracle's verdict replaces the flag when it reaches one.
    retry_budget : int, default 2
        Fault-recovery retries per dispatch round
        (``core/dispatch.py:dispatch_round_safe``): a transient backend
        failure re-dispatches the SAME round from its carried resume
        state up to this many times — on the routed fallback backend
        (:func:`fault_fallback`) with capped exponential backoff —
        before the error propagates.  0 disables recovery.  In the
        continuous serve loop the budget is per group round; a group
        that exhausts it dead-letters its LPs
        (``serve/engine.py``).
    retry_backoff : float, default 0.05
        Base of the recovery backoff: retry k sleeps
        ``retry_backoff * 2**k`` seconds, capped at 1s.
    speculation : bool, default False
        Straggler mitigation for multi-chunk rounds
        (``runtime/straggler.py:run_with_speculation``): chunks of a
        round dispatch from worker threads, and a chunk exceeding
        ``alpha * median(done chunk times)`` is speculatively re-executed
        — first result wins (solves are deterministic, so twins agree).
        Single-chunk and mesh-sharded rounds ignore the knob.
    tile_b : int, optional
        Pallas batch tile override for the kernel backends.  None
        (default) defers to the tuned/heuristic tile
        (``kernels/ops.py:auto_tile_b``); the XLA drivers ignore the
        knob.  The tile never changes per-LP results — only how many
        LPs share one kernel grid step.
    autotune : str, default "predict"
        How ``backend="auto"`` / ``layout=None`` / ``tile_b=None`` gaps
        are filled (``runtime/autotune.py``):

        * ``"predict"`` — rank feasible candidate configs by the
          analytic roofline cost model and take the cheapest.  Pure:
          no disk IO, no extra compiles; reproduces the static routing
          table exactly.
        * ``"trial"`` — additionally confirm the predicted top-k by
          timed micro-solves and persist the measured winner in the
          on-disk tuning cache (``$REPRO_AUTOTUNE_CACHE``), so warm
          processes resolve with zero micro-trials.
        * ``"off"`` — the static routing table alone
          (:func:`route_shape` + :data:`DEFAULT_LAYOUT` + the VMEM tile
          heuristic); the tuner is never consulted.

        Whatever the mode, explicit pins (a concrete ``backend``, a
        non-None ``layout``/``tile_b``) always win, and the tuner only
        ever changes WHICH config runs — never the per-LP results a
        given config produces.
    """

    backend: str = "xla"
    rule: str = _engine.LPC
    max_iters: int = 0
    tolerance: float = 0.0
    unroll: int = 1
    chunk_size: Optional[int] = None
    first_cap: Optional[int] = None
    compaction: str = "off"
    compact_every: int = 0
    resume: str = "scratch"
    dynamic_caps: bool = True
    layout: Optional[str] = None
    seed: int = 0
    pdhg_tol: float = 0.0
    pdhg_restart: int = 0
    crossover: bool = False
    route_frontier: int = 0
    guardrails: bool = True
    quarantine: bool = False
    retry_budget: int = 2
    retry_backoff: float = 0.05
    speculation: bool = False
    tile_b: Optional[int] = None
    autotune: str = "predict"

    def __post_init__(self):
        # Validate here (not in the dispatch layer) so every route —
        # including the boxlike/hyperbox paths that never iterate — rejects
        # a misconfiguration at the same place.
        if self.compaction not in COMPACTION_MODES:
            raise ValueError(
                f"unknown compaction mode {self.compaction!r}; "
                f"expected one of {COMPACTION_MODES}"
            )
        if self.resume not in RESUME_MODES:
            raise ValueError(
                f"unknown resume mode {self.resume!r}; "
                f"expected one of {RESUME_MODES}"
            )
        if self.rule not in _engine.RULES:
            raise ValueError(
                f"unknown pivot rule {self.rule!r}; "
                f"expected one of {_engine.RULES}"
            )
        if self.layout is not None and self.layout not in LAYOUTS:
            raise ValueError(
                f"unknown tableau layout {self.layout!r}; "
                f"expected one of {LAYOUTS} (or None to auto-resolve)"
            )
        if self.autotune not in AUTOTUNE_MODES:
            raise ValueError(
                f"unknown autotune mode {self.autotune!r}; "
                f"expected one of {AUTOTUNE_MODES}"
            )
        if self.tile_b is not None and self.tile_b < 1:
            raise ValueError(f"tile_b must be >= 1, got {self.tile_b!r}")
        if self.pdhg_tol < 0.0:
            raise ValueError(f"pdhg_tol must be >= 0, got {self.pdhg_tol!r}")
        if self.pdhg_restart < 0:
            raise ValueError(
                f"pdhg_restart must be >= 0, got {self.pdhg_restart!r}"
            )
        if self.route_frontier < 0:
            raise ValueError(
                f"route_frontier must be >= 0, got {self.route_frontier!r}"
            )
        if self.retry_budget < 0:
            raise ValueError(
                f"retry_budget must be >= 0, got {self.retry_budget!r}"
            )
        if self.retry_backoff < 0.0:
            raise ValueError(
                f"retry_backoff must be >= 0, got {self.retry_backoff!r}"
            )
        if self.backend == "pdhg":
            # A first-order method has no pivot rule and no tableau: a
            # non-default rule/layout on it is a misconfiguration, not a
            # silently-ignorable hint.
            if self.rule != _engine.LPC:
                raise ValueError(
                    f"rule={self.rule!r} is meaningless for backend='pdhg' "
                    "(a first-order method performs no pivots); leave rule "
                    "at its default 'lpc'"
                )
            if self.layout not in (None, DEFAULT_LAYOUT):
                raise ValueError(
                    f"layout={self.layout!r} is meaningless for "
                    "backend='pdhg' (a first-order method stores no "
                    f"tableau); leave layout unset or at its default "
                    f"{DEFAULT_LAYOUT!r}"
                )
        if self.crossover and self.backend not in ("pdhg", "auto"):
            raise ValueError(
                "crossover=True polishes a first-order solution into an "
                "exact vertex and requires backend='pdhg' or 'auto'; "
                f"backend={self.backend!r} already returns vertices"
            )

    @property
    def effective_layout(self) -> str:
        """The concrete tableau layout consumers should build with.

        ``layout`` when pinned, else :data:`DEFAULT_LAYOUT` — the value
        an unresolved ``layout=None`` means everywhere a tableau is
        actually constructed (the autotuner fills the field with its
        choice during resolution, so a resolved options record only
        falls back here when tuning is off).
        """
        return self.layout if self.layout is not None else DEFAULT_LAYOUT

    def replace(self, **kw) -> "SolveOptions":
        """Return a copy with the given fields replaced.

        Parameters
        ----------
        **kw
            Field-name/value pairs, as for :func:`dataclasses.replace`.

        Returns
        -------
        SolveOptions
            A new frozen record; ``self`` is unchanged.
        """
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass
class SolveStats:
    """Mutable host-side counters accumulated across a solve pipeline.

    Pass an instance to :func:`repro.solve` /
    :func:`repro.core.dispatch.solve_canonical` (``stats=``) to measure
    the work a pipeline actually performed — the counters that make the
    compaction and warm-start wins observable.  It is opt-in
    (``stats=None`` costs nothing).

    Only :meth:`record` forces a sync: it reads each dispatched chunk's
    iteration counts back to the host, and it alone feeds ``lps``,
    ``rounds``, ``simplex_iterations``, ``lockstep_iterations`` and
    ``phase_rewrites``.  So a
    solve with ``stats=`` waits for each chunk before dispatching the next.
    Every other counter is host bookkeeping the pipeline already holds,
    and ``host_syncs`` counts the syncs themselves, ``record``'s own
    included.

    Attributes
    ----------
    lps : int
        LP solves recorded (an LP re-dispatched by a compaction round or a
        two-pass solve counts once per dispatch).
    rounds : int
        Backend dispatches recorded (compaction rounds, chunks, sweep
        steps).
    simplex_iterations : int
        Total simplex pivots across all recorded LPs — the counter the
        warm-started reachability sweep drives down.
    lockstep_iterations : int
        ``max(iterations) * batch`` summed per dispatch: the lockstep cost
        model, in which every LP pays the slowest LP's iteration count.
        Compaction shrinks this toward ``simplex_iterations``.
    phase_rewrites : int
        Iterations in which a tile of the Pallas tableau kernel ran the
        phase-I to phase-II objective rewrite, summed once per tile
        (``LPSolution.phase_rewrites``): at most one per phase-I LP of
        the tile.  Zero where every LP starts in phase II, and on the
        backends that do not report it.
    warm_started : int
        LPs that entered a dispatch with a usable warm-start basis.
    resumed : int
        LPs that entered a dispatch round carrying exact mid-solve state
        (``SolveOptions.resume="basis"``) instead of restarting from
        scratch.
    spliced : int
        Newly admitted LPs the continuous-batching serve loop merged into
        a round that already carried in-flight survivors (the
        iteration-0 ``init_canonical`` states joining a resume dispatch).
        A first admission into an empty shape class is not a splice.
    compiles : int
        New solver executables compiled by the dispatches this record
        observed (measured through the backend's compile-cache hook).
        Under the compile-once contract this stays at one per tableau
        shape bucket no matter how many rounds/caps/sweep steps run.
    cache_hits : int
        Dispatches that reused an already-compiled executable.  The
        steady-state counter: a warmed-up serving loop or sweep should
        accumulate only cache hits.
    tableau_bytes : int
        PEAK per-round LOGICAL tableau footprint (bytes) across the
        recorded dispatches: padded batch size times the UNPADDED per-LP
        tableau bytes under the configured :attr:`SolveOptions.layout`
        (``TableauSpec.bytes_per_lp``).  Exact for the ``xla`` driver's
        ``(B, m+1, q)`` arrays; backend-internal padding (the Pallas
        kernel's 128-lane/8-sublane alignment, which can dominate at
        small ``q``, or the ``reference`` oracle's own dense float64
        copies) is not included.  The memory counterpart of the
        iteration counters — sessions and benchmarks report it alongside
        iterations/compiles, and it is what the compact layout drives
        down (~33% on square LPs).
    retries : int
        Dispatch rounds re-executed from their carried resume state by
        the fault-recovery wrapper
        (``core/dispatch.py:dispatch_round_safe``) after a transient
        backend failure.  Zero on the clean path.
    quarantined : int
        Guardrail-flagged (``NUMERICAL``) rows re-solved on the float64
        oracle by the opt-in quarantine lane
        (``SolveOptions.quarantine``).
    dead_lettered : int
        Serve-loop LPs retired without a solve because their group
        exhausted its retry budget (``serve/engine.py``); their tickets
        redeem ``NUMERICAL`` results and appear in
        ``LPEngine.dead_letters``.
    faults_injected : int
        Injected chaos faults (``runtime/chaos.py``) observed by the
        recovery path — raised faults that were caught plus state rows
        poisoned.  Zero outside fault-injection runs.
    autotuned : int
        Options resolutions the cost-model autotuner performed
        (``runtime/autotune.py``) — one per ``resolve_backend`` call
        with ``autotune`` active, whatever knobs it ended up filling.
    autotune_log : list of dict
        One record per autotuned resolution: the shape class, the chosen
        ``backend``/``layout``/``tile_b``, ``predicted_s`` vs
        ``measured_s`` cost, and the decision ``source``
        (``"predicted"``/``"measured"``/``"cache"``) — the
        predicted-versus-measured audit trail.
    host_syncs : int
        Host read-backs the pipeline made (each a ``dispatch.sync`` span,
        :func:`read_back`): the per-round status read of a compaction or
        two-pass solve, :meth:`record`'s reads, the quarantine lane's
        reads, the speculative chunk waits, and the serve loop's
        per-round reads.  A plain one-round solve without ``stats=``
        makes none.
    bytes_staged : int
        Host bytes handed to ``jax.device_put`` by the chunk staging
        (each a ``dispatch.stage`` span).
    """

    lps: int = 0
    rounds: int = 0
    simplex_iterations: int = 0
    lockstep_iterations: int = 0
    phase_rewrites: int = 0
    warm_started: int = 0
    resumed: int = 0
    spliced: int = 0
    compiles: int = 0
    cache_hits: int = 0
    tableau_bytes: int = 0
    retries: int = 0
    quarantined: int = 0
    dead_lettered: int = 0
    faults_injected: int = 0
    autotuned: int = 0
    autotune_log: List[dict] = dataclasses.field(default_factory=list)
    host_syncs: int = 0
    bytes_staged: int = 0

    def record_tableau(self, nbytes: int) -> None:
        """Fold one dispatch round's tableau footprint into the peak.

        Parameters
        ----------
        nbytes : int
            The round's total tableau bytes (padded batch x bytes/LP).
        """
        self.tableau_bytes = max(self.tableau_bytes, int(nbytes))

    def record_cache(self, before: int, after: int) -> None:
        """Attribute one backend call's compile-cache delta.

        The single implementation of the compiles-vs-hits rule, shared by
        the dispatch round loop and the compiled sweep session: a grown
        cache books the growth as ``compiles``, an unchanged cache books
        one ``cache_hits``.
        """
        delta = after - before
        if delta > 0:
            self.compiles += delta
        else:
            self.cache_hits += 1

    def record(self, sol: LPSolution) -> None:
        """Accumulate one dispatch's ``LPSolution`` into the counters.

        Parameters
        ----------
        sol : LPSolution
            The solution batch returned by a backend dispatch.
        """
        if sol.phase_rewrites is None:
            iters = read_back(sol.iterations, "stats.record", self)
            rewrites = 0
        else:
            # One read-back for both, so a round still makes one sync here.
            iters, rewrites = read_back(
                (sol.iterations, sol.phase_rewrites), "stats.record", self
            )
        if iters.size == 0:
            return
        self.lps += int(iters.size)
        self.rounds += 1
        self.simplex_iterations += int(iters.sum())
        self.lockstep_iterations += int(iters.max()) * int(iters.size)
        self.phase_rewrites += int(np.sum(rewrites))


def read_back(x, site: str, stats: Optional[SolveStats] = None) -> np.ndarray:
    """Copy ``x`` to the host: one host sync, spanned and counted.

    The pipeline's read-backs of one array go through here, so a traced
    run shows each as a ``dispatch.sync`` span (``site`` names the
    caller) and ``stats.host_syncs`` counts it.
    """
    with _trace.span("dispatch.sync", site=site):
        out = np.asarray(x)
    if stats is not None:
        stats.host_syncs += 1
    return out


@dataclasses.dataclass(frozen=True)
class Backend:
    """A named solver implementation over the canonical problem protocol.

    Attributes
    ----------
    name : str
        Registry key, selected by :attr:`SolveOptions.backend`.
    solve_canonical : callable
        ``(LPBatch, SolveOptions) -> LPSolution``.  The batch may carry a
        warm-start basis in ``LPBatch.basis0``; backends that cannot honor
        it must ignore it (a warm start is a hint, never a semantic
        change) and may leave ``LPSolution.basis`` as None.  A
        ``max_iters`` of 0 must resolve to ``core.lp.auto_cap(m, n)`` —
        the compaction engine relies on every backend sharing that rule
        for its results-identical-to-``off`` guarantee.
    solve_hyperbox : callable
        ``(lo, hi, directions, SolveOptions) -> LPSolution`` — the
        closed-form box path (paper Sec. 6).
    start_canonical : callable, optional
        ``(LPBatch, SolveOptions) -> (LPSolution, ResumeState)`` — like
        ``solve_canonical`` but also reporting the exact terminal solver
        state, so a capped round can be continued.  None means the
        backend cannot produce state; the dispatch layer then falls back
        to scratch-mode rounds.
    resume_canonical : callable, optional
        ``(LPBatch, ResumeState, SolveOptions) -> (LPSolution,
        ResumeState)`` — continue the batch from carried state for
        ``options.max_iters`` ADDITIONAL steps.  ``batch.a`` is ignored
        (the tableau already encodes it); ``batch.b``/``batch.c``
        re-derive the cost row and feasibility threshold bit-identically.
    init_canonical : callable, optional
        ``(LPBatch, SolveOptions) -> ResumeState`` — the ITERATION-0
        resume state of the batch (tableau built / iterates zeroed,
        nothing advanced), such that resuming it for ``K`` additional
        steps is bit-identical to a cold ``solve_canonical`` with cap
        ``K``.  This is the splice primitive of the continuous-batching
        serve loop (``serve/engine.py``): newly admitted LPs are
        materialized as states and concatenated with the round's carried
        survivors, so one capped resume dispatch advances both.  None
        means newcomers cannot be spliced; the serve loop then falls back
        to one-shot solves at admission.
    cache_size : callable, optional
        ``() -> int`` — number of solver executables this backend has
        compiled so far.  The dispatch layer diffs it around each call to
        maintain ``SolveStats.compiles`` / ``SolveStats.cache_hits``.
    auto_cap : callable, optional
        ``(m, n) -> int`` — the backend's auto iteration cap when
        ``SolveOptions.max_iters`` is 0.  None means the library-wide
        simplex rule ``core.lp.auto_cap`` (``50 (m + n)``); the
        first-order ``pdhg`` backend overrides it (cheap iterations,
        more of them).  The dispatch layer's round scheduler reads this
        hook so its final compaction round uses the same cap a plain
        solve on this backend would — the rule its
        results-identical-to-``"off"`` guarantee rests on.
    """

    name: str
    solve_canonical: Callable[[LPBatch, SolveOptions], LPSolution]
    solve_hyperbox: Callable[..., LPSolution]
    start_canonical: Optional[
        Callable[[LPBatch, SolveOptions], Tuple[LPSolution, ResumeState]]
    ] = None
    resume_canonical: Optional[
        Callable[[LPBatch, ResumeState, SolveOptions], Tuple[LPSolution, ResumeState]]
    ] = None
    init_canonical: Optional[Callable[[LPBatch, SolveOptions], ResumeState]] = None
    cache_size: Optional[Callable[[], int]] = None
    auto_cap: Optional[Callable[[int, int], int]] = None

    @property
    def supports_resume(self) -> bool:
        """True when the backend implements the exact-state round protocol."""
        return self.start_canonical is not None and self.resume_canonical is not None

    @property
    def supports_splice(self) -> bool:
        """True when new LPs can join an in-flight resume round mid-solve.

        Requires both the resume protocol and the iteration-0 init hook —
        what the continuous-batching serve loop needs to splice arrivals
        into the next capped dispatch alongside carried survivors.
        """
        return self.supports_resume and self.init_canonical is not None


_REGISTRY: Dict[str, Backend] = {}


def register_backend(backend: Backend, overwrite: bool = False) -> Backend:
    """Add a backend to the registry.

    Parameters
    ----------
    backend : Backend
        The implementation record to register.
    overwrite : bool, default False
        Replace an existing backend of the same name instead of raising.

    Returns
    -------
    Backend
        The registered backend (for chaining).

    Raises
    ------
    ValueError
        If the name is already registered and ``overwrite`` is False.
    """
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    """Look up a registered backend by name.

    Parameters
    ----------
    name : str
        A name from :func:`available_backends`.

    Returns
    -------
    Backend

    Raises
    ------
    ValueError
        If no backend of that name is registered.
    """
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {', '.join(available_backends())}"
        ) from None


def available_backends() -> Tuple[str, ...]:
    """Sorted names of all registered backends."""
    return tuple(sorted(_REGISTRY))


def route_shape(
    m: int,
    n: int,
    dtype=jnp.float32,
    options: Optional[SolveOptions] = None,
    layout: Optional[str] = None,
    shared: bool = False,
) -> str:
    """The shape-routing table: pick a backend name for an LP shape.

    One rule, consulted from both directions:

    * ``backend="auto"`` resolves through it in the dispatch layer —
      simplex below the routing frontier (``pallas`` when on TPU and the
      tableau fits VMEM, else ``xla``), the first-order ``pdhg`` backend
      at or above it (the regime the paper's tableau simplex cedes);
    * the ``pallas`` backend's VMEM fallback
      (:func:`_pallas_vmem_fallback`) re-routes over-budget shapes
      through it instead of hard-coding ``xla``, so a tableau too big
      for VMEM lands on ``pdhg`` when it is also past the frontier —
      which is exactly the shape class where the O(m (n + m)) tableau
      stops making sense anywhere, not just in VMEM.

    The frontier is ``SolveOptions.route_frontier`` (0 ->
    :data:`DEFAULT_ROUTE_FRONTIER`); the simplex leg reuses the kernel's
    ``fits_vmem`` predicate with the conservative ``want_state=True``
    footprint so routing never flips between the start and resume rounds
    of one solve.

    ``shared=True`` routes a :class:`~repro.core.lp.SharedLPBatch` —
    one of :data:`SHARED_BACKENDS`, never ``pdhg``: the frontier exists
    because the per-LP tableau is O(m (n + m)), but the shared batch's
    per-LP state is the O(m^2) revised-simplex basis record and its
    stored problem data is O(m) amortized, so densifying past the
    frontier would forfeit exactly the memory win the caller asked for.
    """
    if options is not None and options.autotune != "off":
        # Tuner-backed routing (the default): same candidate space, same
        # frontier/VMEM constraints, but ranked by the cost model — and a
        # measured micro-trial winner (autotune="trial") can overrule the
        # static table.  The caller's pinned backend is deliberately NOT
        # forwarded: route_shape asks where a shape SHOULD go (e.g. the
        # VMEM fallback rerouting an over-budget pallas pin).
        from ..runtime import autotune as _autotune

        return _autotune.choose_backend(
            m, n, dtype, options, shared=shared, layout=layout
        )
    if shared:
        from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

        if kernel_ops._on_tpu() and kernel_ops.revised_fits_vmem(m, n, dtype):
            return "pallas-shared"
        return "xla-shared"
    frontier = DEFAULT_ROUTE_FRONTIER
    if options is not None and options.route_frontier > 0:
        frontier = options.route_frontier
    if max(m, n) >= frontier:
        return "pdhg"
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    layout = layout or (
        options.effective_layout if options is not None else DEFAULT_LAYOUT
    )
    if kernel_ops._on_tpu() and kernel_ops.fits_vmem(
        m, n, dtype, layout, want_state=True
    ):
        return "pallas"
    return "xla"


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------


def _xla_solve(
    batch: LPBatch, options: SolveOptions, want_state: bool = False
):
    return _simplex.solve_batched(
        batch.a,
        batch.b,
        batch.c,
        rule=options.rule,
        max_iters=options.max_iters,
        seed=options.seed,
        unroll=options.unroll,
        tol=options.tolerance,
        basis0=batch.basis0,
        want_state=want_state,
        dynamic_cap=options.dynamic_caps,
        layout=options.effective_layout,
    )


def _xla_start(batch: LPBatch, options: SolveOptions):
    return _xla_solve(batch, options, want_state=True)


def _xla_resume(batch: LPBatch, state: ResumeState, options: SolveOptions):
    return _simplex.resume_batched(
        batch.b,
        batch.c,
        state,
        rule=options.rule,
        max_iters=options.max_iters,
        seed=options.seed,
        unroll=options.unroll,
        tol=options.tolerance,
        want_state=True,
        dynamic_cap=options.dynamic_caps,
    )


def _xla_init(batch: LPBatch, options: SolveOptions) -> ResumeState:
    return _simplex.init_batched(
        batch.a, batch.b, batch.c, basis0=batch.basis0,
        layout=options.effective_layout,
    )


def _xla_hyperbox(lo, hi, directions, options: SolveOptions) -> LPSolution:
    return _hyperbox.solve_batched(lo, hi, directions)


# One keyed warn-once table for every routing-fallback message in this
# module (simplex pallas->xla/pdhg VMEM fallback, the pdhg kernel->XLA
# driver fallback, the pallas-shared->xla-shared fallback).  Keys are
# ``(path, m, n, dtype, ...)`` tuples; values keep the emitted message so
# tests can assert on what was (or wasn't) reported.  Replaces the
# per-path ad-hoc ``set`` registries that each fallback used to grow.
# BOUNDED at :data:`_WARN_ONCE_MAX` entries (FIFO eviction): a process
# solving an unbounded stream of distinct shapes — the serve loop, a
# long sweep — must not grow a per-shape table forever.  Evicting an old
# key merely re-arms its warning, which is harmless.
_WARN_ONCE: Dict[Tuple, str] = {}

#: Capacity of the warn-once table; far above any test or benchmark's
#: distinct-shape count, far below anything that could matter for RSS.
_WARN_ONCE_MAX = 256


def _warn_once(key: Tuple, message: str, stacklevel: int = 4) -> None:
    """Emit ``message`` as a UserWarning once per ``key``."""
    if key in _WARN_ONCE:
        return
    while len(_WARN_ONCE) >= _WARN_ONCE_MAX:
        _WARN_ONCE.pop(next(iter(_WARN_ONCE)))  # FIFO: dicts keep order
    _WARN_ONCE[key] = message
    warnings.warn(message, stacklevel=stacklevel)


def reset_warnings() -> None:
    """Clear the warn-once table so every fallback warning re-arms.

    The supported test/REPL hook for re-observing a routing-fallback
    warning (``pytest.warns`` blocks around a shape that already warned
    earlier in the process) — clears only warning dedup state, never
    routing or compile caches.
    """
    _WARN_ONCE.clear()


#: Fault-recovery routing: the backend a faulted dispatch round retries
#: on.  Only twins appear — the pallas kernels and the xla drivers run
#: the same ``core/engine.py`` / ``core/revised.py`` blocks and their
#: resume states are interchangeable, so a retry on the twin continues
#: the carried state.  On CPU the twins are bit-identical; on a TPU v5e
#: only single-phase tableau solves are, because Mosaic and XLA round
#: the phase-II objective and pricing matmuls differently (``PERF.md``).
#: Backends with no twin (``xla``,
#: ``pdhg``, ``reference``) retry in place: a different-tolerance
#: substitute would silently change answers, which a fault must never do.
FAULT_FALLBACKS = {"pallas": "xla", "pallas-shared": "xla-shared"}


def fault_fallback(name: str) -> str:
    """The backend name a faulted round of ``name`` should retry on.

    Returns ``name`` itself when no twin exists (see
    :data:`FAULT_FALLBACKS`); warns once per rerouted backend through
    the same warn-once table as the VMEM fallbacks.
    """
    target = FAULT_FALLBACKS.get(name, name)
    if target != name:
        _warn_once(
            ("fault-fallback", name),
            f"{name} backend: dispatch fault — retrying the round from "
            f"its carried resume state on the {target} backend (its twin)",
        )
    return target


def _pallas_vmem_fallback(
    m: int, n: int, dtype, options: SolveOptions, layout: Optional[str] = None
) -> Optional[str]:
    """The backend name this shape must route to, or None to run the kernel.

    A shape whose smallest legal tile (8 LPs, ``kernels/ops.py:MIN_TILE_B``)
    exceeds the kernel's VMEM budget cannot run as a Pallas tile — it
    would fail inside Mosaic.  The fallback consults the shape-routing table
    (:func:`route_shape`): below the routing frontier the substitute is
    ``xla`` (bit-identical results — both simplex backends drive the
    same ``core/engine.py`` blocks, and their resume states are
    interchangeable); at or past it the substitute is the first-order
    ``pdhg`` backend, whose O(m n) state is why the shape overflowed a
    tableau in the first place (results then carry pdhg's tolerance
    semantics — the warning says which backend was chosen).

    ``layout`` overrides ``options.layout`` for the footprint estimate —
    a resume runs in the layout of its CARRIED state, which a cross-
    layout caller's options need not match.
    """
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    layout = layout or options.effective_layout
    # want_state=True is the conservative (largest-footprint) estimate, so
    # the start/resume rounds of a basis-resumed solve route consistently.
    if kernel_ops.fits_vmem(m, n, dtype, layout, want_state=True):
        return None
    target = route_shape(m, n, dtype, options, layout=layout)
    if target == "pallas":  # the table can't re-route here: it won't fit
        target = "xla"
    fidelity = (
        "bit-identical results"
        if target == "xla"
        else "first-order results at pdhg_tol accuracy"
    )
    per_lp = kernel_ops.kernel_vmem_bytes_per_lp(
        TableauSpec(m, n, layout), dtype, want_state=True
    )
    budget = int(kernel_ops.VMEM_BUDGET_BYTES * kernel_ops.VMEM_TILE_FRACTION)
    dtype_str = str(jnp.dtype(dtype))
    _warn_once(
        ("pallas-vmem", m, n, dtype_str, layout),
        f"pallas backend: tableau for shape (m={m}, n={n}, "
        f"{dtype_str}, layout={layout!r}) needs {per_lp} VMEM bytes/LP "
        f"against the {budget}-byte per-tile budget, which must hold "
        f"{kernel_ops.MIN_TILE_B} LPs "
        f"({kernel_ops.VMEM_BUDGET_BYTES} total x "
        f"{kernel_ops.VMEM_TILE_FRACTION} tile fraction); routing to the "
        f"{target} backend ({fidelity})",
    )
    return target


def _pallas_solve(
    batch: LPBatch, options: SolveOptions, want_state: bool = False
):
    fallback = _pallas_vmem_fallback(batch.m, batch.n, batch.a.dtype, options)
    if fallback == "pdhg":
        return _pdhg_solve(batch, options, want_state)
    if fallback is not None:
        return _xla_solve(batch, options, want_state)
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    return kernel_ops.simplex_solve(
        batch.a,
        batch.b,
        batch.c,
        rule=options.rule,
        max_iters=options.max_iters,
        seed=options.seed,
        tol=options.tolerance,
        basis0=batch.basis0,
        want_state=want_state,
        dynamic_cap=options.dynamic_caps,
        layout=options.effective_layout,
        tile_b=options.tile_b,
    )


def _pallas_start(batch: LPBatch, options: SolveOptions):
    return _pallas_solve(batch, options, want_state=True)


def _pallas_resume(batch: LPBatch, state: ResumeState, options: SolveOptions):
    # A solve the fallback routed to pdhg hands back a PDHGResumeState;
    # continue it on the pdhg backend (a first-order state has no tableau
    # to sniff a layout from).
    if isinstance(state, _pdhg.PDHGResumeState):
        return _pdhg_resume(batch, state, options)
    # The resume runs in the layout of the CARRIED state (recovered from
    # the tableau width), not options.layout — route on that layout so a
    # cross-layout resume can't sneak an over-budget tableau past the
    # check (or needlessly fall back when the carried layout fits).
    state_layout = TableauSpec.from_tableau(
        batch.m, batch.n, state.tab.shape[-1]
    ).layout
    if _pallas_vmem_fallback(
        batch.m, batch.n, batch.a.dtype, options, layout=state_layout
    ):
        # A carried simplex tableau can only continue on a simplex
        # driver, whatever the routing table says for cold solves.
        return _xla_resume(batch, state, options)
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    return kernel_ops.simplex_resume(
        batch.b,
        batch.c,
        state,
        rule=options.rule,
        max_iters=options.max_iters,
        seed=options.seed,
        tol=options.tolerance,
        tile_b=options.tile_b,
        want_state=True,
        dynamic_cap=options.dynamic_caps,
    )


def _pallas_init(batch: LPBatch, options: SolveOptions) -> ResumeState:
    # The simplex backends share one tableau builder and one engine, and
    # their resume states are interchangeable — so the iteration-0 state
    # is built by the XLA driver and the kernel continues it.  A shape the
    # VMEM fallback routes to pdhg gets a pdhg state instead (the resume
    # hook type-sniffs the state, so the whole solve stays on one driver).
    fallback = _pallas_vmem_fallback(batch.m, batch.n, batch.a.dtype, options)
    if fallback == "pdhg":
        return _pdhg_init(batch, options)
    return _xla_init(batch, options)


def _pallas_cache_size() -> int:
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    # Include the fallback targets' caches: the VMEM fallback routes
    # over-budget shapes through _xla_solve/_xla_resume or the pdhg
    # backend, and their compiles must stay visible to SolveStats'
    # compiles/cache_hits attribution (for pure-kernel traffic the other
    # terms are constant, so the diff the dispatch layer takes is
    # unchanged).
    return (
        kernel_ops.compile_cache_size()
        + _simplex.compile_cache_size()
        + _pdhg_cache_size()
    )


def _pallas_hyperbox(lo, hi, directions, options: SolveOptions) -> LPSolution:
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    from .lp import OPTIMAL

    obj = kernel_ops.hyperbox_support(lo, hi, directions)
    pick = jnp.where(directions < 0, lo, hi)
    bsz = obj.shape[0]
    return LPSolution(
        objective=obj,
        x=pick,
        status=jnp.full((bsz,), OPTIMAL, jnp.int32),
        iterations=jnp.zeros((bsz,), jnp.int32),
    )


# The pdhg backend has two drivers behind one step function
# (core/pdhg.py:pdhg_step): the XLA while_loop driver everywhere, the
# VMEM-resident Pallas kernel (kernels/pdhg_pallas.py) on TPU when the
# O(m n) data block fits the budget.  Unlike the simplex pair the two are
# not bit-identical (matvec reduction order differs), so the choice is
# per-platform, never per-call: every round of one solve uses one driver.


def _pdhg_use_kernel(m: int, n: int, dtype) -> bool:
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    if not kernel_ops._on_tpu():
        return False
    if kernel_ops.pdhg_fits_vmem(m, n, dtype):
        return True
    # On TPU but over budget: the XLA while_loop driver takes over.  Same
    # step function, but matvec reduction order differs — worth one
    # warning per shape (through the module-wide warn-once table) since
    # the driver choice is observable in the last ulp of the results.
    per_lp = kernel_ops.pdhg_vmem_bytes_per_lp(m, n, dtype)
    budget = int(kernel_ops.VMEM_BUDGET_BYTES * kernel_ops.VMEM_TILE_FRACTION)
    dtype_str = str(jnp.dtype(dtype))
    _warn_once(
        ("pdhg-kernel", m, n, dtype_str),
        f"pdhg backend: per-LP kernel state for shape (m={m}, n={n}, "
        f"{dtype_str}) needs {per_lp} VMEM bytes/LP against the "
        f"{budget}-byte per-tile budget; running the XLA while_loop "
        f"driver instead (same pdhg_step, different matvec reduction "
        f"order)",
    )
    return False


def _pdhg_solve(
    batch: LPBatch, options: SolveOptions, want_state: bool = False
):
    # basis0 is a simplex warm-start hint; a first-order method has no
    # basis to warm from, so it is ignored per the backend contract.
    kw = dict(
        tol=options.pdhg_tol,
        restart=options.pdhg_restart,
        max_iters=options.max_iters,
        want_state=want_state,
        dynamic_cap=options.dynamic_caps,
    )
    if _pdhg_use_kernel(batch.m, batch.n, batch.a.dtype):
        from ..kernels import ops as kernel_ops

        return kernel_ops.pdhg_solve(
            batch.a, batch.b, batch.c, tile_b=options.tile_b, **kw
        )
    return _pdhg.solve_batched(batch.a, batch.b, batch.c, **kw)


def _pdhg_start(batch: LPBatch, options: SolveOptions):
    return _pdhg_solve(batch, options, want_state=True)


def _pdhg_resume(
    batch: LPBatch, state: "_pdhg.PDHGResumeState", options: SolveOptions
):
    # Unlike the simplex resume, pdhg reads batch.a every step (the
    # matvecs) — the dispatch layer always passes the full batch back.
    kw = dict(
        tol=options.pdhg_tol,
        restart=options.pdhg_restart,
        max_iters=options.max_iters,
        want_state=True,
        dynamic_cap=options.dynamic_caps,
    )
    if _pdhg_use_kernel(batch.m, batch.n, batch.a.dtype):
        from ..kernels import ops as kernel_ops

        return kernel_ops.pdhg_resume(
            batch.a, batch.b, batch.c, state, tile_b=options.tile_b, **kw
        )
    return _pdhg.resume_batched(batch.a, batch.b, batch.c, state, **kw)


def _pdhg_init(batch: LPBatch, options: SolveOptions) -> "_pdhg.PDHGResumeState":
    # The pdhg cold solve is literally `iterate(a, b, c, init_state(...))`,
    # so resuming the all-zeros state replays it bit-identically.  basis0
    # is a simplex hint; ignored here per the backend contract.
    return _pdhg.init_state(batch.batch, batch.m, batch.n, batch.a.dtype)


def _pdhg_cache_size() -> int:
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    return _pdhg.compile_cache_size() + kernel_ops.pdhg_compile_cache_size()


# The shared backends consume SharedLPBatch: ONE (m, n) constraint
# matrix read-shared by every LP, per-LP c/b, and the revised-simplex
# engine (core/revised.py) that keeps only the O(m^2) basis-inverse
# record per LP.  Same solve/start/resume/init protocol as the tableau
# backends — RevisedResumeState rides the generic tree_map plumbing of
# the dispatch layer — so compaction rounds, sessions, and the
# continuous serve loop work unchanged.


def _xla_shared_solve(
    batch: SharedLPBatch, options: SolveOptions, want_state: bool = False
):
    return _revised.solve_batched(
        batch.a,
        batch.b,
        batch.c,
        rule=options.rule,
        max_iters=options.max_iters,
        seed=options.seed,
        unroll=options.unroll,
        tol=options.tolerance,
        basis0=batch.basis0,
        want_state=want_state,
        dynamic_cap=options.dynamic_caps,
    )


def _xla_shared_start(batch: SharedLPBatch, options: SolveOptions):
    return _xla_shared_solve(batch, options, want_state=True)


def _xla_shared_resume(
    batch: SharedLPBatch, state: "_revised.RevisedResumeState",
    options: SolveOptions,
):
    # Unlike the tableau resume (which re-reads A from the carried
    # tableau), the revised engine prices against the shared A every
    # step — the dispatch layer always passes the batch back whole.
    return _revised.resume_batched(
        batch.a,
        batch.b,
        batch.c,
        state,
        rule=options.rule,
        max_iters=options.max_iters,
        seed=options.seed,
        unroll=options.unroll,
        tol=options.tolerance,
        want_state=True,
        dynamic_cap=options.dynamic_caps,
    )


def _xla_shared_init(
    batch: SharedLPBatch, options: SolveOptions
) -> "_revised.RevisedResumeState":
    return _revised.init_batched(
        batch.a, batch.b, batch.c, basis0=batch.basis0
    )


def _pallas_shared_fallback(m: int, n: int, dtype) -> bool:
    """Whether the pallas-shared kernel must fall back to xla-shared.

    The revised kernel holds the shared A tile plus each LP's basis
    inverse in VMEM; a shape whose single-LP footprint exceeds the
    budget runs the XLA driver instead (bit-identical — both drive the
    same pricing/ratio/update formulas in the same order).
    """
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    if kernel_ops.revised_fits_vmem(m, n, dtype):
        return False
    per_lp = kernel_ops.revised_vmem_bytes_per_lp(m, n, dtype)
    budget = int(kernel_ops.VMEM_BUDGET_BYTES * kernel_ops.VMEM_TILE_FRACTION)
    dtype_str = str(jnp.dtype(dtype))
    _warn_once(
        ("pallas-shared-vmem", m, n, dtype_str),
        f"pallas-shared backend: shared-A block plus per-LP basis state "
        f"for shape (m={m}, n={n}, {dtype_str}) needs {per_lp} VMEM "
        f"bytes/LP against the {budget}-byte per-tile budget; routing "
        f"to the xla-shared backend (bit-identical results)",
    )
    return True


def _pallas_shared_solve(
    batch: SharedLPBatch, options: SolveOptions, want_state: bool = False
):
    if _pallas_shared_fallback(batch.m, batch.n, batch.a.dtype):
        return _xla_shared_solve(batch, options, want_state)
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    return kernel_ops.revised_solve(
        batch.a,
        batch.b,
        batch.c,
        rule=options.rule,
        max_iters=options.max_iters,
        seed=options.seed,
        tol=options.tolerance,
        tile_b=options.tile_b,
        basis0=batch.basis0,
        want_state=want_state,
        dynamic_cap=options.dynamic_caps,
    )


def _pallas_shared_start(batch: SharedLPBatch, options: SolveOptions):
    return _pallas_shared_solve(batch, options, want_state=True)


def _pallas_shared_resume(
    batch: SharedLPBatch, state: "_revised.RevisedResumeState",
    options: SolveOptions,
):
    if _pallas_shared_fallback(batch.m, batch.n, batch.a.dtype):
        return _xla_shared_resume(batch, state, options)
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    return kernel_ops.revised_resume(
        batch.a,
        batch.b,
        batch.c,
        state,
        rule=options.rule,
        max_iters=options.max_iters,
        seed=options.seed,
        tol=options.tolerance,
        tile_b=options.tile_b,
        want_state=True,
        dynamic_cap=options.dynamic_caps,
    )


def _pallas_shared_init(
    batch: SharedLPBatch, options: SolveOptions
) -> "_revised.RevisedResumeState":
    # Iteration-0 state is pure setup (no pivots): built by the XLA
    # driver, continued by whichever driver the shape routes to — the
    # same split the tableau pallas backend uses.
    return _xla_shared_init(batch, options)


def _pallas_shared_cache_size() -> int:
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    # Include the XLA driver's cache: the VMEM fallback and the init
    # hook both compile through it (see _pallas_cache_size).
    return (
        kernel_ops.revised_compile_cache_size()
        + _revised.compile_cache_size()
    )


def _reference_solve(batch: LPBatch, options: SolveOptions) -> LPSolution:
    # The oracle has no warm-start path; batch.basis0 is ignored (a warm
    # start is a hint) and LPSolution.basis stays None.
    from . import oracle  # lazy: keep the hot import path lean

    obj, xs, status, iters = oracle.solve_batch(
        np.asarray(batch.a),
        np.asarray(batch.b),
        np.asarray(batch.c),
        max_iters=options.max_iters,
    )
    dtype = batch.a.dtype
    return LPSolution(
        objective=jnp.asarray(obj, dtype),
        x=jnp.asarray(xs, dtype),
        status=jnp.asarray(status, jnp.int32),
        iterations=jnp.asarray(iters, jnp.int32),
    )


def _reference_hyperbox(lo, hi, directions, options: SolveOptions) -> LPSolution:
    from . import oracle
    from .lp import OPTIMAL

    support, pick = oracle.solve_hyperbox(
        np.asarray(lo), np.asarray(hi), np.asarray(directions)
    )
    dtype = jnp.asarray(directions).dtype
    bsz = support.shape[0]
    return LPSolution(
        objective=jnp.asarray(support, dtype),
        x=jnp.asarray(pick, dtype),
        status=jnp.full((bsz,), OPTIMAL, jnp.int32),
        iterations=jnp.zeros((bsz,), jnp.int32),
    )


register_backend(
    Backend(
        "xla",
        _xla_solve,
        _xla_hyperbox,
        start_canonical=_xla_start,
        resume_canonical=_xla_resume,
        init_canonical=_xla_init,
        cache_size=_simplex.compile_cache_size,
    )
)
register_backend(
    Backend(
        "pallas",
        _pallas_solve,
        _pallas_hyperbox,
        start_canonical=_pallas_start,
        resume_canonical=_pallas_resume,
        init_canonical=_pallas_init,
        cache_size=_pallas_cache_size,
    )
)
# Box problems are closed-form (no iteration at all) — the first-order
# backend routes its hyperbox leg straight to the xla implementation.
register_backend(
    Backend(
        "pdhg",
        _pdhg_solve,
        _xla_hyperbox,
        start_canonical=_pdhg_start,
        resume_canonical=_pdhg_resume,
        init_canonical=_pdhg_init,
        cache_size=_pdhg_cache_size,
        auto_cap=_pdhg.auto_cap_pdhg,
    )
)
# The shared pair consumes SharedLPBatch (one A, batched c/b) through
# the revised-simplex engine; plain LPBatch traffic never routes here
# (the dispatch layer raises instead of silently replicating A).
register_backend(
    Backend(
        "xla-shared",
        _xla_shared_solve,
        _xla_hyperbox,
        start_canonical=_xla_shared_start,
        resume_canonical=_xla_shared_resume,
        init_canonical=_xla_shared_init,
        cache_size=_revised.compile_cache_size,
    )
)
register_backend(
    Backend(
        "pallas-shared",
        _pallas_shared_solve,
        _pallas_hyperbox,
        start_canonical=_pallas_shared_start,
        resume_canonical=_pallas_shared_resume,
        init_canonical=_pallas_shared_init,
        cache_size=_pallas_shared_cache_size,
    )
)
# The float64 oracle neither tracks mid-solve state nor compiles anything:
# resume="basis" on it falls back to scratch rounds in the dispatch layer.
register_backend(Backend("reference", _reference_solve, _reference_hyperbox))

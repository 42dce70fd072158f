"""Cost-model autotuner: per-shape-class config selection with a winner cache.

Every performance knob the solver grew — backend (``xla``/``pallas``/
``pdhg``/shared twins), tableau layout (``dense``/``compact``), the
Pallas batch tile ``tile_b`` — used to be a hand-picked default.  This
module owns that knob space per ``(m, n, batch-class, dtype)`` shape
class, in three stages:

1. **Predict** — rank every feasible candidate config by a static cost
   model: the analytic per-iteration roofline
   (``runtime/roofline.py:iteration_profile``) under TPU v5e-class
   machine constants, optionally refined by HLO-derived
   ``dot_flops``/``traffic_bytes`` from a compiled executable
   (:func:`hlo_profile`, via ``launch/hlo_stats.py``).  Feasibility —
   including the PR 5 VMEM-budget rule that used to live as a special
   pallas→xla fallback — is a constraint here (:func:`feasible`), not a
   separate code path.  Prediction is pure: no disk, no device work.
2. **Trial** — optionally confirm the predicted top-k by timed
   micro-solves on the real shape (``autotune="trial"``), so a measured
   winner can overrule the model.
3. **Cache** — persist measured winners in an on-disk JSON cache keyed
   like the compile cache (shape class + dtype + platform + VMEM budget,
   schema-versioned), written torn-write-safe with the
   ``ckpt/checkpoint.py`` tmp+rename pattern — a warm process resolves
   every shape class with zero micro-trials.

The tuner is the DEFAULT resolution path:
``SolveOptions(backend="auto", layout=None, tile_b=None)`` consults it
through ``core/dispatch.py:resolve_backend`` /
``core/backends.py:route_shape``, ``kernels/ops.py:auto_tile_b`` asks
:func:`cached_tile_b` for a measured tile before falling back to the
VMEM heuristic, and ``SolveSession.resolve_options`` pins the tuned
config per shape class for the session's lifetime.  In the default
``"predict"`` mode the ranking reproduces the static routing table
exactly (frontier gate, VMEM feasibility, compact layout, max fitting
tile) — the tuner changes WHICH config runs only when a measured trial
says so, and never the per-LP results a given config produces.

Decisions are observable (``SolveStats.autotuned`` + per-decision
``SolveStats.autotune_log`` rows with predicted vs measured cost), and
:func:`warm` exposes explicit offline tuning (``repro.autotune.warm``).

Semantics note: the simplex-vs-``pdhg`` frontier
(``SolveOptions.route_frontier``) stays a CONSTRAINT, not a ranked knob
— crossing it changes answer semantics (pdhg_tol accuracy vs exact
vertices), and an autotuner must never trade accuracy for speed.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bucketing import next_pow2
from ..core.tableau import DEFAULT_LAYOUT, LAYOUTS, TableauSpec
from .roofline import HBM_BW, PEAK_FLOPS, iteration_profile

#: Bump when the cache entry format or the cost model changes shape —
#: a file with any other schema is ignored wholesale (stale winners are
#: worse than a re-tune).
SCHEMA_VERSION = 1

#: Valid values of ``SolveOptions.autotune``.
MODES = ("off", "predict", "trial")

#: Environment override for the on-disk winner cache location.
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

#: Backends the tuner enumerates candidates for; anything else (the
#: ``reference`` oracle, plug-ins) passes through untouched.
TUNABLE_BACKENDS = ("xla", "pallas", "pdhg", "xla-shared", "pallas-shared")

#: Kernel backends whose per-LP state is VMEM-resident for the whole
#: solve: their state streams HBM once per round, not once per
#: iteration, which is the model's reason to prefer them when feasible.
VMEM_RESIDENT = ("pallas", "pallas-shared")

#: Modeled per-kernel-launch overhead (seconds per grid step) — breaks
#: ties toward larger tiles, matching the VMEM heuristic's preference.
LAUNCH_OVERHEAD_S = 2e-6

#: Batch class assumed when the caller resolves without a batch in hand.
DEFAULT_BATCH_CLASS = 1024


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One resolved configuration choice for a shape class.

    Attributes
    ----------
    backend : str
        Concrete backend name.
    layout : str, optional
        Tableau layout for the simplex backends; None where the knob is
        meaningless (``pdhg``, shared twins, plug-ins).
    tile_b : int, optional
        Pallas batch tile; None leaves the kernel's VMEM heuristic
        (``kernels/ops.py:auto_tile_b``) in charge.
    predicted_s : float, optional
        Modeled solve seconds for the batch (the ranking score).
    measured_s : float, optional
        Micro-trial seconds of the winner, when one ran.
    source : str
        ``"predicted"`` | ``"measured"`` | ``"cache"`` — how the choice
        was reached, recorded into ``SolveStats.autotune_log``.
    """

    backend: str
    layout: Optional[str] = None
    tile_b: Optional[int] = None
    predicted_s: Optional[float] = None
    measured_s: Optional[float] = None
    source: str = "predicted"


def default_cache_path() -> str:
    """The winner-cache file: ``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache``."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "autotune.json"
    )


def cache_key(
    m: int, n: int, batch: Optional[int], dtype, shared: bool = False
) -> str:
    """Shape-class cache key, built like the compile cache's.

    Power-of-two size classes (``core/bucketing.py``) so every shape in a
    bucket shares one entry; platform and the (env-overridable) VMEM
    budget are part of the key because they decide pallas feasibility —
    a winner tuned on TPU must not be served to a CPU process.
    """
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    bc = next_pow2(batch) if batch else DEFAULT_BATCH_CLASS
    kind = "shared" if shared else "lp"
    return (
        f"{jax.default_backend()}|vmem{kernel_ops.VMEM_BUDGET_BYTES}|{kind}"
        f"|m{next_pow2(m)}|n{next_pow2(n)}|b{bc}|{np.dtype(dtype).name}"
    )


def expected_iterations(backend: str, m: int, n: int) -> float:
    """Expected lockstep iterations to convergence for the cost model.

    Simplex paths use the ``2 (m + n)`` expected-pivot rule the repo
    already budgets oracle re-solves with (quarantine/crossover); pdhg
    assumes a quarter of its auto cap (restarted first-order methods
    rarely run to the ``ITER_LIMIT`` budget on feasible LPs).  Only the
    RELATIVE per-candidate cost matters for ranking — candidates of one
    shape class share the iteration estimate within their family, and
    the simplex/pdhg families are never ranked against each other (the
    frontier is a semantic constraint).
    """
    if backend == "pdhg":
        from ..core.pdhg import auto_cap_pdhg

        return 0.25 * auto_cap_pdhg(m, n)
    return 2.0 * (m + n)


def _profile_kind(backend: str, layout: Optional[str]) -> str:
    if backend == "pdhg":
        return "pdhg"
    if backend.endswith("-shared"):
        return "shared"
    return layout or DEFAULT_LAYOUT


def predict_cost(
    backend: str,
    layout: Optional[str],
    tile_b: Optional[int],
    m: int,
    n: int,
    batch: int,
    dtype,
    features: Optional[Dict[str, float]] = None,
) -> float:
    """Modeled wall seconds to solve one ``batch`` of this shape.

    Per-iteration FLOPs/bytes come from the analytic roofline
    (``runtime/roofline.py``); ``features`` — an :func:`hlo_profile`
    record — substitutes HLO-measured per-iteration numbers when the
    caller compiled one.  VMEM-resident kernels charge their state
    stream once per solve instead of once per iteration (that residency
    is the point of the kernels), plus a per-grid-step launch overhead
    so larger feasible tiles rank better.
    """
    kind = _profile_kind(backend, layout)
    item = np.dtype(dtype).itemsize
    # the shared-A amortization tile: the XLA driver prices the whole
    # batch against A in one GEMM, the kernel per VMEM tile.
    prof_tile = tile_b or (batch if kind == "shared" else 1)
    prof = iteration_profile(kind, m, n, tile_b=max(prof_tile, 1), dtype_bytes=item)
    flops = prof["flops"]
    byts = prof["bytes"]
    if features is not None:
        flops = max(flops, features.get("dot_flops_per_iter", 0.0) / max(batch, 1))
        measured_bytes = features.get("traffic_bytes_per_iter", 0.0) / max(batch, 1)
        if measured_bytes > 0.0:
            byts = measured_bytes
    iters = expected_iterations(backend, m, n)
    flop_s = flops / PEAK_FLOPS
    byte_s = byts / HBM_BW
    if backend in VMEM_RESIDENT:
        per_lp = iters * flop_s + byte_s  # state streams HBM once per solve
    else:
        per_lp = iters * max(flop_s, byte_s)  # roofline: bound by the max
    seconds = per_lp * max(batch, 1)
    if tile_b:
        seconds += LAUNCH_OVERHEAD_S * math.ceil(max(batch, 1) / tile_b)
    return seconds


def feasible(
    backend: str, layout: Optional[str], tile_b: Optional[int], m: int, n: int, dtype
) -> bool:
    """Whether this candidate can run AT ALL on this platform and shape.

    This is where the PR 5 VMEM-fallback heuristic lives now: the same
    ``fits_vmem`` / ``revised_fits_vmem`` predicates (conservative
    ``want_state=True`` footprint) that used to be a special pallas→xla
    reroute are a constraint the candidate enumeration applies up front.
    The dispatch-time fallback in ``core/backends.py`` remains as the
    safety net for explicitly pinned ``backend="pallas"`` calls that
    bypass the tuner.
    """
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    if backend == "pallas":
        lay = layout or DEFAULT_LAYOUT
        if not (
            kernel_ops._on_tpu()
            and kernel_ops.fits_vmem(m, n, dtype, lay, want_state=True)
        ):
            return False
        if tile_b:
            per_lp = kernel_ops.kernel_vmem_bytes_per_lp(
                TableauSpec(m, n, lay), dtype, want_state=True
            )
            budget = int(
                kernel_ops.VMEM_BUDGET_BYTES * kernel_ops.VMEM_TILE_FRACTION
            )
            return tile_b * per_lp <= budget
        return True
    if backend == "pallas-shared":
        return kernel_ops._on_tpu() and kernel_ops.revised_fits_vmem(m, n, dtype)
    return True


def _tile_candidates(
    backend: str, m: int, n: int, batch: int, dtype, layout: Optional[str]
) -> List[Optional[int]]:
    """Tile values worth ranking for one backend (None = kernel default)."""
    from ..kernels import ops as kernel_ops  # lazy: pulls in Pallas

    if backend == "pallas":
        spec = TableauSpec(m, n, layout or DEFAULT_LAYOUT)
        top = kernel_ops.auto_tile_b(batch, spec, dtype, want_state=True)
    elif backend == "pallas-shared":
        top = kernel_ops.revised_auto_tile_b(batch, m, n, dtype)
    else:
        return [None]
    tiles = {kernel_ops.legal_tile_b(max(1, top // k), batch) for k in (1, 2, 4)}
    return sorted(tiles, reverse=True)


def candidate_configs(
    m: int,
    n: int,
    batch: Optional[int],
    dtype,
    options,
    shared: bool = False,
) -> List[Tuple[str, Optional[str], Optional[int]]]:
    """Enumerate the feasible ``(backend, layout, tile_b)`` candidates.

    Explicit pins in ``options`` (a concrete ``backend``, a non-None
    ``layout`` or ``tile_b``) restrict their dimension — the tuner fills
    gaps, it never overrides the caller.  ``backend="auto"`` enumerates
    the simplex twins below the routing frontier and ``pdhg`` alone at
    or above it (the frontier is a semantics boundary, see module
    docstring).  Candidates that cannot run here (:func:`feasible`) are
    dropped; if NOTHING survives — e.g. a pinned ``pallas`` over the
    VMEM budget — the static pin is returned alone so dispatch-time
    fallbacks keep owning that case.
    """
    from ..core import backends as _backends

    batch = batch or DEFAULT_BATCH_CLASS
    pinned = None if options.backend == "auto" else options.backend
    if pinned is not None and pinned not in TUNABLE_BACKENDS:
        return [(pinned, options.layout, options.tile_b)]
    if pinned is not None:
        names = [pinned]
    elif shared:
        names = ["xla-shared", "pallas-shared"]
    else:
        frontier = options.route_frontier or _backends.DEFAULT_ROUTE_FRONTIER
        names = ["pdhg"] if max(m, n) >= frontier else ["xla", "pallas"]
    out: List[Tuple[str, Optional[str], Optional[int]]] = []
    for name in names:
        if name in ("xla", "pallas"):
            layouts = [options.layout] if options.layout else list(LAYOUTS)
        else:
            layouts = [None]
        for layout in layouts:
            if options.tile_b is not None:
                tiles: List[Optional[int]] = [options.tile_b]
            else:
                tiles = _tile_candidates(name, m, n, batch, dtype, layout)
            for tile in tiles:
                if feasible(name, layout, tile, m, n, dtype):
                    out.append((name, layout, tile))
    if not out:
        out = [(pinned or "xla", options.layout, options.tile_b)]
    return out


def rank_candidates(
    m: int,
    n: int,
    batch: Optional[int],
    dtype,
    options,
    shared: bool = False,
    features: Optional[Dict[str, Dict[str, float]]] = None,
) -> List[TunedConfig]:
    """Candidates ordered by predicted cost (cheapest first).

    ``features`` maps a layout name to an :func:`hlo_profile` record;
    matching simplex candidates are scored on the measured traffic
    instead of the analytic estimate.  Ties break deterministically on
    the candidate tuple so ranking never depends on dict order.
    """
    bsz = batch or DEFAULT_BATCH_CLASS
    scored = []
    for name, layout, tile in candidate_configs(m, n, batch, dtype, options, shared):
        feat = None
        if features and name == "xla":
            feat = features.get(layout or DEFAULT_LAYOUT)
        cost = predict_cost(name, layout, tile, m, n, bsz, dtype, features=feat)
        scored.append(
            TunedConfig(name, layout, tile, predicted_s=cost, source="predicted")
        )
    scored.sort(
        key=lambda c: (c.predicted_s, c.backend, c.layout or "", c.tile_b or 0)
    )
    return scored


def hlo_profile(
    m: int,
    n: int,
    batch: int = 4,
    dtype=jnp.float32,
    layout: Optional[str] = None,
    caps: Tuple[int, int] = (8, 24),
) -> Dict[str, float]:
    """HLO-derived per-iteration cost features for the XLA simplex driver.

    Lowers and compiles the driver at two STATIC iteration caps (the
    while-loop condition then compares against a literal, which is what
    ``launch/hlo_stats.py:analyze`` recovers trip counts from) and
    differences the loop-aware ``dot_flops`` / ``traffic_bytes`` totals,
    isolating the per-iteration cost from one-time setup.  Whole-batch
    numbers — divide by ``batch`` for per-LP features.  Compiling costs
    real time, so this feeds :func:`warm` and ``feature_source="hlo"``
    tuners, never the default predict path.
    """
    from ..core import simplex as _simplex
    from ..launch import hlo_stats

    lay = layout or DEFAULT_LAYOUT
    shapes = [
        jax.ShapeDtypeStruct((batch, m, n), dtype),
        jax.ShapeDtypeStruct((batch, m), dtype),
        jax.ShapeDtypeStruct((batch, n), dtype),
    ]
    totals = []
    for cap in caps:

        def run(a, b, c, cap=cap):
            return _simplex.solve_batched(
                a, b, c, max_iters=cap, dynamic_cap=False, layout=lay
            )

        text = jax.jit(run).lower(*shapes).compile().as_text()
        totals.append(hlo_stats.analyze(text))
    span = float(caps[1] - caps[0])
    return {
        "dot_flops_per_iter": (
            totals[1]["dot_flops"] - totals[0]["dot_flops"]
        )
        / span,
        "traffic_bytes_per_iter": (
            totals[1]["traffic_bytes"] - totals[0]["traffic_bytes"]
        )
        / span,
        "dot_flops": float(totals[1]["dot_flops"]),
        "traffic_bytes": float(totals[1]["traffic_bytes"]),
        "caps": [float(caps[0]), float(caps[1])],
    }


class TuningCache:
    """Torn-write-safe JSON winner cache (the checkpoint tmp+rename rule).

    The file is ``{"schema": N, "entries": {key: entry}}``; a corrupt,
    truncated, or schema-mismatched file reads as EMPTY — the tuner then
    falls back to prediction and the next :meth:`store` rewrites a valid
    file.  Writes go to ``<path>.tmp`` then :func:`os.replace` (atomic
    on POSIX), exactly like ``ckpt/checkpoint.py``, so a reader never
    observes a half-written file; concurrent writers are last-wins,
    which is safe because entries are idempotent measurements.
    """

    def __init__(self, path: str):
        self.path = path
        self._entries: Optional[Dict[str, dict]] = None

    def _read(self) -> Dict[str, dict]:
        try:
            with open(self.path) as f:
                data = json.load(f)
        except FileNotFoundError:
            return {}
        except (OSError, ValueError, UnicodeDecodeError):
            # corrupt / torn / unreadable: behave as empty, never crash
            return {}
        if not isinstance(data, dict) or data.get("schema") != SCHEMA_VERSION:
            return {}  # schema bump invalidates every stale entry
        entries = data.get("entries")
        return entries if isinstance(entries, dict) else {}

    def load(self) -> Dict[str, dict]:
        """Entries, read once and memoized for the process lifetime."""
        if self._entries is None:
            self._entries = self._read()
        return self._entries

    def lookup(self, key: str) -> Optional[dict]:
        """The stored entry for a shape-class key, or None."""
        entry = self.load().get(key)
        if isinstance(entry, dict) and isinstance(entry.get("backend"), str):
            return entry
        return None

    def store(self, key: str, entry: dict) -> None:
        """Merge one winner into the file atomically (tmp then rename)."""
        entries = dict(self._read())  # merge with any concurrent writer
        entries[key] = entry
        self._entries = entries
        directory = os.path.dirname(self.path)
        if directory:
            os.makedirs(directory, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"schema": SCHEMA_VERSION, "entries": entries}, f, indent=2)
        os.replace(tmp, self.path)


class Autotuner:
    """The per-process config selector: predict, optionally trial, cache.

    Parameters
    ----------
    cache_path : str, optional
        Winner-cache file (default :func:`default_cache_path`).  Only
        ``autotune="trial"`` resolutions touch it; prediction is pure.
    top_k : int, default 3
        Predicted-best candidates confirmed by micro-trials.
    trial_batch : int, default 8
        LPs per micro-trial (clamped to the real batch when smaller).
    trial_repeats : int, default 3
        Timed repetitions per candidate (minimum wins) after one
        warmup/compile run.
    feature_source : str, default "analytic"
        ``"analytic"`` scores candidates from the roofline model alone;
        ``"hlo"`` additionally compiles the XLA driver once per layout
        and scores on measured ``traffic_bytes`` (:func:`hlo_profile`).
    """

    def __init__(
        self,
        cache_path: Optional[str] = None,
        top_k: int = 3,
        trial_batch: int = 8,
        trial_repeats: int = 3,
        feature_source: str = "analytic",
    ):
        self.cache = TuningCache(cache_path or default_cache_path())
        self.top_k = top_k
        self.trial_batch = trial_batch
        self.trial_repeats = trial_repeats
        self.feature_source = feature_source
        #: Micro-trials executed by this tuner — the steady-state /
        #: warm-cache assertion counter (zero on a warm cache).
        self.trials_run = 0
        self._memo: Dict[tuple, TunedConfig] = {}

    # -- resolution ---------------------------------------------------------

    def get(
        self,
        m: int,
        n: int,
        dtype,
        options,
        batch: Optional[int] = None,
        shared: bool = False,
    ) -> TunedConfig:
        """The config this shape class should run under ``options``.

        Memoized per (shape class, mode, pins) for the process lifetime
        — a session or serve loop pays the ranking once per class.
        Resolution order: in-memory memo, then (trial mode only) the
        on-disk winner cache, then predicted ranking, then micro-trials
        of the top-k when the mode asks for them.
        """
        mode = options.autotune
        key = cache_key(m, n, batch, dtype, shared)
        memo_key = (
            key, mode, options.backend, options.layout, options.tile_b,
            options.route_frontier,
        )
        hit = self._memo.get(memo_key)
        if hit is not None:
            return hit
        choice: Optional[TunedConfig] = None
        if mode == "trial":
            entry = self.cache.lookup(key)
            if entry is not None and self._entry_usable(entry, m, n, dtype, options):
                choice = TunedConfig(
                    entry["backend"],
                    entry.get("layout"),
                    entry.get("tile_b"),
                    predicted_s=entry.get("predicted_s"),
                    measured_s=entry.get("measured_s"),
                    source="cache",
                )
        if choice is None:
            features = None
            if self.feature_source == "hlo" and not shared:
                features = self._hlo_features(m, n, batch, dtype, options)
            ranked = rank_candidates(
                m, n, batch, dtype, options, shared=shared, features=features
            )
            choice = ranked[0]
            if mode == "trial":
                if len(ranked) > 1:
                    choice = self._confirm(
                        ranked[: self.top_k], m, n, batch, dtype, shared
                    )
                self.cache.store(
                    key, self._entry(choice, m, n, batch, dtype, shared)
                )
        self._memo[memo_key] = choice
        return choice

    def _entry_usable(self, entry: dict, m, n, dtype, options) -> bool:
        """A cached winner counts only if it honors the caller's pins
        and is still feasible here (the cache can outlive a platform)."""
        if options.backend != "auto" and entry.get("backend") != options.backend:
            return False
        if options.layout is not None and entry.get("layout") not in (
            None, options.layout,
        ):
            return False
        if options.tile_b is not None and entry.get("tile_b") not in (
            None, options.tile_b,
        ):
            return False
        tile = entry.get("tile_b")
        if tile is not None and (not isinstance(tile, int) or tile < 1):
            return False
        return feasible(
            entry["backend"], entry.get("layout"), tile, m, n, dtype
        )

    @staticmethod
    def _entry(choice: TunedConfig, m, n, batch, dtype, shared) -> dict:
        return {
            "backend": choice.backend,
            "layout": choice.layout,
            "tile_b": choice.tile_b,
            "predicted_s": choice.predicted_s,
            "measured_s": choice.measured_s,
            "m_class": next_pow2(m),
            "n_class": next_pow2(n),
            "batch_class": next_pow2(batch) if batch else DEFAULT_BATCH_CLASS,
            "dtype": np.dtype(dtype).name,
            "shared": bool(shared),
        }

    def _hlo_features(self, m, n, batch, dtype, options):
        layouts = [options.layout] if options.layout else list(LAYOUTS)
        feats = {}
        for lay in layouts:
            try:
                feats[lay] = hlo_profile(
                    m, n, batch=min(batch or 4, 4), dtype=dtype, layout=lay
                )
            except Exception as exc:  # pragma: no cover - platform-specific
                warnings.warn(
                    f"autotune: HLO feature extraction failed for layout "
                    f"{lay!r} ({exc}); scoring on the analytic model",
                    stacklevel=2,
                )
                return None
        return feats

    # -- micro-trials -------------------------------------------------------

    def _confirm(
        self, top: Sequence[TunedConfig], m, n, batch, dtype, shared
    ) -> TunedConfig:
        """Time the predicted top-k on the real shape; measured best wins."""
        best = None
        best_t = math.inf
        for cand in top:
            t = self._measure(cand, m, n, batch, dtype, shared)
            self.trials_run += 1
            if t < best_t:
                best, best_t = cand, t
        return dataclasses.replace(best, measured_s=best_t, source="measured")

    def _measure(self, cand: TunedConfig, m, n, batch, dtype, shared) -> float:
        from ..core import backends as _backends
        from ..core import dispatch as _dispatch

        bsz = max(1, min(self.trial_batch, batch or self.trial_batch))
        rng = np.random.default_rng(1_000_003 * m + 101 * n + bsz)
        trial = self._trial_batch(rng, bsz, m, n, dtype, shared)
        opts = _backends.SolveOptions(
            backend=cand.backend,
            layout=cand.layout,
            tile_b=cand.tile_b,
            autotune="off",  # the trial must not recurse into the tuner
        )

        def run():
            sol = _dispatch.solve_canonical(trial, opts)
            sol.objective.block_until_ready()

        run()  # warmup: compile + first dispatch
        best = math.inf
        for _ in range(self.trial_repeats):
            t0 = time.perf_counter()
            run()
            best = min(best, time.perf_counter() - t0)
        return best

    @staticmethod
    def _trial_batch(rng, bsz: int, m: int, n: int, dtype, shared: bool):
        from ..core import lp as _lp

        if not shared:
            return _lp.random_lp_batch(
                rng, bsz, m, n, feasible_start=True, dtype=np.dtype(dtype)
            )
        a = jnp.asarray(rng.uniform(0.1, 1.0, (m, n)), dtype)
        b = jnp.asarray(rng.uniform(1.0, 2.0, (bsz, m)), dtype)
        c = jnp.asarray(rng.uniform(0.1, 1.0, (bsz, n)), dtype)
        return _lp.SharedLPBatch(a, b, c)


# ---------------------------------------------------------------------------
# process-wide tuner + the hooks the core layers call
# ---------------------------------------------------------------------------

_TUNER: Optional[Autotuner] = None


def get_tuner() -> Autotuner:
    """The process-wide tuner (created on first use)."""
    global _TUNER
    if _TUNER is None:
        _TUNER = Autotuner()
    return _TUNER


def reset(cache_path: Optional[str] = None, **kw) -> Autotuner:
    """Replace the process-wide tuner (test/benchmark hook).

    Drops the in-memory memo and re-reads the cache file (``cache_path``
    or the default) on next use; extra keyword arguments forward to
    :class:`Autotuner`.
    """
    global _TUNER
    _TUNER = Autotuner(cache_path=cache_path, **kw)
    return _TUNER


def resolve(
    m: int,
    n: int,
    dtype,
    options,
    shared: bool = False,
    batch: Optional[int] = None,
    stats=None,
):
    """Tuner-backed options resolution (the dispatch layer's entry point).

    Fills exactly the knobs the caller left open — ``backend="auto"``,
    ``layout=None``, ``tile_b=None`` — from the tuned choice and records
    the decision into ``stats`` (``SolveStats.autotuned`` plus one
    ``autotune_log`` row).  Explicit pins always pass through untouched.
    A shape routed to ``pdhg`` resets ``rule``/``layout`` to their
    defaults, matching the static table's behavior.
    """
    from ..core import engine as _engine

    choice = get_tuner().get(m, n, dtype, options, batch=batch, shared=shared)
    kw = {}
    if options.backend == "auto":
        kw["backend"] = choice.backend
        if choice.backend == "pdhg":
            kw["rule"] = _engine.LPC
            kw["layout"] = None
    if "layout" not in kw and options.layout is None and choice.layout is not None:
        kw["layout"] = choice.layout
    if options.tile_b is None and choice.tile_b is not None:
        kw["tile_b"] = choice.tile_b
    if stats is not None:
        stats.autotuned += 1
        stats.autotune_log.append(
            {
                "m": m,
                "n": n,
                "batch": batch,
                "dtype": np.dtype(dtype).name,
                "shared": shared,
                "backend": choice.backend,
                "layout": choice.layout,
                "tile_b": choice.tile_b,
                "predicted_s": choice.predicted_s,
                "measured_s": choice.measured_s,
                "source": choice.source,
            }
        )
    return options.replace(**kw) if kw else options


def choose_backend(
    m: int,
    n: int,
    dtype,
    options,
    batch: Optional[int] = None,
    shared: bool = False,
    layout: Optional[str] = None,
) -> str:
    """Backend name for a shape — ``route_shape``'s tuner-backed leg.

    The caller's pinned backend is ignored (routing asks where a shape
    SHOULD go, e.g. the VMEM fallback rerouting an over-budget pallas
    pin), so the candidate set is always the ``"auto"`` one; ``layout``
    overrides the options' layout pin for the feasibility footprint
    (a resume routes on its CARRIED layout).
    """
    kw = {"backend": "auto"}
    if layout is not None:
        kw["layout"] = layout
    options = options.replace(**kw)
    return get_tuner().get(m, n, dtype, options, batch=batch, shared=shared).backend


def cached_tile_b(bsz: int, m: int, n: int, dtype, layout: str) -> Optional[int]:
    """A MEASURED winning tile for this shape class, or None.

    Consulted by ``kernels/ops.py:auto_tile_b`` before its VMEM
    heuristic.  Only micro-trial winners pin a tile — predicted entries
    reproduce the heuristic anyway — and the pin is ignored unless it
    still fits the budget here and matches the kernel's layout.  Scans
    the cached entries across batch classes (the kernel sees padded
    round sizes, not the original batch class) preferring the largest
    batch class, i.e. the measurement closest to steady state.
    """
    tuner = _TUNER
    if tuner is None:
        return None  # nothing tuned or warmed in this process
    mc, nc, dt = next_pow2(m), next_pow2(n), np.dtype(dtype).name
    best: Optional[dict] = None
    for entry in tuner.cache.load().values():
        if not isinstance(entry, dict):
            continue
        tile = entry.get("tile_b")
        if (
            entry.get("measured_s") is None
            or not isinstance(tile, int)
            or tile < 1
            or entry.get("backend") != "pallas"
            or entry.get("layout") not in (None, layout)
            or entry.get("m_class") != mc
            or entry.get("n_class") != nc
            or entry.get("dtype") != dt
        ):
            continue
        if best is None or entry.get("batch_class", 0) > best.get("batch_class", 0):
            best = entry
    if best is None:
        return None
    tile = min(int(best["tile_b"]), next_pow2(bsz))
    if not feasible("pallas", layout, tile, m, n, dtype):
        return None
    return max(1, tile)


def warm(
    shapes: Sequence,
    options=None,
    dtype=jnp.float32,
    hlo: bool = False,
) -> List[TunedConfig]:
    """Explicit offline tuning: trial-resolve shape classes, persist winners.

    Parameters
    ----------
    shapes : sequence of (m, n) or (m, n, batch)
        Shape classes to tune; batch defaults to the tuner's assumed
        class.
    options : SolveOptions, optional
        Pins to respect (backend/layout/tile_b); default is the fully
        open ``backend="auto"`` knob space.
    dtype : dtype, default float32
        Solve dtype of the tuned class.
    hlo : bool, default False
        Also compile the XLA driver per layout and rank on HLO-measured
        traffic (:func:`hlo_profile`) — slower warm, better model.

    Returns
    -------
    list of TunedConfig
        The winner per shape, in input order.  Re-warming against a warm
        cache is free (pure cache hits, zero micro-trials).
    """
    from ..core import backends as _backends

    base = options or _backends.SolveOptions(backend="auto")
    base = base.replace(autotune="trial")
    tuner = get_tuner()
    prior = tuner.feature_source
    if hlo:
        tuner.feature_source = "hlo"
    out = []
    try:
        for shape in shapes:
            m, n = int(shape[0]), int(shape[1])
            batch = int(shape[2]) if len(shape) > 2 else None
            out.append(tuner.get(m, n, dtype, base, batch=batch))
    finally:
        tuner.feature_source = prior
    return out

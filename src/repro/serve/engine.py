"""Serving engines: LM decode loop + continuous-batching LP serving.

``Engine.generate`` drives the model's prefill/decode_step under jit with
donated cache buffers (the functional cache update is in-place
post-donation).  ``LPEngine`` is the LP-serving counterpart, with two
modes over one persistent :class:`~repro.core.session.SolveSession`:

  * **flush mode** (the legacy micro-batcher): requests accumulate until
    ``flush_every`` are pending or ``flush()`` is called, then solve as
    one bucketed megabatch through ``repro.solve``.

  * **continuous mode** (``step()``): a scheduler loop that keeps the
    device busy across request boundaries.  Each step admits pending
    requests (earliest-deadline-first with a starvation bound) into
    per-shape-class in-flight groups — new arrivals are materialized as
    iteration-0 resume states and SPLICED into the same pow-2-padded
    dispatch round as the still-active survivors of previous rounds —
    and each LP completes the round it finishes, not when a whole flush
    drains.  Per-LP results are bit-identical to a one-shot
    ``repro.solve`` of the same problems (the exact-resume contract of
    ``core/dispatch.py``).

launch/serve_lp.py drives the flush mode with straggler-mitigated
workers from ``runtime/straggler.py``; ``serve/loadgen.py`` +
``benchmarks/fig_serve.py`` drive both modes under open-loop Poisson
load and compare their latency distributions."""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dispatch as _dispatch
from ..core import pdhg as _pdhg
from ..core.backends import SolveOptions, SolveStats, get_backend, read_back
from ..core.bucketing import ShapeGrid, next_pow2, shape_class
from ..core.lp import ITER_LIMIT, NUMERICAL, LPBatch, LPSolution
from ..core.problem import (
    Canonicalized,
    LPProblem,
    canonicalize,
    stack_problems,
    uncanonicalize,
    validate_problem,
)
from ..core.session import SolveSession
from ..models.model import Model
from ..runtime import chaos as _chaos
from ..runtime import trace as _trace


class Engine:
    def __init__(self, model: Model, params, max_len: int, enc_len: int = 0):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.enc_len = enc_len

        self._prefill = jax.jit(model.prefill)
        # donate the cache: decode rewrites it in place
        self._decode = jax.jit(model.decode_step, donate_argnums=(2,))

    def generate(
        self,
        inputs: Dict[str, jnp.ndarray],
        steps: int,
        temperature: float = 0.0,
        seed: int = 0,
    ) -> jnp.ndarray:
        """Greedy (or sampled) continuation of a batch of prompts."""
        tokens = inputs["tokens"]
        b, prompt_len = tokens.shape
        cache = self.model.init_cache(b, self.max_len, enc_len=self.enc_len)
        logits, cache = self._prefill(self.params, inputs, cache)
        out = []
        key = jax.random.PRNGKey(seed)
        cur = self._sample(logits[:, -1], temperature, key)
        out.append(cur)
        for i in range(steps - 1):
            key, sub = jax.random.split(key)
            step_in = {"tokens": cur[:, None]}
            logits, cache = self._decode(
                self.params, step_in, cache, prompt_len + i
            )
            cur = self._sample(logits[:, -1], temperature, sub)
            out.append(cur)
        return jnp.stack(out, axis=1)  # (B, steps)

    @staticmethod
    def _sample(logits: jnp.ndarray, temperature: float, key) -> jnp.ndarray:
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class _Group:
    """One in-flight canonical shape class of the continuous serve loop.

    Rows of ``batch``/``state``/``c_user``/``shift`` and the entries of
    the parallel bookkeeping lists are aligned: row i is the LP of
    ``tickets[i]``.  Retirement gathers the finished rows out and the
    next admission concatenates newcomers on — the arrays are the
    spliced round the scheduler dispatches each step.
    """

    options: SolveOptions  # resolved: concrete backend for this class
    full_cap: int  # per-LP total iteration budget (auto rule resolved)
    quantum: int  # per-round incremental budget
    sign: int  # +1 maximize / -1 minimize (uncanonicalize static)
    split: bool  # canonical x+/x- split flag (uncanonicalize static)
    cn: int  # padded user variable count (class width)
    batch: LPBatch  # canonical rows (basis0 consumed by the init state)
    state: object  # backend resume state, row-aligned with batch
    c_user: jnp.ndarray  # (B, cn) user objectives
    shift: jnp.ndarray  # (B, cn) lo' shifts
    tickets: List[int]
    remaining: List[int]  # per-row iteration budget left
    done: List[int]  # per-row iterations spent so far
    true_n: List[int]  # per-row unpadded variable count
    label: str  # the shape class, as the group attribute of its spans


def _group_label(key: Tuple) -> str:
    """A group's shape class, ``"<m>x<n>"``, for the spans' ``group`` attribute."""
    return f"{key[0]}x{key[1]}"


class LPEngine:
    """LP server over one persistent session: flush mode + continuous mode.

    Requests are general-form single-LP :class:`LPProblem`\\ s of any
    shapes, submitted for a ticket and redeemed via :meth:`result`.

    **Flush mode** (the default traffic path): requests accumulate until
    ``flush_every`` are pending or :meth:`flush` is called; each flush is
    one bucketed-megabatch solve through the session.

    **Continuous mode**: drive :meth:`step` instead.  Each step admits
    pending requests into per-shape-class in-flight groups — ordered
    earliest-deadline-first with priority and an aging bound
    (:func:`repro.core.dispatch.admission_order`), so a request waits at
    most ``starvation_rounds`` scheduler rounds before outranking every
    later arrival — and advances every group by one capped dispatch
    round.  Newly admitted LPs enter as iteration-0 resume states
    (``Backend.init_canonical``) concatenated with the carried survivors,
    so ONE resume dispatch per round advances both (``stats.spliced``
    counts the newcomers that joined a non-empty round); each LP
    completes and becomes redeemable the round it finishes.  Because the
    exact-resume protocol replays an uninterrupted solve
    arithmetic-for-arithmetic, per-LP results are bit-identical to a
    one-shot ``repro.solve`` of the same problems — continuous batching
    changes latency, never answers.

    Both modes share the compile-once discipline: shape classes pin
    pow-2-padded executables, iteration caps are traced, and a warmed-up
    engine's ``stats.compiles`` stays flat while ``cache_hits`` grow.
    Requests that cannot be spliced (boxlike closed-form problems, a
    backend without ``init_canonical``, ``unroll > 1``) complete at
    admission through the one-shot path instead — same results, no
    incremental rounds.

    For mixed-size traffic, construct the engine with
    ``SolveOptions(backend="auto")``: each shape class resolves once at
    admission through the routing table — simplex below the
    ``route_frontier``, the first-order ``pdhg`` backend above it (add
    ``crossover=True`` when callers need exact vertices from the
    first-order side).

    **Degradation under faults**: every dispatch round runs through the
    recovery wrapper (``core.dispatch.dispatch_round_safe``), so a
    transient backend failure re-dispatches the same round from the same
    carried state — on the routed twin backend where one exists — up to
    ``options.retry_budget`` times.  A round that still fails retires
    only ITS shape-class group through the dead-letter path (tickets
    complete with ``NUMERICAL`` status, recorded in ``dead_letters`` and
    ``stats.dead_lettered``); other groups keep advancing.  Rows whose
    carried state goes non-finite are caught by the per-round guardrail
    and retire individually as ``NUMERICAL``.

    Parameters
    ----------
    options : SolveOptions, optional
        Pinned solver configuration for every request.
    flush_every : int, default 256
        Auto-flush threshold of the flush mode.  Continuous callers that
        never want a stop-the-world flush should set it large.
    grid : sequence of (int, int), optional
        Caller-pinned shape classes (``core.bucketing.shape_class``).
    mesh : jax.sharding.Mesh, optional
        Mesh for batch-dimension sharding.
    stats : SolveStats, optional
        The record to accumulate into; a fresh one by default.
    step_iters : int, default 0
        Per-round iteration budget of the continuous scheduler; 0 means
        the compaction auto rule ``8 (m' + n')`` per canonical class.
    max_inflight : int, optional
        Admission cap: at most this many LPs in flight across all
        groups (None = admit everything pending each step).
    admission : {"edf", "fifo"}, default "edf"
        Admission ordering — earliest-deadline-first (with priority and
        the starvation bound) or plain submission order.
    starvation_rounds : int, default 8
        Rounds a request may wait before aging ahead of every non-aged
        request (the EDF starvation bound).
    clock : callable, default time.monotonic
        Time source ``() -> float`` that request deadlines are measured
        against (``deadline_misses`` counts completions past their
        deadline; injectable for tests).

    Notes
    -----
    Under ``jax.profiler`` the engine writes ``serve.submit``,
    ``serve.step``, ``serve.admit``, ``serve.advance`` and
    ``serve.retire`` spans (``repro.runtime.trace``; ``ticket`` on
    per-ticket spans, ``group`` on group spans), and each ticket that
    completes appends two intervals timed on ``time.perf_counter``:
    ``serve.queued`` (submit to admission) and ``serve.inflight``
    (admission to completion).
    """

    def __init__(
        self,
        options: Optional[SolveOptions] = None,
        flush_every: int = 256,
        grid: Optional[ShapeGrid] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        stats: Optional[SolveStats] = None,
        *,
        step_iters: int = 0,
        max_inflight: Optional[int] = None,
        admission: str = "edf",
        starvation_rounds: int = 8,
        clock: Callable[[], float] = time.monotonic,
    ):
        if admission not in ("edf", "fifo"):
            raise ValueError(f'admission must be "edf" or "fifo", got {admission!r}')
        self.options = options or SolveOptions()
        self.flush_every = flush_every
        self.grid = grid
        self.mesh = mesh
        self.session = SolveSession(
            self.options, mesh=mesh, grid=grid, stats=stats
        )
        self.step_iters = int(step_iters)
        self.max_inflight = max_inflight
        self.admission = admission
        self.starvation_rounds = int(starvation_rounds)
        self.clock = clock
        self.deadline_misses = 0
        # Tickets retired through the dead-letter path: their group's
        # dispatch round kept failing after every in-round retry
        # (``options.retry_budget``) so the whole group was retired with
        # NUMERICAL status rather than stalling the other shape classes.
        self.dead_letters: List[int] = []
        self._pending: List[Tuple[int, LPProblem]] = []
        self._pending_ids: Set[int] = set()
        # ticket -> (deadline, priority, submitted_step); admission order
        self._meta: Dict[int, Tuple[Optional[float], int, int]] = {}
        self._results: Dict[int, LPSolution] = {}
        self._inflight: Dict[int, Tuple] = {}  # ticket -> group key
        self._groups: Dict[Tuple, _Group] = {}
        self._next_ticket = 0
        self._step_count = 0
        # ticket -> [submit, admit] on time.perf_counter, kept while traced
        self._times: Dict[int, List[Optional[float]]] = {}

    @property
    def stats(self) -> SolveStats:
        """Cumulative counters for every dispatch this engine performed."""
        return self.session.stats

    @property
    def pending_count(self) -> int:
        """Requests submitted but not yet admitted or flushed."""
        return len(self._pending)

    @property
    def inflight_count(self) -> int:
        """LPs currently carried by the continuous scheduler's groups."""
        return len(self._inflight)

    # -- submission ---------------------------------------------------------

    def submit(
        self,
        problem: LPProblem,
        deadline: Optional[float] = None,
        priority: int = 0,
    ) -> int:
        """Queue one request; returns a ticket redeemable once it completes.

        Parameters
        ----------
        problem : LPProblem
            A single-LP (batch == 1) general-form problem.
        deadline : float, optional
            Absolute completion deadline on the engine's ``clock``.
            Orders EDF admission and feeds ``deadline_misses``; it never
            cancels work.
        priority : int, default 0
            Tie-break among equal deadlines (larger wins).

        Raises
        ------
        ValueError
            Immediately — before a ticket is allocated — when the
            problem payload contains NaN/Inf where finite data is
            required (the message names the offending field) or when
            ``deadline`` is NaN or negative.  Rejecting poisoned input
            at the door is the cheap half of the numerical guardrails:
            everything past this point may assume admission-time data
            was finite.
        """
        with _trace.span("serve.submit") as sp:
            if isinstance(problem, LPProblem):
                validate_problem(problem, where="submit: problem")
            if deadline is not None:
                deadline = float(deadline)
                if np.isnan(deadline) or deadline < 0.0:
                    raise ValueError(
                        "submit: deadline must be a non-negative clock time "
                        f"(or None), got {deadline!r}"
                    )
            ticket = self._next_ticket
            self._next_ticket += 1
            sp.set(ticket=ticket)
            if _trace.recording():
                self._times[ticket] = [time.perf_counter(), None]
            self._pending.append((ticket, problem))
            self._pending_ids.add(ticket)
            self._meta[ticket] = (
                None if deadline is None else float(deadline),
                int(priority),
                self._step_count,
            )
            if len(self._pending) >= self.flush_every:
                self.flush()
            return ticket

    def done(self, ticket: int) -> bool:
        """Whether a ticket's result is ready to redeem."""
        return ticket in self._results

    def cancel(self, ticket: int) -> bool:
        """Drop a still-pending request; False once admitted or solved."""
        if ticket not in self._pending_ids:
            return False
        self._pending = [(t, p) for t, p in self._pending if t != ticket]
        self._pending_ids.discard(ticket)
        self._meta.pop(ticket, None)
        self._times.pop(ticket, None)
        return True

    # -- continuous scheduler -----------------------------------------------

    def step(self) -> List[int]:
        """One scheduler round: admit pending, advance every group.

        Returns the tickets that completed this round (admission-time
        one-shot completions included), in no particular order.  Results
        are in ``result()``'s store; ``step()`` never blocks on a ticket.
        """
        self._step_count += 1
        completed: List[int] = []
        with _trace.span("serve.step", step=self._step_count):
            self._admit(completed)
            self._advance(completed)
        return completed

    def _admit(self, completed: List[int]) -> None:
        """Admit pending requests into in-flight groups (EDF-ordered)."""
        if not self._pending:
            return
        if self.max_inflight is None:
            capacity = len(self._pending)
        else:
            capacity = self.max_inflight - self.inflight_count
            if capacity <= 0:
                return
        if self.admission == "edf":
            order = _dispatch.admission_order(
                [(t, *self._meta[t]) for t, _ in self._pending],
                now=self._step_count,
                starvation_rounds=self.starvation_rounds,
            )
        else:
            order = list(range(len(self._pending)))
        chosen = order[:capacity]
        # Validate and group BEFORE mutating any engine state: a bad
        # request must fail the admission without dropping the others
        # (the flush error-path contract, continuous flavor).
        waves: Dict[Tuple, Tuple[List[int], List[LPProblem], List[int]]] = {}
        for i in chosen:
            ticket, p = self._pending[i]
            if not isinstance(p, LPProblem):
                raise TypeError(
                    f"ticket {ticket} holds {type(p).__name__}, expected LPProblem"
                )
            if p.batch != 1:
                raise ValueError(
                    "LPEngine serves single-LP requests (batch == 1); "
                    f"ticket {ticket} has batch {p.batch} — solve it directly"
                )
            cm, cn = shape_class(p.m, p.n, self.grid)
            padded = p.pad_to(cm, cn)
            # Key on the PADDED problem's static flags: pad_to can flip
            # boxlike/var_upper, and the flags fix the canonical (m', n')
            # every row of a group must share.
            key = (
                cm, cn, padded.maximize, str(padded.dtype),
                padded.split, padded.row_lower, padded.var_upper, padded.boxlike,
            )
            tickets, probs, true_ns = waves.setdefault(key, ([], [], []))
            tickets.append(ticket)
            probs.append(padded)
            true_ns.append(p.n)
        for key, (tickets, probs, true_ns) in waves.items():
            self._mark_admitted(tickets)
            with _trace.span("serve.admit", group=_group_label(key), rows=len(tickets)):
                self._admit_wave(key, tickets, probs, true_ns, completed)
            wave = set(tickets)
            self._pending = [(t, p) for t, p in self._pending if t not in wave]
            self._pending_ids -= wave

    def _mark_admitted(self, tickets: List[int]) -> None:
        """Stamp the admission time of the tickets whose submit was traced."""
        now = time.perf_counter()
        for t in tickets:
            if t in self._times:
                self._times[t][1] = now

    def _admit_wave(
        self,
        key: Tuple,
        tickets: List[int],
        padded: List[LPProblem],
        true_ns: List[int],
        completed: List[int],
    ) -> None:
        """Splice one shape-class wave into its group (or solve one-shot)."""
        stacked = stack_problems(padded)
        if stacked.boxlike:
            # Closed form — nothing to iterate, complete at admission.
            self._complete_oneshot(tickets, stacked, true_ns, completed)
            return
        canon = canonicalize(stacked)
        resolved = self.session.resolve_options(
            canon.batch.m, canon.batch.n, canon.batch.a.dtype
        )
        backend = get_backend(resolved.backend)
        # unroll > 1 re-aligns loop-step grouping across round splits
        # (same reason solve_canonical's basis-resume falls back there).
        if not backend.supports_splice or resolved.unroll > 1:
            self._complete_oneshot(tickets, stacked, true_ns, completed)
            return
        # Pad the admission wave to a pow-2 batch size before init, same
        # discipline as the dispatch rounds: one init executable per size
        # class instead of one per distinct wave size.  Replica rows are
        # trimmed off the state (init is per-row, so real rows are
        # unaffected).  The floor of 2 keeps every dispatch off XLA's
        # special-cased batch-1 contraction codepath, whose reduction
        # order differs at the ulp level from the batched one — solving a
        # row alone would not be bit-identical to solving it inside the
        # one-shot megabatch.
        wave = canon.batch.batch
        target = max(2, next_pow2(wave))
        init_in, _ = _dispatch._pad_batch_to(canon.batch, target)
        state = self.session.init_state(init_in, resolved)
        if target != wave:
            state = state.take(slice(None, wave))
        full_cap = _dispatch._full_cap(canon.batch, resolved, backend)
        batch = LPBatch(canon.batch.a, canon.batch.b, canon.batch.c)
        g = self._groups.get(key)
        if g is None:
            quantum = self.step_iters or 8 * (canon.batch.m + canon.batch.n)
            g = _Group(
                options=resolved,
                full_cap=full_cap,
                quantum=max(1, min(quantum, full_cap)),
                sign=canon.sign,
                split=canon.split,
                cn=canon.n,
                batch=batch,
                state=state,
                c_user=canon.c_user,
                shift=canon.shift,
                tickets=[],
                remaining=[],
                done=[],
                true_n=[],
                label=_group_label(key),
            )
            self._groups[key] = g
        else:
            if g.tickets:
                self.stats.spliced += len(tickets)
            g.batch = LPBatch(
                jnp.concatenate([g.batch.a, batch.a]),
                jnp.concatenate([g.batch.b, batch.b]),
                jnp.concatenate([g.batch.c, batch.c]),
            )
            g.state = _dispatch._concat_states([g.state, state])
            g.c_user = jnp.concatenate([g.c_user, canon.c_user])
            g.shift = jnp.concatenate([g.shift, canon.shift])
        g.tickets.extend(tickets)
        g.remaining.extend([g.full_cap] * len(tickets))
        g.done.extend([0] * len(tickets))
        g.true_n.extend(true_ns)
        for t in tickets:
            self._inflight[t] = key

    def _complete_oneshot(
        self,
        tickets: List[int],
        stacked: LPProblem,
        true_ns: List[int],
        completed: List[int],
    ) -> None:
        """Admission-time completion through the one-shot solve path."""
        from .. import api  # lazy: api imports this package's siblings

        sol = api._solve_problem(
            stacked, self.options, self.mesh, ("data",), self.stats
        )
        for row, (t, tn) in enumerate(zip(tickets, true_ns)):
            self._finish(
                t,
                LPSolution(
                    objective=sol.objective[row : row + 1],
                    x=sol.x[row : row + 1, :tn],
                    status=sol.status[row : row + 1],
                    iterations=sol.iterations[row : row + 1],
                ),
                completed,
            )

    def _advance(self, completed: List[int]) -> None:
        """One capped dispatch round for every in-flight group.

        Faults are isolated per shape-class group: a round that still
        fails after ``dispatch_round_safe``'s in-round retries (i.e. the
        per-round ``retry_budget`` is exhausted) retires that ONE group
        through the dead-letter path — its tickets complete with
        ``NUMERICAL`` status and a NaN objective — while every other
        group keeps advancing.  Non-transient errors (``ValueError`` and
        friends: caller bugs; kernel compile failures: the program cannot
        run here) propagate.
        """
        for key in list(self._groups):
            g = self._groups[key]
            if g.tickets:
                try:
                    with _trace.span("serve.advance", group=g.label, rows=len(g.tickets)):
                        self._step_group(g, completed)
                except Exception as exc:
                    if not _chaos.is_transient(exc):
                        raise
                    self._dead_letter_group(key, g, completed)
                    continue
            if not g.tickets:
                del self._groups[key]

    def _dead_letter_group(
        self, key: Tuple, g: _Group, completed: List[int]
    ) -> None:
        """Retire a group whose round exhausted the retry budget.

        ``_step_group`` is fault-atomic — it commits nothing until every
        sub-dispatch of the round succeeds — so the group's bookkeeping
        still reflects the last GOOD round here.  Each ticket finishes
        with ``NUMERICAL`` status, a NaN objective, zero x and the
        iteration count it had actually banked; the ticket numbers land
        in ``engine.dead_letters`` and ``stats.dead_lettered`` so
        callers can tell "solver gave up" from "solver answered".
        """
        dtype = g.batch.a.dtype
        for i, t in enumerate(list(g.tickets)):
            sol = LPSolution(
                objective=jnp.full((1,), jnp.nan, dtype),
                x=jnp.zeros((1, g.true_n[i]), dtype),
                status=jnp.full((1,), NUMERICAL, jnp.int32),
                iterations=jnp.asarray([g.done[i]], jnp.int32),
            )
            self.dead_letters.append(t)
            self.stats.dead_lettered += 1
            self._finish(t, sol, completed)
        g.tickets = []
        self._groups.pop(key, None)

    def _step_group(self, g: _Group, completed: List[int]) -> None:
        """Advance one group by one round; retire the rows that finished.

        Per-row round budgets are ``min(quantum, remaining)``; every row
        starts from the same ``full_cap``, so at most two distinct values
        exist per round (``quantum`` and the final ``full_cap %
        quantum``) and each value is one pow-2-padded resume dispatch —
        budgets sum exactly to ``full_cap`` per LP, never overshooting,
        which is what keeps the replay bit-identical to one-shot.

        The round is fault-atomic: per-row ``done``/``remaining`` deltas
        accumulate in locals and commit only after every sub-dispatch of
        the round succeeded.  If any dispatch escapes the retry wrapper,
        the group is exactly as it was before the round — same carried
        state, same budgets — which is what lets ``_advance`` either
        retry the group next step or dead-letter it with honest
        bookkeeping.
        """
        nrows = len(g.tickets)
        incs = np.minimum(g.quantum, np.asarray(g.remaining, np.int64))
        status = np.empty(nrows, np.int32)
        obj = jnp.zeros((nrows,), g.batch.a.dtype)
        x = jnp.zeros((nrows, g.batch.n), g.batch.a.dtype)
        done_inc = np.zeros(nrows, np.int64)
        new_state = g.state
        for v in sorted(set(incs.tolist())):
            rows = np.nonzero(incs == v)[0]
            ridx = jnp.asarray(rows)
            sub = _dispatch._gather_batch(g.batch, ridx)
            sub_state = g.state.take(ridx)
            # size floor 2: see _admit_wave — batch-1 dispatches take a
            # different XLA contraction codepath and lose bit-identity.
            sol, part_state = self.session.resume_round(
                sub, sub_state, int(v), g.options,
                size_class=max(2, next_pow2(int(rows.size))),
            )
            status[rows] = read_back(sol.status, "serve.status", self.stats)
            obj = obj.at[ridx].set(sol.objective)
            x = x.at[ridx].set(sol.x)
            new_state = jax.tree_util.tree_map(
                lambda full, part: full.at[ridx].set(part), new_state, part_state
            )
            done_inc[rows] = read_back(sol.iterations, "serve.iterations", self.stats)
        # Every sub-dispatch succeeded: commit the round's bookkeeping.
        for i in range(nrows):
            g.done[i] += int(done_inc[i])
            g.remaining[i] -= int(incs[i])
        keep = [
            i for i in range(nrows)
            if status[i] == ITER_LIMIT and g.remaining[i] > 0
        ]
        kept = set(keep)
        drop = [i for i in range(nrows) if i not in kept]
        if drop:
            with _trace.span("serve.retire", group=g.label, rows=len(drop)):
                self._retire(g, drop, status, obj, x, completed)
        if len(keep) == nrows:
            g.state = new_state
            return
        kidx = jnp.asarray(keep, jnp.int32)
        g.batch = _dispatch._gather_batch(g.batch, kidx)
        g.state = new_state.take(kidx)
        g.c_user = g.c_user[kidx]
        g.shift = g.shift[kidx]
        g.tickets = [g.tickets[i] for i in keep]
        g.remaining = [g.remaining[i] for i in keep]
        g.done = [g.done[i] for i in keep]
        g.true_n = [g.true_n[i] for i in keep]

    def _retire(
        self,
        g: _Group,
        rows: List[int],
        status: np.ndarray,
        obj: jnp.ndarray,
        x: jnp.ndarray,
        completed: List[int],
    ) -> None:
        """Finish rows: post-passes, uncanonicalize, store per-ticket rows."""
        ridx = jnp.asarray(rows, jnp.int32)
        sub = _dispatch._gather_batch(g.batch, ridx)
        sol = LPSolution(
            objective=obj[ridx],
            x=x[ridx],
            status=jnp.asarray(status[np.asarray(rows)]),
            iterations=jnp.asarray(
                np.asarray([g.done[i] for i in rows], np.int32)
            ),
        )
        if g.options.backend == "pdhg":
            # Same once-per-row post-passes solve_canonical applies to its
            # final merged solution; both are per-row deterministic, so a
            # retired sub-batch equals the one-shot full-batch application.
            sol = _pdhg.confirm_certificates(sub, sol, g.options)
            if g.options.crossover:
                sol = _pdhg.crossover(sub, sol, g.options)
        canon = Canonicalized(
            batch=sub,
            c_user=g.c_user[ridx],
            shift=g.shift[ridx],
            n=g.cn,
            sign=g.sign,
            split=g.split,
        )
        out = uncanonicalize(canon, sol)
        for row, i in enumerate(rows):
            self._finish(
                g.tickets[i],
                LPSolution(
                    objective=out.objective[row : row + 1],
                    x=out.x[row : row + 1, : g.true_n[i]],
                    status=out.status[row : row + 1],
                    iterations=out.iterations[row : row + 1],
                ),
                completed,
            )

    def _finish(
        self, ticket: int, sol: LPSolution, completed: List[int]
    ) -> None:
        deadline, _, _ = self._meta.pop(ticket, (None, 0, 0))
        if deadline is not None and self.clock() > deadline:
            self.deadline_misses += 1
        times = self._times.pop(ticket, None)
        if times is not None and times[1] is not None:
            _trace.interval("serve.queued", times[0], times[1], ticket=ticket)
            _trace.interval("serve.inflight", times[1], time.perf_counter(), ticket=ticket)
        self._results[ticket] = sol
        self._inflight.pop(ticket, None)
        completed.append(ticket)

    def _drain(self) -> int:
        """Run the in-flight groups to empty (no admission); count retires."""
        done = 0
        while self._groups:
            completed: List[int] = []
            self._advance(completed)
            done += len(completed)
        return done

    # -- flush mode ---------------------------------------------------------

    def flush(self) -> int:
        """Complete everything: drain in-flight groups, megabatch the rest.

        Pending (never-admitted) requests solve through the legacy
        one-bucketed-megabatch path.  Returns the number of requests
        completed.  A raising solve retains every pending request.
        """
        done = self._drain()
        if not self._pending:
            return done
        tickets = [t for t, _ in self._pending]
        problems = [p for _, p in self._pending]
        self._mark_admitted(tickets)
        sols = self.session.solve(problems)
        # Clear only after the solve succeeds: a raising solve (bad problem,
        # backend error) must not silently drop the other queued requests.
        self._pending = []
        self._pending_ids.clear()
        completed: List[int] = []
        for t, s in zip(tickets, sols):
            self._finish(t, s, completed)
        return done + len(completed)

    def result(self, ticket: int) -> LPSolution:
        """Redeem a ticket, running the engine forward if it must.

        An in-flight ticket is stepped to completion; a pending one is
        flushed.  An unknown or already-redeemed ticket raises
        ``KeyError`` immediately — no flush, no steps.
        """
        if ticket in self._results:
            return self._results.pop(ticket)
        if ticket in self._inflight:
            while ticket not in self._results:
                self.step()
            return self._results.pop(ticket)
        if ticket in self._pending_ids:
            self.flush()
            if ticket in self._results:
                return self._results.pop(ticket)
            self._pending_ids.discard(ticket)
        raise KeyError(f"ticket {ticket} unknown or already redeemed")

"""Deterministic, seedable fault injection for the dispatch pipeline.

The test substrate of the robustness layer: a :class:`ChaosMonkey`
installed with :func:`inject` is consulted by
``core/dispatch.py:dispatch_round`` at three points —

  * **before the round** (:meth:`ChaosMonkey.on_round`): inject an
    artificial delay and/or raise a :class:`ChaosError` (a backend
    exception, as if the device runtime failed the dispatch);
  * **before each chunk** (:meth:`ChaosMonkey.on_chunk`): raise a
    :class:`ShardCrash` mid-round, after earlier chunks already solved
    (the multi-chunk analogue of losing one shard of a sharded round);
  * **after the round** (:meth:`ChaosMonkey.poison_state`): overwrite
    selected rows of the carried resume state with NaN (silent numerical
    corruption the per-round guardrails must catch).

Faults are scheduled either deterministically (``fail_rounds`` /
``crash_rounds`` / ``poison_rows``, keyed by the monkey's dispatch-round
counter — every ``dispatch_round`` invocation, including retries,
advances it by one) or probabilistically from a seeded per-round RNG
(``error_rate`` / ``crash_rate``), so a given monkey configuration
injects the exact same fault sequence on every run.  ``max_faults``
bounds the total number of raised faults, which is how a test arranges
"fail once, then recover".

The module deliberately imports nothing from ``repro.core`` — the
dispatch layer imports *it*, never the reverse.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ChaosError(RuntimeError):
    """An injected backend failure (the whole dispatch round errored)."""


class ShardCrash(ChaosError):
    """An injected mid-round crash: one chunk/shard of the round died."""


#: Exception types the recovery layer treats as PROGRAMMING errors, never
#: retried: re-dispatching the same arguments cannot fix a bad argument.
NON_TRANSIENT = (ValueError, TypeError, KeyError, NotImplementedError)

#: Pallas's own lowering and verification exceptions, matched by class
#: name because JAX defines them in private modules.
_LOWERING_ERRORS = frozenset({"LoweringException", "MosaicError", "VerificationError"})

#: What the TPU compilers say when they refuse a program.  The runtime
#: raises these as the same ``JaxRuntimeError`` a device fault raises,
#: so only the message tells them apart.
_COMPILE_MARKERS = ("Mosaic failed to compile", "compile permanent error")


def is_compile_error(exc: BaseException) -> bool:
    """Whether ``exc`` is a kernel lowering or program compile failure.

    Deterministic: the same program fails the same way on every retry,
    and a retry on another backend would only hide that this one cannot
    run on the device.
    """
    if any(t.__name__ in _LOWERING_ERRORS for t in type(exc).__mro__):
        return True
    return isinstance(exc, jax.errors.JaxRuntimeError) and any(
        marker in str(exc) for marker in _COMPILE_MARKERS
    )


def is_transient(exc: BaseException) -> bool:
    """Whether a dispatch failure is worth a retry-from-carried-state.

    Injected faults (:class:`ChaosError`) and runtime/device errors are
    transient — the round's inputs are intact, so re-dispatching the same
    carried state can succeed.  :data:`NON_TRANSIENT` types (bad
    arguments, unknown keys) are deterministic programming errors, and
    compile failures (:func:`is_compile_error`) deterministic refusals of
    the program; both propagate immediately.
    """
    return not isinstance(exc, NON_TRANSIENT) and not is_compile_error(exc)


@dataclasses.dataclass
class ChaosMonkey:
    """One seeded fault schedule plus its injection counters.

    Parameters
    ----------
    seed : int, default 0
        Seed of the per-round RNG behind ``error_rate``/``crash_rate``/
        ``poison_rate`` — same seed, same fault sequence.
    fail_rounds : sequence of int, optional
        Dispatch-round indices that raise :class:`ChaosError` before any
        chunk runs.  Round indices count EVERY ``dispatch_round``
        invocation the monkey observes (retries included), so
        ``fail_rounds=(1,)`` fails the second dispatch once and its
        retry — round 2 — succeeds.
    crash_rounds : sequence of int, optional
        Round indices that raise :class:`ShardCrash` before chunk 1 —
        mid-round by construction, so the schedule only fires on rounds
        the chunking actually splits (set ``SolveOptions.chunk_size``).
    poison_rows : mapping {int: sequence of int}, optional
        ``round -> row indices`` whose carried-state rows are overwritten
        with NaN after that round's dispatch (rows past the round's
        batch are ignored).
    delay_rounds : sequence of int, optional
        Round indices to sleep ``delay_s`` before; empty + ``delay_s > 0``
        delays EVERY round.
    delay_s : float, default 0.0
        Artificial pre-round delay in seconds.
    error_rate, crash_rate, poison_rate : float, default 0.0
        Seeded per-round probabilities of the three fault kinds, for
        soak-style tests (deterministic given ``seed``).  ``poison_rate``
        poisons each state row independently.
    max_faults : int, optional
        Stop RAISING faults after this many (delays and poisoning are
        not counted against it) — the "fail N times then recover" knob.
    """

    seed: int = 0
    fail_rounds: Sequence[int] = ()
    crash_rounds: Sequence[int] = ()
    poison_rows: Dict[int, Sequence[int]] = dataclasses.field(
        default_factory=dict
    )
    delay_rounds: Sequence[int] = ()
    delay_s: float = 0.0
    error_rate: float = 0.0
    crash_rate: float = 0.0
    poison_rate: float = 0.0
    max_faults: Optional[int] = None
    # -- counters (read by tests/benchmarks) --------------------------------
    rounds_seen: int = 0
    faults_injected: int = 0
    rows_poisoned: int = 0
    delays_injected: int = 0

    def _rng(self, round_idx: int, salt: int) -> np.random.Generator:
        return np.random.default_rng((self.seed, round_idx, salt))

    def _may_raise(self) -> bool:
        return self.max_faults is None or self.faults_injected < self.max_faults

    def on_round(self, backend_name: str) -> int:
        """Pre-round hook: count the round, maybe delay, maybe raise."""
        r = self.rounds_seen
        self.rounds_seen += 1
        if self.delay_s > 0 and (not self.delay_rounds or r in self.delay_rounds):
            self.delays_injected += 1
            time.sleep(self.delay_s)
        scheduled = r in self.fail_rounds
        rolled = self.error_rate > 0 and (
            self._rng(r, 0).random() < self.error_rate
        )
        if (scheduled or rolled) and self._may_raise():
            self.faults_injected += 1
            raise ChaosError(
                f"chaos: injected backend failure on {backend_name} "
                f"dispatch round {r}"
            )
        return r

    def on_chunk(self, round_idx: int, chunk_no: int) -> None:
        """Per-chunk hook: raise :class:`ShardCrash` mid-round."""
        if chunk_no == 0:
            return  # "mid-round" means at least one chunk already solved
        scheduled = round_idx in self.crash_rounds
        rolled = self.crash_rate > 0 and (
            self._rng(round_idx, chunk_no).random() < self.crash_rate
        )
        if (scheduled or rolled) and self._may_raise():
            self.faults_injected += 1
            raise ShardCrash(
                f"chaos: injected shard crash at chunk {chunk_no} of "
                f"dispatch round {round_idx}"
            )

    def poison_state(self, round_idx: int, state) -> Tuple[object, int]:
        """Post-round hook: NaN-poison scheduled rows of the carried state.

        Returns ``(state, rows_poisoned)`` — the state is returned
        unchanged when nothing is scheduled for this round.
        """
        bsz = int(state.batch)
        rows = [r for r in self.poison_rows.get(round_idx, ()) if r < bsz]
        if self.poison_rate > 0:
            mask = self._rng(round_idx, 2).random(bsz) < self.poison_rate
            rows = sorted(set(rows) | set(np.nonzero(mask)[0].tolist()))
        if not rows:
            return state, 0
        idx = jnp.asarray(rows, jnp.int32)

        def nan_rows(leaf):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            return leaf.at[idx].set(jnp.nan)

        self.rows_poisoned += len(rows)
        return jax.tree_util.tree_map(nan_rows, state), len(rows)


_ACTIVE: Optional[ChaosMonkey] = None


def active() -> Optional[ChaosMonkey]:
    """The currently installed monkey, or None (the clean path)."""
    return _ACTIVE


@contextlib.contextmanager
def inject(monkey: ChaosMonkey) -> Iterator[ChaosMonkey]:
    """Install ``monkey`` as the active fault source for the duration.

    Every ``dispatch_round`` executed under the context consults the
    monkey's hooks; the previous monkey (usually None) is restored on
    exit, exception or not::

        with chaos.inject(chaos.ChaosMonkey(fail_rounds=(1,))) as monkey:
            sol = repro.solve(batch, options)
        assert monkey.faults_injected == 1
    """
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = monkey
    try:
        yield monkey
    finally:
        _ACTIVE = prev

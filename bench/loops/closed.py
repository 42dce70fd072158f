"""Closed loop: whole ``repro.solve`` calls back to back, each on host-resident LPs.

Traffic keys:

- ``pool``: distinct batches made at set-up; the calls cycle through them,
  so no call repeats the one before it;
- ``per_chip``: when true, a call holds ``config.batch`` LPs for each chip
  and the chunk size grows with the chips, so each chip does the one-chip
  cell's work per chunk; the batch dimension is sharded over a
  ``Mesh(devices, ("data",))``.

A call hands ``repro.solve`` the NumPy arrays of a pool batch in the
container the configuration's LP class makes of them (``LPBatch`` by
default, ``SharedLPBatch`` for a class whose LPs share one ``A``), as an
application does, and ends when status, objective, ``x`` and iteration
counts are back on the host.  The window runs whole calls until
``seconds`` have passed; ``lps_per_s`` is the LPs that reached a final
status over the time from the first call's start to the last call's end.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np

from bench.loops import Block, Record
from bench.loops.spans import Spans

FINAL = (1, 2, 3)  # optimal, unbounded, infeasible


@dataclasses.dataclass
class State:
    repro: object
    inputs: object
    options: object
    mesh: object
    pool: List[tuple]
    m: int
    n: int
    notes: List[str]
    calls: int = 0


def setup(cell, seed, seconds, devices) -> State:
    import jax
    import jax.numpy as jnp

    import repro
    from repro.core import dispatch

    cfg, traffic = cell.config, cell.traffic
    m, n = cfg["m"], cfg["n"]
    chips = len(devices) if traffic.get("per_chip") else 1
    opts = dict(cfg["options"])
    if opts.get("chunk_size"):
        opts["chunk_size"] = opts["chunk_size"] * chips
    options = repro.SolveOptions(**opts)
    mesh = jax.sharding.Mesh(np.array(devices), ("data",)) if chips > 1 else None
    # Each chip's share is drawn as its own block, so no device holds more
    # than one block's draw at a time.
    pool = []
    for i in range(int(traffic["pool"])):
        parts = [cell.inputs.draw(cfg, seed, i * chips + k, cfg["batch"])
                 for k in range(chips)]
        pool.append(tuple(np.concatenate(p) if chips > 1 else p[0] for p in zip(*parts)))
    shared = isinstance(cell.inputs.problem(repro, *pool[0]), repro.SharedLPBatch)
    routed = dispatch.resolve_backend(m, n, jnp.float32, options, shared=shared,
                                      batch=pool[0][1].shape[0])
    notes = [f"LPs per call {pool[0][1].shape[0]}, m={m}, n={n}, chunk_size "
             f"{options.chunk_size}, chips {chips}; {options.backend!r} routes to "
             f"{routed.backend!r} (layout {routed.layout}, tile_b {routed.tile_b})"]
    state = State(repro, cell.inputs, options, mesh, pool, m, n, notes)
    _call(state, 0, Spans())  # warm-up: the window's only shape
    state.calls = 1  # the window starts on the next batch of the pool
    return state


def _call(state: State, i: int, spans: Spans):
    batch = state.pool[i % len(state.pool)]
    with spans("solve"):
        sol = state.repro.solve(state.inputs.problem(state.repro, *batch), state.options,
                                mesh=state.mesh)
    with spans("result"):
        out = (np.asarray(sol.status), np.asarray(sol.objective), np.asarray(sol.x),
               np.asarray(sol.iterations))
    return out


def window(state: State, seconds: float) -> Record:
    spans = Spans()
    calls = []
    start = time.perf_counter()
    while True:
        i = state.calls
        state.calls += 1
        t0 = time.perf_counter()
        out = _call(state, i, spans)
        t1 = time.perf_counter()
        calls.append((i % len(state.pool), t0, t1, out))
        if t1 - start >= seconds:
            break
    lps = sum(len(out[0]) for *_, out in calls)
    final = sum(int(np.isin(out[0], FINAL).sum()) for *_, out in calls)
    elapsed = calls[-1][2] - calls[0][1]
    return Record(
        attempted=lps,
        end_to_end={"lps_per_s": final / elapsed},
        spans=spans.items,
        data={"calls": calls, "elapsed_s": elapsed, "final": final},
    )


def after_trace(state: State, record: Record) -> dict:
    """Counters of one untraced call per pool batch, with ``SolveStats`` on.

    ``SolveStats`` reads every chunk's iterations as it finishes, which
    puts a host sync between chunks, so it stays out of the window.
    """
    stats = state.repro.SolveStats()
    for batch in state.pool:
        sol = state.repro.solve(state.inputs.problem(state.repro, *batch), state.options,
                                mesh=state.mesh, stats=stats)
        np.asarray(sol.status)
    return {"stats": stats, "m": state.m, "n": state.n}


def answers(state: State, record: Record) -> List[Block]:
    blocks = []
    for p, _, _, (status, objective, x, iterations) in record.data["calls"]:
        a, b, c = state.pool[p]
        blocks.append(Block(a, b, c, status, objective, x, iterations))
    return blocks


def release(state: State) -> None:
    """Nothing to drop: a call leaves its results on the host only."""

"""Open loop: single-LP requests at scheduled arrival times into ``LPEngine``.

Traffic keys:

- ``rate``: offered load in requests per second, fixed in the file;
- ``warm_seconds``: length of the warm-up replay at the same rate (its own
  requests), after ``warm_bursts``: one burst of each size from 1 to that
  size, each stepped to completion, so that the engine's programs for
  every group size it meets compile in set-up (its dispatch pads to
  powers of two, but its host-side gathers and scatters run at the
  group's exact size);
- ``drain_seconds``: how long past the window's end the loop keeps
  stepping to finish the window's requests;
- ``trace_seconds``: the window of a traced run.

The requests are the ``requests`` that the configuration's LP class
makes of its draw (``bench/lpgen.py``).  The window holds
``round(rate * seconds)`` of them; their gaps are exponential draws from
the seed, scaled so the last arrives just before the window ends, so
every seed offers the same work.  The loop is the
continuous mode of ``LPEngine``: it submits each request when it is due,
calls ``step()`` while work is pending or in flight, and sleeps until the
next arrival when there is none.  A request's latency runs from its
scheduled arrival until the ``step()`` that returns its ticket, after
which ``result()`` redeems it (adapted from ``serve/loadgen.py:replay``,
copied so that a change to the program cannot change the yardstick).
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import List

import numpy as np

from bench.loops import Block, Record, percentile
from bench.loops.spans import Spans


@dataclasses.dataclass
class State:
    repro: object
    engine: object
    problems: list
    data: tuple
    arrivals: np.ndarray
    drain_seconds: float
    notes: List[str]


def arrival_times(seed: int, count: int, seconds: float) -> np.ndarray:
    """``count`` Poisson arrivals whose span is scaled to end inside ``seconds``."""
    gaps = np.random.default_rng([int(seed), 7]).exponential(1.0, size=count + 1)
    times = np.cumsum(gaps)
    return times[:-1] / times[-1] * seconds


def _problems(repro, cell, seed, salt, count):
    a, b, c = cell.inputs.draw(cell.config, seed, salt, count)
    return cell.inputs.requests(repro, a, b, c), (a, b, c)


def _replay(engine, problems, arrivals, limit, spans: Spans):
    """Drive the engine through one trace; returns per-request times."""
    count = len(problems)
    tickets = [None] * count
    by_ticket = {}
    submitted = np.full(count, np.nan)
    finished = np.full(count, np.nan)
    completed_per_step = []
    clock = time.perf_counter
    start = clock()
    i = 0
    done = 0
    while done < count and clock() - start < limit:
        now = clock() - start
        while i < count and arrivals[i] <= now:
            with spans("submit"):
                tk = engine.submit(problems[i])
            submitted[i] = clock() - start
            tickets[i] = tk
            by_ticket[tk] = i
            i += 1
        if engine.pending_count or engine.inflight_count:
            with spans("step"):
                out = engine.step()
            t = clock() - start
            completed_per_step.append(len(out))
            for tk in out:
                finished[by_ticket[tk]] = t
            done += len(out)
        elif i < count:
            time.sleep(max(0.0, arrivals[i] - (clock() - start)))
    return tickets, submitted, finished, completed_per_step


def setup(cell, seed, seconds, devices) -> State:
    import repro
    from repro.serve.engine import LPEngine

    cfg, traffic = cell.config, cell.traffic
    rate = float(traffic["rate"])
    count = int(round(rate * seconds))
    problems, data = _problems(repro, cell, seed, 0, count)
    arrivals = arrival_times(seed, count, seconds)
    options = repro.SolveOptions(**cfg["options"])
    engine = LPEngine(options, flush_every=1 << 30)
    # Warm-up: a burst of every size up to warm_bursts, then a replay at
    # the cell's rate on requests of their own.
    bursts = list(range(1, int(traffic["warm_bursts"]) + 1))
    warm_count = int(round(rate * float(traffic["warm_seconds"])))
    warm, _ = _problems(repro, cell, seed, 1, max(warm_count, sum(bursts)))
    spans = Spans()
    used = 0
    for size in bursts:
        tickets = [engine.submit(p) for p in warm[used : used + size]]
        used += size
        while not all(engine.done(tk) for tk in tickets):
            engine.step()
        for tk in tickets:
            engine.result(tk)
    tickets, *_ = _replay(engine, warm[:warm_count],
                          arrival_times(seed + 1, warm_count, float(traffic["warm_seconds"])),
                          float(traffic["warm_seconds"]) + float(traffic["drain_seconds"]), spans)
    for tk in tickets:
        if tk is not None and engine.done(tk):
            engine.result(tk)
    resolved = engine.session.resolve_options(cfg["m"], cfg["n"], np.float32)
    notes = [f"rate {rate} req/s, {count} requests in the window, m={cfg['m']}, n={cfg['n']}; "
             f"{options.backend!r} routes to {resolved.backend!r}; warm-up {warm_count} "
             f"requests and bursts of 1 to {bursts[-1]}; engine compiles {engine.stats.compiles}"]
    return State(repro, engine, problems, data, arrivals,
                 float(traffic["drain_seconds"]), notes)


def window(state: State, seconds: float) -> Record:
    count = int(np.searchsorted(state.arrivals, seconds))
    spans = Spans()
    tickets, submitted, finished, per_step = _replay(
        state.engine, state.problems[:count], state.arrivals[:count],
        seconds + state.drain_seconds, spans)
    latency = finished - state.arrivals[:count]
    ok = latency[np.isfinite(latency)]
    end_to_end = {}
    if ok.size:
        end_to_end = {"latency_p50_ms": 1e3 * percentile(ok, 50),
                      "latency_p95_ms": 1e3 * percentile(ok, 95)}
    return Record(
        attempted=count,
        end_to_end=end_to_end,
        spans=spans.items,
        data={"tickets": tickets, "latency_s": latency,
              "late_s": submitted - state.arrivals[:count], "per_step": per_step},
    )


def after_trace(state: State, record: Record) -> dict:
    return {}


def answers(state: State, record: Record) -> List[Block]:
    tickets = record.data["tickets"]
    count = len(tickets)
    a, b, c = state.data
    if a.ndim == 3:  # one A per LP; a 2-D ``a`` is shared by every row
        a = a[:count]
    b, c = b[:count], c[:count]
    status = np.zeros(count, np.int32)
    objective = np.full(count, np.nan, np.float32)
    x = np.zeros((count, c.shape[1]), np.float32)
    iterations = np.zeros(count, np.int32)
    for i, tk in enumerate(tickets):
        if tk is None or not state.engine.done(tk):
            continue
        sol = state.engine.result(tk)
        status[i] = int(np.asarray(sol.status)[0])
        objective[i] = np.asarray(sol.objective)[0]
        x[i] = np.asarray(sol.x)[0]
        iterations[i] = int(np.asarray(sol.iterations)[0])
    return [Block(a, b, c, status, objective, x, iterations)]


def release(state: State) -> None:
    state.engine = None
    state.problems = []
    gc.collect()

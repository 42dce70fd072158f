"""Batched-LP serving: megabatch dispatch with straggler mitigation.

The production picture: LP requests stream in (e.g., support-function
samples from a fleet of reachability workers), are bucketed by (m, n)
shape, megabatched, and dispatched to device groups; deadline-based
speculative re-dispatch covers stragglers (runtime/straggler.py).

Homogeneous mode solves one shape through ``repro.solve(LPBatch)``;
``--mixed-dims`` serves a heterogeneous request stream through the shape
bucketing front-end (one ``repro.solve(list_of_problems)`` call per unit).

Example:
  PYTHONPATH=src python -m repro.launch.serve_lp --n-lps 20000 --dim 28 \
      --units 8 --workers 4
  PYTHONPATH=src python -m repro.launch.serve_lp --n-lps 2000 \
      --mixed-dims 5,12,28 --units 4 --workers 4
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from .. import api
from ..core import lp as lp_mod
from ..core.backends import SolveOptions
from ..core.problem import LPProblem
from ..runtime import compile_cache
from ..runtime.straggler import run_with_speculation


def _hetero_requests(rng, n_lps, dims):
    """A synthetic heterogeneous request stream: one LPProblem per request."""
    problems = []
    for _ in range(n_lps):
        d = int(rng.choice(dims))
        b = lp_mod.random_lp_batch(rng, 1, d, d, True)
        problems.append(LPProblem.make(b.c, b.a, bu=b.b))
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-lps", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=28)
    ap.add_argument("--mixed-dims", default=None,
                    help="comma-separated dims; enables heterogeneous bucketed serving")
    ap.add_argument("--units", type=int, default=8)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--rule", default="lpc", choices=["lpc", "rpc", "bland"])
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "reference"])
    ap.add_argument("--inject-straggler", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    rng = np.random.default_rng(0)
    options = SolveOptions(rule=args.rule, backend=args.backend)

    if args.mixed_dims:
        dims = [int(d) for d in args.mixed_dims.split(",")]
        problems = _hetero_requests(rng, args.n_lps, dims)
        per = -(-len(problems) // args.units)  # ceil: slices cover every problem
        units = [problems[i * per : (i + 1) * per] for i in range(args.units)]
        units = [u for u in units if u]
        # warm every shape class deterministically (one problem per dim)
        warm_batches = [lp_mod.random_lp_batch(rng, 1, d, d, True) for d in dims]
        api.solve([LPProblem.make(b.c, b.a, bu=b.b) for b in warm_batches], options)

        slow_unit = {0} if args.inject_straggler else set()

        def solve_unit(payload, worker):
            if payload is units[0] and 0 in slow_unit and worker == 0:
                time.sleep(1.0)  # injected straggler: first attempt is slow
            sols = api.solve(payload, options)
            return np.asarray([float(s.objective[0]) for s in sols])

    else:
        batch = lp_mod.random_lp_batch(rng, args.n_lps, args.dim, args.dim, True)
        # warm the executable so unit timings reflect steady-state serving
        warm = lp_mod.LPBatch(batch.a[:8], batch.b[:8], batch.c[:8])
        api.solve(warm, options).objective.block_until_ready()

        per = args.n_lps // args.units
        units = [
            lp_mod.LPBatch(
                batch.a[i * per : (i + 1) * per],
                batch.b[i * per : (i + 1) * per],
                batch.c[i * per : (i + 1) * per],
            )
            for i in range(args.units)
        ]

        slow_unit = {0} if args.inject_straggler else set()

        def solve_unit(payload, worker):
            if payload is units[0] and 0 in slow_unit and worker == 0:
                time.sleep(1.0)  # injected straggler: first attempt is slow
            sol = api.solve(payload, options)
            sol.objective.block_until_ready()
            return np.asarray(sol.objective)

    t0 = time.perf_counter()
    report = run_with_speculation(
        units, solve_unit, n_workers=args.workers, alpha=3.0
    )
    wall = time.perf_counter() - t0
    n_opt = sum(int((np.isfinite(r.value)).sum()) for r in report.results)
    shape_note = f"mixed dims {args.mixed_dims}" if args.mixed_dims else f"dim {args.dim}"
    print(
        f"solved {args.n_lps} LPs {shape_note} in {wall:.3f}s "
        f"({args.n_lps / wall:.0f} LP/s), optimal={n_opt}, "
        f"speculative re-dispatches={report.respawned}"
    )


if __name__ == "__main__":
    main()

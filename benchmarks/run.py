"""Benchmark driver: one module per paper table/figure.

``python -m benchmarks.run [--full] [--only fig8,table1,...]``
prints ``name,us_per_call,derived`` CSV rows per benchmark.
"""

from __future__ import annotations

import argparse
import traceback

from repro.runtime import compile_cache

from . import (
    fig5_layout,
    fig6_transfer,
    fig8_feasible,
    fig9_infeasible,
    fig10_cpu_threads,
    fig_autotune,
    fig_compaction,
    fig_dispatch,
    fig_faults,
    fig_frontier,
    fig_memory,
    fig_rules,
    fig_serve,
    fig_shared,
    roofline,
    table1_hyperbox,
    table2_reach,
)

BENCHES = {
    "fig5": fig5_layout.run,
    "fig6": fig6_transfer.run,
    "fig8": fig8_feasible.run,
    "fig9": fig9_infeasible.run,
    "fig10": fig10_cpu_threads.run,
    "table1": table1_hyperbox.run,
    "table2": table2_reach.run,
    "autotune": fig_autotune.run,
    "compaction": fig_compaction.run,
    "dispatch": fig_dispatch.run,
    "faults": fig_faults.run,
    "frontier": fig_frontier.run,
    "memory": fig_memory.run,
    "rules": fig_rules.run,
    "serve": fig_serve.run,
    "shared": fig_shared.run,
    "roofline": roofline.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", default=None, help="comma-separated subset")
    args = ap.parse_args()
    compile_cache.enable()
    names = list(BENCHES) if not args.only else args.only.split(",")
    failures = []
    for name in names:
        print(f"## {name}", flush=True)
        try:
            BENCHES[name](full=args.full)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()

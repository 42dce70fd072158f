"""The control of the comparison: the reference in the program's place, in bfloat16.

    python3 bench/control.py --workload fig8_m100.batch --seeds 11 12 13

For each seed it makes the cell's inputs as a run does (the first batch
of the pool, or the window's requests), draws the sample as a run does,
answers the sampled rows with the reference computed in bfloat16, the
precision below the configuration's float32, and prints the numbers the
comparison gives them beside the configuration's limits.  The control
has to break one limit on every seed.  The benchmark's own runs do not
run it; ``bench/tests/test_bench_loops.py`` runs it at a small size.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, seconds: float, precision: str = "bfloat16") -> dict:
    """The control's numbers for one seed of a run of ``seconds``."""
    from bench import check
    from bench.loops import Block

    cfg = cell.config
    if cell.traffic["loop"] == "open":
        count = int(round(float(cell.traffic["rate"]) * seconds))
    else:
        count = cfg["batch"]
    a, b, c = cell.inputs.draw(cfg, seed, 0, count)
    rows = len(b)
    empty = Block(a, b, c, np.ones(rows, np.int32), np.zeros(rows, np.float32),
                  np.zeros(c.shape, np.float32), np.zeros(rows, np.int32))
    picks = check.sample_rows([empty], seed, int(cfg["check_sample"]))
    ref = check.reference_answers([empty], picks)
    ctrl_blocks, ctrl_picks = check.control_blocks([empty], picks, precision)
    numbers = check.numbers_against(ctrl_blocks, ctrl_picks, ref)
    failed = [k for k, v in cfg["limits"].items() if numbers[k] > v]
    return {"seed": seed, "rows": len(picks), "numbers": numbers, "fails": failed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import cells

    cell = cells.load(ROOT, args.workload)
    seconds = json.loads((ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    ok = True
    for seed in args.seeds:
        out = readings(cell, seed, seconds)
        ok &= bool(out["fails"])
        print(json.dumps({"workload": args.workload, **out,
                          "limits": cell.config["limits"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

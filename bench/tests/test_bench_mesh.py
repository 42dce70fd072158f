"""The per-chip closed mix shards each call over a mesh: run on four virtual CPU devices.

The mix (``bench/traffic/closed_pool2_per_chip.json``) is for a four-chip
cell that a later change adds as data alone; this runs it end to end in
a child process that has four host devices, in a tiny copy of the
benchmark with a dummy cell.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]

CHILD = r"""
import json, sys, time
sys.path[:0] = [{here!r}, {repo!r}, {src!r}]
from pathlib import Path
import jax
from bench_tiny import make_tiny
from bench import run
assert len(jax.devices()) == 4, jax.devices()
root = make_tiny(Path({dest!r}))
bench = json.loads((root / "BENCHMARK.json").read_text())
bench["workloads"].append({{"name": "mesh.batch", "config": "paper_feasible_m100",
                           "traffic": "closed_pool2_per_chip", "chips": 4, "why": "dummy"}})
for m in bench["end_to_end"] + bench["per_layer"]:
    if "fig8_m100.batch" in m.get("workloads", []):
        m["workloads"].append("mesh.batch")
(root / "BENCHMARK.json").write_text(json.dumps(bench))
res = run.run_cell(root, "mesh.batch", 2**33 + 9, 1.0, False, jax.devices(), time.perf_counter())
print(json.dumps(res))
"""


def test_per_chip_mix_runs_sharded_on_four_devices(tmp_path):
    code = CHILD.format(here=str(HERE), repo=str(REPO), src=str(REPO / "src"),
                        dest=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checked"]
    assert res["attempted"] % (4 * 64) == 0  # 64 LPs per chip and call
    assert res["device"]["count"] == 4

"""A tiny copy of the benchmark to run on the CPU, for the benchmark's own tests.

The copy keeps every file and entry of the benchmark and shrinks only
the sizes (m, n, LPs per call, chunk, sample, serve rate and warm-up),
so the harness's own functions run end to end in seconds.  The chip
check of ``bench/run.py:main`` is the one part they skip.

A configuration may carry its own tiny sizes: ``"tiny": {"cpu": {...},
"control": {...}}``, the keys that the copy sets on the CPU, and the
``m`` and ``n`` at which the control test reads the bfloat16 control.
Without it the copy takes ``TINY_CONFIG`` and the control ``TINY_CONTROL``.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {"m": 6, "n": 5, "batch": 64, "check_sample": 16}
TINY_CONTROL = {"m": 40, "n": 40}  # at m=n=6 bfloat16 rounding stays under the limits
TINY_CHUNK = 32
TINY_OPEN = {"rate": 20, "warm_seconds": 1, "warm_bursts": 4, "drain_seconds": 60}


#: The served cell that ``bench/loops/open.py`` and ``bench/traffic/poisson_single.json``
#: are for, as data entries; not yet in BENCHMARK.json (its knee is not measured).
SERVED_CELL = {"name": "fig8_m100.serve", "config": "paper_feasible_m100",
               "traffic": "poisson_single", "chips": 1,
               "why": "open-loop Poisson single-LP requests into LPEngine's continuous mode"}
SERVED_METRICS = [
    {"name": n, "unit": "ms", "better": "lower", "bound": 0.1, "source": "host_clock",
     "workloads": ["fig8_m100.serve"]} for n in ("latency_p50_ms", "latency_p95_ms")]
SERVED_LAYER = [
    {"name": n, "unit": u, "better": b, "source": src, "layer": layer, "moves": moves,
     "workloads": ["fig8_m100.serve"]}
    for n, u, b, src, layer, moves in (
        ("device.idle_share.serve", "%", "lower", "device_trace", "device", "latency_p95_ms"),
        ("serve.lps_per_step", "LP", "higher", "program_counter", "serving", "latency_p95_ms"),
        ("serve.step_ms_p50", "ms", "lower", "host_clock", "serving", "latency_p50_ms"),
        ("frontend.submit_us", "us", "lower", "host_clock", "front end", "latency_p50_ms"),
        ("loadgen.late_ms_p95", "ms", "lower", "host_clock", "load generator",
         "latency_p95_ms"))]


def control_sizes(cfg: dict) -> dict:
    """The ``m`` and ``n`` at which the control test reads a configuration's control."""
    return cfg.get("tiny", {}).get("control", TINY_CONTROL)


def make_tiny(dest: Path, source: Path = REPO) -> Path:
    """Copy the benchmark at ``source`` to ``dest`` at tiny sizes, plus the served cell.

    Returns the copy's root.
    """
    shutil.copytree(source / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((source / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        path = dest / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(cfg.get("tiny", {}).get("cpu", TINY_CONFIG))
        if cfg["options"].get("chunk_size"):
            cfg["options"]["chunk_size"] = TINY_CHUNK
        path.write_text(json.dumps(cfg))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        traffic = json.loads(path.read_text())
        if traffic["loop"] == "open":
            traffic.update(TINY_OPEN)
        path.write_text(json.dumps(traffic))
    bench["workloads"].append(SERVED_CELL)
    bench["end_to_end"] += SERVED_METRICS
    bench["per_layer"] += SERVED_LAYER
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


def run(root: Path, workload: str, seed: int = 2**33 + 17, seconds: float = 1.0) -> dict:
    """One run of ``workload`` in the tiny copy at ``root``, on the CPU; its result."""
    import jax

    from bench import run as bench_run

    return bench_run.run_cell(root, workload, seed, seconds, False, jax.devices(),
                              time.perf_counter())


def broken_round(kind: str):
    """A ``dispatch_round`` that breaks the round's answers the way ``kind`` says.

    ``unchanged``: the round returns its state unchanged, with made-up
    optimal rows; ``half``: the second half of the batch left out;
    ``altered``: the first row's answer altered where it is produced.
    """
    import jax.numpy as jnp

    from repro.core import dispatch

    original = dispatch.dispatch_round

    def broken(batch, options, *args, **kwargs):
        sol, state = original(batch, options, *args, **kwargs)
        rows = sol.status.shape[0]
        if kind == "unchanged":
            sol = sol.__class__(objective=jnp.zeros_like(sol.objective),
                                x=jnp.zeros_like(sol.x),
                                status=jnp.ones_like(sol.status),
                                iterations=jnp.zeros_like(sol.iterations), basis=sol.basis)
            state = kwargs.get("state")
        elif kind == "half":
            keep = jnp.arange(rows) < (rows + 1) // 2
            sol = sol.__class__(objective=jnp.where(keep, sol.objective, -jnp.inf),
                                x=jnp.where(keep[:, None], sol.x, 0.0),
                                status=jnp.where(keep, sol.status, 0),
                                iterations=sol.iterations, basis=sol.basis)
        elif kind == "altered":
            sol = sol.__class__(objective=sol.objective.at[0].multiply(1.1),
                                x=sol.x.at[0].multiply(1.1), status=sol.status,
                                iterations=sol.iterations, basis=sol.basis)
        return sol, state

    return broken

"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload fig8_m100.batch --seed 7 --seconds 30 --trace 0

from the root of a checkout.  The cell's configuration, traffic mix and
per-layer metric readers are found by name (``bench/cells.py``).  A run:

1. fails (exit 2, no result) unless JAX finds a TPU with at least the
   chips the cell asks for;
2. sets up: the persistent compilation cache in the checkout's
   ``.jax_cache/``, inputs made from ``--seed``, and a warm-up of every
   shape the window uses.  That is ``setup_s``;
3. measures for ``--seconds`` (``--trace 1``: under the profiler, and
   reports the per-layer metrics instead of the end-to-end ones);
4. reads the device's peak memory, then checks what the timed path
   returned against the float64 reference (``bench/check.py``);
5. prints the numbers compared, each beside its limit, as the last lines
   of standard error, and one JSON object as the last line of standard
   output.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from the start of the process

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             devices, t_setup: float) -> dict:
    """Set up, measure, check; returns the result object.

    ``t_setup`` is the ``perf_counter`` reading at which set-up began.
    """
    from bench import cells, check, loops, peaks, tracing

    cell = cells.load(root, workload)
    devices = list(devices)[: cell.chips]
    counter = tracing.CompileCounter()
    loop = loops.get(cell.traffic["loop"])
    state = loop.setup(cell, seed, seconds, devices)
    setup_s = time.perf_counter() - t_setup
    _log(f"set-up {setup_s:.3f} s; compiles in set-up {counter.snapshot()}")
    for line in state.notes:
        _log(line)

    counter.reset()
    profile = None
    if trace:
        window = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
        with tracing.Profile() as profile:
            record = loop.window(state, window)
    else:
        record = loop.window(state, seconds)
    in_window = counter.snapshot()
    _log(f"compiles in the window: {in_window}")
    memory_peak = _memory_peak(devices)
    device = {**_device_info(devices), "memory_peak_bytes": memory_peak}

    metrics = {}
    breakdown = None
    if trace:
        reduced = tracing.reduce(profile.path, len(devices))
        profile.cleanup()
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        ctx = loops.ReadContext(cell=cell, record=record, trace=reduced,
                                peaks=peaks.for_kind(devices[0].device_kind),
                                extra=loop.after_trace(state, record))
        for m in cell.per_layer:
            value = cell.readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        for line in ctx.notes:
            _log(line)
    else:
        values = dict(record.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    answers = loop.answers(state, record)
    loop.release(state)
    numbers = check.compare(answers, cell.config, seed)
    limits = cell.config["limits"]
    correct = all(numbers[k] <= limits[k] for k in limits)
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    for k, v in compared.items():
        _log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    result = {
        "correct": bool(correct),
        "attempted": int(record.attempted),
        "failed": int(numbers["unanswered"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checked"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import cells

    chips = cells.load(ROOT, args.workload).chips
    import jax

    devices = jax.devices()
    info = _device_info(devices)
    _log(f"device {json.dumps(info)}")
    if info["platform"] != "tpu":
        _log("no TPU found; the benchmark runs on the chip only")
        return 2
    if info["count"] < chips:
        _log(f"{chips} chips asked, {info['count']} found")
        return 2
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
                      devices, T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Smoke test of the batched LP solver on a TPU, through its public API.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the batch-sharded path only

One process drives the chip.  Each phase solves seeded float32 LPs at
the paper's sizes through ``repro.solve`` / ``repro.solve_hyperbox`` /
``LPEngine``, then prints one line: the backend and driver that ran, the
``SolveStats`` counters, the status counts, the device's memory figures,
and the agreement with the float64 oracle (``core/oracle.py``) on a
seeded sample of rows.  A phase that fails — an exception, a driver
other than the one it expects, a routing fallback or retry, an OPTIMAL
row that disagrees with the oracle — ends the run with a nonzero exit.
The count of rows a ``pallas`` backend returns bit-identical to its
``xla`` twin is printed as a finding, not checked.

The last line is ``{"ok": true, "device": {...}}``.  Without a TPU, or
without the repository around it, the script exits nonzero first.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

import numpy as np

#: Oracle sample per phase, and the objective agreement it must show on
#: rows both sides solve to OPTIMAL.  Simplex: float32 pivoting against
#: the float64 oracle, whose error grows with the LP's conditioning — the
#: worst sampled Fig. 9 row (optimum 73,157, 150x the sample's median)
#: is off by 9.7e-4 under the XLA driver on a CPU, so 1e-3 would sit at
#: the edge.  PDHG: the backend's 1e-4 relative KKT tolerance.
SAMPLE = 64
RTOL = {"simplex": 1e-2, "pdhg": 1e-2}

STATUS = {0: "running", 1: "optimal", 2: "unbounded", 3: "infeasible",
          4: "iter_limit", 5: "numerical"}


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# seeded data, made on the device
# ---------------------------------------------------------------------------


def _constraints(key, bsz, m, n):
    """U(-1, 1) rows with a strengthened diagonal (``lp.random_lp_batch``)."""
    import jax
    import jax.numpy as jnp

    a = jax.random.uniform(key, (bsz, m, n), jnp.float32, -1.0, 1.0)
    diag = jnp.eye(m, n, dtype=bool)
    return jnp.where(diag, jnp.abs(a) + 1.0, a)


def feasible_batch(seed, bsz, m, n):
    """Fig. 8 class: b > 0, so the origin is a feasible start."""
    import jax
    import jax.numpy as jnp

    ka, kb, kc = jax.random.split(jax.random.key(seed), 3)
    a = _constraints(ka, bsz, m, n)
    b = jax.random.uniform(kb, (bsz, m), jnp.float32, 1.0, 10.0)
    c = jax.random.uniform(kc, (bsz, n), jnp.float32, 0.1, 1.0)
    return a, b, c


def two_phase_batch(seed, bsz, m, n):
    """Fig. 9 class: feasible at a random interior x0, but many b_i < 0.

    ``b = A x0 + slack`` for ``x0`` in [0.5, 1.5]: rows whose ``A x0`` is
    negative give ``b_i < 0``, so the origin is infeasible and the solver
    runs phase I first.
    """
    import jax
    import jax.numpy as jnp

    ka, kx, ks, kc = jax.random.split(jax.random.key(seed), 4)
    a = _constraints(ka, bsz, m, n)
    x0 = jax.random.uniform(kx, (bsz, n), jnp.float32, 0.5, 1.5)
    slack = jax.random.uniform(ks, (bsz, m), jnp.float32, 0.1, 1.0)
    b = jnp.einsum("bmn,bn->bm", a, x0, precision=jax.lax.Precision.HIGHEST) + slack
    c = jax.random.uniform(kc, (bsz, n), jnp.float32, 0.1, 1.0)
    return a, b, c


# ---------------------------------------------------------------------------
# what a phase reports
# ---------------------------------------------------------------------------


def _kernel_caches():
    from repro.kernels import ops

    return {
        "pallas": ops.compile_cache_size(),
        "pdhg": ops.pdhg_compile_cache_size(),
        "pallas-shared": ops.revised_compile_cache_size(),
        "hyperbox": int(ops.hyperbox_support._cache_size()),
    }


def _memory():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {
        k: stats[k]
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in stats
    }


def _status_counts(status) -> dict:
    values, counts = np.unique(np.asarray(status), return_counts=True)
    return {STATUS.get(int(v), str(v)): int(c) for v, c in zip(values, counts)}


def run_solve(repro, label, problem, options, kernel=None, **kw):
    """Solve once; fail on a fallback warning, a retry, or the wrong driver.

    ``kernel`` names the Mosaic kernel this backend must compile
    (a key of :func:`_kernel_caches`), or None for an XLA driver.
    """
    import jax

    from repro.core import backends

    backends.reset_warnings()
    stats = repro.SolveStats()
    before = _kernel_caches()
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sol = repro.solve(problem, options, stats=stats, **kw)
        jax.block_until_ready(sol.status)
    wall = time.perf_counter() - t0
    grew = [k for k, v in _kernel_caches().items() if v > before[k]]
    reroutes = [str(w.message) for w in caught if " backend: " in str(w.message)]
    driver = "mosaic:" + ",".join(grew) if grew else "xla"
    info = {
        "backend": options.backend,
        "driver": driver,
        "retries": stats.retries,
        "compiles": stats.compiles,
        "cache_hits": stats.cache_hits,
        "rounds": stats.rounds,
        "status": _status_counts(sol.status),
        "wall_s_incl_compile": wall,
        "memory": _memory(),
    }
    if reroutes:
        raise RuntimeError(f"{label}: the backend rerouted: {reroutes}")
    if stats.retries:
        raise RuntimeError(f"{label}: {stats.retries} dispatch retries")
    if kernel is not None and kernel not in grew and stats.compiles:
        raise RuntimeError(f"{label}: expected the {kernel} kernel, ran {driver}")
    if kernel is None and grew:
        raise RuntimeError(f"{label}: expected an XLA driver, ran {driver}")
    return sol, info


def reference(a, b, c):
    """Float64 oracle (objective, status) of sampled rows; ``a`` may be shared."""
    from repro.core import oracle

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    c = np.asarray(c, np.float64)
    out = [oracle.solve_lp(a if a.ndim == 2 else a[k], b[k], c[k]) for k in range(len(b))]
    return np.array([o[0] for o in out]), np.array([o[2] for o in out])


def oracle_check(label, sol, rows, ref, rtol):
    """Objective agreement with the float64 oracle on the sampled rows.

    Fails if a row both sides solve to OPTIMAL disagrees beyond ``rtol``,
    or if the device certifies OPTIMAL where the oracle proves the LP
    infeasible or unbounded.
    """
    ref_obj, ref_status = ref
    obj = np.asarray(sol.objective, np.float64)[rows]
    status = np.asarray(sol.status)[rows]
    both = (status == 1) & (ref_status == 1)
    err = np.zeros(len(rows))
    err[both] = np.abs(obj[both] - ref_obj[both]) / (1.0 + np.abs(ref_obj[both]))
    wrong = rows[(err > rtol) | ((status == 1) & np.isin(ref_status, (2, 3)))]
    if wrong.size:
        raise RuntimeError(f"{label}: oracle disagrees on rows {wrong[:5].tolist()}")
    return {"sampled": len(rows), "both_optimal": int(both.sum()),
            "oracle_status": _status_counts(ref_status),
            "max_rel_err": float(err.max()), "rtol": rtol}


def bit_identical(p, q) -> int:
    """Rows whose objective, status and x are bit-for-bit the same."""
    same = (np.asarray(p.status) == np.asarray(q.status))
    same &= np.asarray(p.objective).view(np.int32) == np.asarray(q.objective).view(np.int32)
    px, qx = np.asarray(p.x), np.asarray(q.x)
    same &= np.all(px.view(np.int32) == qx.view(np.int32), axis=1)
    return int(same.sum())


def _sample(seed, bsz):
    return np.sort(np.random.default_rng(seed).choice(bsz, SAMPLE, replace=False))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_pair(repro, name, a, b, c, seed, chunk, backends_, shared=False):
    """One batch on a ``pallas`` backend and its ``xla`` twin."""
    import jax

    if shared:
        problem = repro.SharedLPBatch(a, b, c)
    else:
        problem = repro.LPBatch(a, b, c)
    bsz = b.shape[0]
    rows = _sample(seed, bsz)
    idx = jax.numpy.asarray(rows)
    ref = reference(a if shared else a[idx], b[idx], c[idx])
    sols = {}
    for backend, kernel in backends_:
        opts = repro.SolveOptions(backend=backend, chunk_size=chunk)
        sol, info = run_solve(repro, f"{name}/{backend}", problem, opts, kernel)
        info["oracle"] = oracle_check(f"{name}/{backend}", sol, rows, ref, RTOL["simplex"])
        _emit(name, batch=bsz, m=b.shape[1], n=c.shape[1], chunk_size=chunk, **info)
        sols[backend] = sol
    (p, _), (x, _) = backends_
    _emit(name, bit_identical=bit_identical(sols[p], sols[x]), of=bsz,
          pair=[p, x])


def phase_hyperbox(repro, seed, bsz=1 << 22, n=5):
    """Table 1: one box, ~4M directions, against the closed form in NumPy."""
    import jax

    from repro.core import backends

    k1, k2 = jax.random.split(jax.random.key(seed))
    lo = -jax.random.uniform(k1, (n,), jax.numpy.float32, 0.5, 2.0)
    hi = lo + jax.random.uniform(k2, (n,), jax.numpy.float32, 0.5, 3.0)
    d = jax.random.normal(jax.random.key(seed + 1), (bsz, n), jax.numpy.float32)
    d64 = np.asarray(d, np.float64)
    ref = np.sum(d64 * np.where(d64 < 0, np.asarray(lo, np.float64),
                                np.asarray(hi, np.float64)), axis=1)
    sols = {}
    for backend, kernel in (("xla", None), ("pallas", "hyperbox")):
        backends.reset_warnings()
        stats = repro.SolveStats()
        before = _kernel_caches()["hyperbox"]
        t0 = time.perf_counter()
        sol = repro.solve_hyperbox(lo, hi, d, repro.SolveOptions(backend=backend),
                                   stats=stats)
        jax.block_until_ready(sol.objective)
        wall = time.perf_counter() - t0
        ran = "mosaic:hyperbox" if _kernel_caches()["hyperbox"] > before else "xla"
        if (kernel is None) != (ran == "xla"):
            raise RuntimeError(f"table1/{backend}: ran {ran}")
        err = np.abs(np.asarray(sol.objective, np.float64) - ref) / (1 + np.abs(ref))
        if err.max() > 1e-5:
            raise RuntimeError(f"table1/{backend}: closed form off by {err.max()}")
        _emit("table1_hyperbox", backend=backend, driver=ran, batch=bsz, n=n,
              retries=stats.retries, status=_status_counts(sol.status),
              max_rel_err_vs_numpy=float(err.max()), wall_s_incl_compile=wall,
              memory=_memory())
        sols[backend] = sol
    _emit("table1_hyperbox", pair=["pallas", "xla"], of=bsz,
          bit_identical=int(np.sum(np.asarray(sols["pallas"].objective).view(np.int32)
                                   == np.asarray(sols["xla"].objective).view(np.int32))))


def phase_pdhg(repro, seed, bsz=1000, m=500, n=500):
    """First-order backend at m = n = 500 — the kernel on a v5e.

    Packing LPs (A >= 0, b > 0): bounded, with moderate optima; the Fig. 8
    class at this size has optima near 1e4 that take the float64 oracle
    ~5,000 pivots (10 s) per LP.
    """
    import jax
    import jax.numpy as jnp

    ka, kb, kc = jax.random.split(jax.random.key(seed), 3)
    a = jax.random.uniform(ka, (bsz, m, n), jnp.float32)
    b = jax.random.uniform(kb, (bsz, m), jnp.float32, 1.0, 10.0)
    c = jax.random.uniform(kc, (bsz, n), jnp.float32, 0.1, 1.0)
    rows = _sample(seed, bsz)
    idx = jnp.asarray(rows)
    ref = reference(a[idx], b[idx], c[idx])
    opts = repro.SolveOptions(backend="pdhg")
    sol, info = run_solve(repro, "pdhg", repro.LPBatch(a, b, c), opts, "pdhg")
    info["oracle"] = oracle_check("pdhg", sol, rows, ref, RTOL["pdhg"])
    _emit("pdhg", batch=bsz, m=m, n=n, **info)


def _requests(seed, per_dim, dims):
    import repro

    rng = np.random.default_rng(seed)
    problems = []
    for dim in dims:
        for _ in range(per_dim):
            a = rng.uniform(-1.0, 1.0, (dim, dim)).astype(np.float32)
            a[np.diag_indices(dim)] = np.abs(np.diag(a)) + 1.0
            b = rng.uniform(1.0, 10.0, dim).astype(np.float32)
            c = rng.uniform(0.1, 1.0, dim).astype(np.float32)
            problems.append(repro.LPProblem.make(c[None], a[None], bu=b[None]))
    order = rng.permutation(len(problems))
    return [problems[i] for i in order]


def phase_engine(repro, seed, per_dim=100, dims=(5, 28, 100)):
    """LPEngine continuous mode against a one-shot ``repro.solve``."""
    from repro.core import backends
    from repro.serve.engine import LPEngine

    problems = _requests(seed, per_dim, dims)
    opts = repro.SolveOptions(backend="auto")
    backends.reset_warnings()
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        engine = LPEngine(opts)
        tickets = [engine.submit(p) for p in problems]
        steps = 0
        while engine.pending_count or engine.inflight_count:
            engine.step()
            steps += 1
        served = [engine.result(t) for t in tickets]
        oneshot = repro.solve(problems, opts)
    wall = time.perf_counter() - t0
    reroutes = [str(w.message) for w in caught if " backend: " in str(w.message)]
    if reroutes:
        raise RuntimeError(f"engine: the backend rerouted: {reroutes}")
    st = engine.stats
    if st.retries or st.dead_lettered:
        raise RuntimeError(f"engine: {st.retries} retries, {st.dead_lettered} dead-lettered")
    status_ok = all(int(s.status[0]) == int(o.status[0]) for s, o in zip(served, oneshot))
    same = sum(
        int(np.asarray(s.objective).view(np.int32)[0] == np.asarray(o.objective).view(np.int32)[0])
        for s, o in zip(served, oneshot)
    )
    worst = max(
        abs(float(s.objective[0]) - float(o.objective[0])) / (1 + abs(float(o.objective[0])))
        for s, o in zip(served, oneshot) if int(o.status[0]) == 1
    )
    if not status_ok or worst > 1e-5:
        raise RuntimeError(f"engine: served results differ from repro.solve ({worst})")
    _emit("lpengine", requests=len(problems), dims=list(dims), steps=steps,
          routed={str(d): backends.route_shape(d, d, options=opts) for d in dims},
          retries=st.retries, compiles=st.compiles, cache_hits=st.cache_hits,
          spliced=st.spliced,
          status=_status_counts([int(s.status[0]) for s in served]),
          objective_bit_identical_to_oneshot=same, max_rel_err_vs_oneshot=worst,
          wall_s_incl_compile=wall, memory=_memory())


def one_chip(repro, seed):
    a, b, c = feasible_batch(seed, 50_000, 100, 100)
    # One chunk of 50k would need ~17 GiB of HBM; 10k-LP chunks fit.
    phase_pair(repro, "fig8_feasible", a, b, c, seed, 10_000,
               (("pallas", "pallas"), ("xla", None)))
    del a, b, c
    a, b, c = two_phase_batch(seed + 1, 10_000, 200, 200)
    phase_pair(repro, "fig9_two_phase", a, b, c, seed + 1, 2_500,
               (("pallas", "pallas"), ("xla", None)))
    del a, b, c
    phase_hyperbox(repro, seed + 2)
    a, b, c = feasible_batch(seed + 3, 10_000, 100, 100)
    phase_pair(repro, "shared_a_sweep", a[0], b, c, seed + 3, None,
               (("pallas-shared", "pallas-shared"), ("xla-shared", None)),
               shared=True)
    del a, b, c
    phase_pdhg(repro, seed + 4)
    phase_engine(repro, seed + 5)


def four_chips(repro, seed):
    """The batch-sharded path against the same solve on one device.

    Statuses must match and objectives agree within the simplex oracle
    tolerance.  Bit-identity is reported, not required: a kernel runs the
    same per-tile program either way, but XLA compiles a GSPMD shard of
    2,500 rows apart from a 10,000-row program and may tile the revised
    driver's pricing matmul differently.
    """
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()), ("data",))
    a, b, c = feasible_batch(seed, 10_000, 100, 100)
    cases = (("fig8_feasible", repro.LPBatch(a, b, c), ("pallas", "xla")),
             ("shared_a_sweep", repro.SharedLPBatch(a[0], b, c),
              ("pallas-shared", "xla-shared")))
    for name, problem, pair in cases:
        for backend in pair:
            opts = repro.SolveOptions(backend=backend)
            kernel = None if backend.startswith("xla") else backend
            sharded, info = run_solve(repro, f"{name}/{backend}/mesh", problem, opts,
                                      kernel, mesh=mesh)
            # Read before the one-device solve: devices 1..3 have held
            # nothing but their shards of this solve.
            per_device = [
                {k: (d.memory_stats() or {}).get(k)
                 for k in ("bytes_in_use", "peak_bytes_in_use")}
                for d in jax.devices()
            ]
            one, _ = run_solve(repro, f"{name}/{backend}/1dev", problem, opts, kernel)
            shards = sorted({s.device.id for s in sharded.status.addressable_shards})
            if len(shards) != len(jax.devices()):
                raise RuntimeError(f"{name}/{backend}: result on devices {shards}")
            status_differs = int(np.sum(np.asarray(sharded.status) != np.asarray(one.status)))
            p, q = np.asarray(sharded.objective), np.asarray(one.objective)
            opt = np.asarray(one.status) == 1
            err = float(np.max(np.abs(p[opt] - q[opt]) / (1 + np.abs(q[opt])), initial=0.0))
            _emit(f"mesh_{name}", mesh=dict(mesh.shape), batch=problem.batch,
                  result_devices=shards, memory_per_device=per_device,
                  status_differs=status_differs, max_rel_err_vs_one_device=err,
                  bit_identical_to_one_device=bit_identical(sharded, one), **info)
            if status_differs or err > RTOL["simplex"]:
                raise RuntimeError(f"{name}/{backend}: sharded solve differs ({err})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(json.dumps({"device": device}), flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {device['count']} found",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    import repro
    from repro.runtime import compile_cache

    cache = {"dir": compile_cache.enable(), "hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(count)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(repro, args.seed)
    else:
        one_chip(repro, args.seed)
    _emit("compile_cache", wall_s=time.perf_counter() - t0, **cache)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Batch-sharded solves on 4 emulated host devices (subprocess-isolated).

A batch sharded across a mesh runs the Pallas kernels once per device
over its own rows (``kernels/ops.py:_launch_split``) and the XLA drivers
as GSPMD-partitioned programs.  Every backend must return, row for row,
the bits a one-device solve returns — under both pivot rules (RPC noise
is keyed on global rows) and through resumed compaction rounds — with
the result spread over every device of the mesh.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_ENV = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
    "JAX_PLATFORMS": "cpu",
}

_SCRIPT = """
    import json, sys
    import jax, numpy as np
    from jax.sharding import Mesh
    import repro

    backend, rule, compaction = sys.argv[1:]
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.default_rng(0)
    bsz, m, n = 64, 10, 12
    a = rng.uniform(-1, 1, (bsz, m, n)).astype(np.float32)
    a[:, np.arange(m), np.arange(m)] = np.abs(a[:, np.arange(m), np.arange(m)]) + 1
    b = rng.uniform(-1, 10, (bsz, m)).astype(np.float32)
    c = rng.uniform(0.1, 1, (bsz, n)).astype(np.float32)
    if backend.endswith("shared"):
        problem = repro.SharedLPBatch(a[0], b, c)
    else:
        problem = repro.LPBatch(a, b, c)
    opts = repro.SolveOptions(
        backend=backend, rule=rule, compaction=compaction, resume="basis",
        compact_every=4,
    )
    one = repro.solve(problem, opts)
    sharded = repro.solve(problem, opts, mesh=mesh)

    def bits(sol):
        return [np.asarray(f).view(np.int32).tolist()
                for f in (sol.objective, sol.x, sol.status, sol.iterations)]

    print(json.dumps({
        "same": bits(one) == bits(sharded),
        "devices": sorted({s.device.id for s in sharded.status.addressable_shards}),
        "optimal": int(np.sum(np.asarray(one.status) == 1)),
    }))
"""


@pytest.mark.parametrize(
    "backend,rule,compaction",
    [
        ("xla", "lpc", "off"),
        ("xla", "rpc", "every_k"),
        ("pallas", "rpc", "off"),
        ("pallas", "lpc", "every_k"),
        ("xla-shared", "rpc", "off"),
        ("pallas-shared", "rpc", "every_k"),
    ],
)
def test_sharded_solve_matches_one_device(backend, rule, compaction):
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SCRIPT), backend, rule, compaction],
        env=_ENV, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["same"], rec
    assert rec["devices"] == [0, 1, 2, 3], rec
    assert rec["optimal"] > 0, rec

"""Compile every Pallas kernel entry for a described TPU v5e chip.

Nothing here runs: each test lowers one jitted kernel entry of
``kernels/ops.py`` with ``interpret=False`` and compiles it for one chip
of a described ``v5e:2x2`` topology, at the shapes ``chip_smoke.py``
solves.  That catches what interpret mode cannot — Mosaic's block-shape,
layout and VMEM refusals, and programs that outgrow the chip's HBM —
without a chip.  The topology is described inside a fixture (never at
import), so every test worker collects the same tests and only the
worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tableau import TableauSpec
from repro.kernels import ops

F32 = jnp.float32
I32 = jnp.int32

#: v5e HBM per chip (Google Cloud, "TPU v5e": 16 GB).
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    # A compile written to a persistent cache could not be read back
    # without a chip; keep these compiles out of it.  The chip has no
    # float64, so compile as a float32 program runs there (conftest.py
    # turns 64-bit mode on for the float64 oracles).
    saved = {
        k: getattr(jax.config, k)
        for k in ("jax_enable_compilation_cache", "jax_enable_x64")
    }
    for k in saved:
        jax.config.update(k, False)
    yield make
    for k, v in saved.items():
        jax.config.update(k, v)


def _check(compiled):
    """The program holds a Mosaic kernel and fits one chip's HBM."""
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    )
    assert total < HBM_BYTES, total


@pytest.mark.parametrize(
    "m,n,bsz,want_state",
    [
        (100, 100, 10_000, False),  # Fig. 8 chunk
        (200, 200, 2_500, False),  # Fig. 9 chunk
        (28, 28, 256, True),  # LPEngine resume rounds
    ],
)
def test_tableau_kernel_compiles(shape, m, n, bsz, want_state):
    spec = TableauSpec(m, n)
    tile = ops.auto_tile_b(bsz, spec, F32, want_state)
    compiled = ops._solve_jit.lower(
        shape((bsz, m, n)), shape((bsz, m)), shape((bsz, n)), None,
        shape((2,), I32),
        spec=spec, rule="lpc", seed=0, tol=1e-5, tile_b=tile, static_cap=None,
        want_state=want_state, interpret=False,
    ).compile()
    _check(compiled)


def test_pdhg_kernel_compiles(shape):
    m = n = 500
    bsz = 1_000
    compiled = ops._pdhg_solve_jit.lower(
        shape((bsz, m, n)), shape((bsz, m)), shape((bsz, n)), shape((1,), I32),
        tol=1e-4, restart=64, tile_b=ops.pdhg_auto_tile_b(bsz, m, n),
        static_cap=None, want_state=False, interpret=False,
    ).compile()
    _check(compiled)


@pytest.mark.parametrize("rule", ["lpc", "rpc"])
def test_revised_kernel_compiles(shape, rule):
    m = n = 100
    bsz = 10_000
    compiled = ops._revised_solve_jit.lower(
        shape((m, n)), shape((bsz, m)), shape((bsz, n)), None, shape((2,), I32),
        rule=rule, seed=0, tol=1e-5, tile_b=ops.revised_auto_tile_b(bsz, m, n),
        static_cap=None, want_state=False, interpret=False,
    ).compile()
    _check(compiled)


def test_hyperbox_kernel_compiles(shape):
    n, bsz = 5, 1 << 22
    compiled = ops.hyperbox_support.lower(
        shape((n,)), shape((n,)), shape((bsz, n)), interpret=False
    ).compile()
    _check(compiled)

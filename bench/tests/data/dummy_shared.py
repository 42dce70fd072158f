"""A shared-A LP class for the harness's own tests: one A, per-row b and c.

The test that adds it copies it to ``bench/inputs/dummy_shared.py`` of a
tiny copy of the benchmark.  ``A`` is one ``(m, n)`` matrix drawn like a
Fig. 8 row (U(-1, 1) with ``|a_ii| + 1`` on the diagonal),
``b ~ U(1, 10)`` and ``c ~ U(0.1, 1)`` per row, all from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import lpgen


@functools.partial(jax.jit, static_argnames=("bsz", "m", "n"))
def _draw(k, *, bsz, m, n):
    ka, kb, kc = jax.random.split(k, 3)
    a = lpgen.constraints(ka, 1, m, n)[0]
    b = jax.random.uniform(kb, (bsz, m), jnp.float32, 1.0, 10.0)
    c = jax.random.uniform(kc, (bsz, n), jnp.float32, 0.1, 1.0)
    return a, b, c


def draw(cfg, seed, index, rows):
    out = _draw(lpgen.key(seed, index), bsz=rows, m=cfg["m"], n=cfg["n"])
    return tuple(np.asarray(v) for v in out)


def problem(repro, a, b, c):
    return repro.SharedLPBatch(a, b, c)


def requests(repro, a, b, c):
    return [repro.LPProblem.make(c[i], a, bu=b[i], maximize=True) for i in range(len(b))]

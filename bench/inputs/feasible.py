"""Fig. 8 class: b > 0, so the origin is a feasible start.

Copied from ``chip_smoke.py`` (``feasible_batch``) so that a later change
to the program cannot change the benchmark's inputs: ``A`` with U(-1, 1)
entries and ``|a_ii| + 1`` on the diagonal, ``b ~ U(1, 10)``,
``c ~ U(0.1, 1)``, one ``A`` per LP, in float32.
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
    a = lpgen.constraints(ka, bsz, m, n)
    b = jax.random.uniform(kb, (bsz, m), jnp.float32, 1.0, 10.0)
    c = jax.random.uniform(kc, (bsz, n), jnp.float32, 0.1, 1.0)
    return a, b, c


def draw(cfg, seed, index, rows):
    out = _draw(lpgen.key(seed, index), bsz=rows, m=cfg["m"], n=cfg["n"])
    return tuple(np.asarray(v) for v in out)

"""Fig. 9 class: feasible at a random interior x0, but many b_i < 0.

Copied from ``chip_smoke.py`` (``two_phase_batch``) so that a later
change to the program cannot change the benchmark's inputs.
``b = A x0 + slack`` for ``x0`` in [0.5, 1.5]: rows whose ``A x0`` is
negative give ``b_i < 0``, so the origin is infeasible and phase I runs.
One ``A`` per LP, drawn as in ``feasible.py``, in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import lpgen


@functools.partial(jax.jit, static_argnames=("bsz", "m", "n"))
def _draw(k, *, bsz, m, n):
    ka, kx, ks, kc = jax.random.split(k, 4)
    a = lpgen.constraints(ka, bsz, m, n)
    x0 = jax.random.uniform(kx, (bsz, n), jnp.float32, 0.5, 1.5)
    slack = jax.random.uniform(ks, (bsz, m), jnp.float32, 0.1, 1.0)
    b = jnp.einsum("bmn,bn->bm", a, x0, precision=jax.lax.Precision.HIGHEST) + slack
    c = jax.random.uniform(kc, (bsz, n), jnp.float32, 0.1, 1.0)
    return a, b, c


def draw(cfg, seed, index, rows):
    out = _draw(lpgen.key(seed, index), bsz=rows, m=cfg["m"], n=cfg["n"])
    return tuple(np.asarray(v) for v in out)

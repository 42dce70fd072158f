"""Seeded LP batches of the paper's two experiment classes, made on the device.

Copied from ``chip_smoke.py`` (``_constraints``, ``feasible_batch``,
``two_phase_batch``) so that a later change to the program cannot change
the benchmark's inputs.  Each batch is canonical: maximise ``c.x`` subject
to ``A x <= b`` and ``x >= 0``, in float32.

``host_batch`` draws a batch in one jitted call on the default device and
copies it to host memory as NumPy, which is how an application hands
``repro.solve`` its LPs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, *salt: int):
    """A JAX key from a seed of any size (``jax.random.key`` folds big ints to 0)."""
    words = np.random.SeedSequence([int(seed), *salt]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _constraints(k, bsz, m, n):
    """U(-1, 1) rows with a strengthened diagonal."""
    a = jax.random.uniform(k, (bsz, m, n), jnp.float32, -1.0, 1.0)
    diag = jnp.eye(m, n, dtype=bool)
    return jnp.where(diag, jnp.abs(a) + 1.0, a)


def feasible(k, bsz, m, n):
    """Fig. 8 class: b > 0, so the origin is a feasible start."""
    ka, kb, kc = jax.random.split(k, 3)
    a = _constraints(ka, bsz, m, n)
    b = jax.random.uniform(kb, (bsz, m), jnp.float32, 1.0, 10.0)
    c = jax.random.uniform(kc, (bsz, n), jnp.float32, 0.1, 1.0)
    return a, b, c


def two_phase(k, bsz, m, n):
    """Fig. 9 class: feasible at a random interior x0, but many b_i < 0.

    ``b = A x0 + slack`` for ``x0`` in [0.5, 1.5]: rows whose ``A x0`` is
    negative give ``b_i < 0``, so the origin is infeasible and phase I runs.
    """
    ka, kx, ks, kc = jax.random.split(k, 4)
    a = _constraints(ka, bsz, m, n)
    x0 = jax.random.uniform(kx, (bsz, n), jnp.float32, 0.5, 1.5)
    slack = jax.random.uniform(ks, (bsz, m), jnp.float32, 0.1, 1.0)
    b = jnp.einsum("bmn,bn->bm", a, x0, precision=jax.lax.Precision.HIGHEST) + slack
    c = jax.random.uniform(kc, (bsz, n), jnp.float32, 0.1, 1.0)
    return a, b, c


GENERATORS = {"feasible": feasible, "two_phase": two_phase}


@functools.partial(jax.jit, static_argnames=("generator", "bsz", "m", "n"))
def _draw(k, *, generator, bsz, m, n):
    return GENERATORS[generator](k, bsz, m, n)


def host_batch(generator: str, seed: int, index: int, bsz: int, m: int, n: int):
    """Batch ``index`` of a run's pool as host NumPy ``(a, b, c)``."""
    out = _draw(key(seed, index), generator=generator, bsz=bsz, m=m, n=n)
    return tuple(np.asarray(v) for v in out)

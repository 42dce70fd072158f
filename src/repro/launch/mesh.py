"""Production mesh construction (TPU v5e pod: 16x16 = 256 chips/pod)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Mesh over whatever devices exist (tests / single host)."""
    n = jax.device_count()
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"))

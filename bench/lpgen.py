"""Resolve an LP class by name: ``bench/inputs/<generator>.py``, one module per class.

A configuration's ``generator`` key names its class.  The module offers:

- ``draw(cfg, seed, index, rows)``: batch ``index`` of a run's inputs,
  ``rows`` LPs at the configuration's sizes, as host NumPy ``(a, b, c)``
  made from the seed (in one jitted call on the default device, then
  copied to host memory, which is how an application hands ``repro.solve``
  its LPs).  Each LP is canonical: maximise ``c.x`` subject to
  ``A x <= b`` and ``x >= 0``.  ``a`` is ``(rows, m, n)``, or one
  ``(m, n)`` matrix for a class whose LPs share it;
- optionally ``problem(repro, a, b, c)``: what a closed-loop call hands
  ``repro.solve``; by default ``repro.LPBatch(a, b, c)``;
- optionally ``requests(repro, a, b, c)``: the open loop's ``LPProblem``
  requests, one per row; by default ``LPProblem.make(c[i], a[i],
  bu=b[i], maximize=True)``.

So a later change adds a class by adding a module, and edits no file that
is already there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def key(seed: int, *salt: int):
    """A JAX key from a seed of any size (``jax.random.key`` folds big ints to 0)."""
    words = np.random.SeedSequence([int(seed), *salt]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def constraints(k, bsz, m, n):
    """U(-1, 1) rows with a strengthened diagonal."""
    a = jax.random.uniform(k, (bsz, m, n), jnp.float32, -1.0, 1.0)
    diag = jnp.eye(m, n, dtype=bool)
    return jnp.where(diag, jnp.abs(a) + 1.0, a)


def _batch(repro, a, b, c):
    return repro.LPBatch(a, b, c)


def _rows(repro, a, b, c):
    return [repro.LPProblem.make(c[i], a[i], bu=b[i], maximize=True) for i in range(len(b))]


@dataclasses.dataclass(frozen=True)
class LPClass:
    """One LP class's module, with the defaults filled in."""

    draw: Callable
    problem: Callable
    requests: Callable


def load(root: Path, generator: str) -> LPClass:
    """The class ``bench/inputs/<generator>.py`` under ``root``; raises ``FileNotFoundError``."""
    path = Path(root) / "bench" / "inputs" / f"{generator}.py"
    spec = importlib.util.spec_from_file_location(f"bench_inputs_{generator}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return LPClass(module.draw, getattr(module, "problem", _batch),
                   getattr(module, "requests", _rows))


def host_batch(generator: str, seed: int, index: int, bsz: int, m: int, n: int):
    """Batch ``index`` of a run's pool as host NumPy ``(a, b, c)``."""
    return load(ROOT, generator).draw({"m": m, "n": n}, seed, index, bsz)

"""Analytic per-iteration roofline for the batched LP backends.

Every backend in this repo is a lockstep iteration over per-LP state, so
its steady-state speed is set by one number: the arithmetic intensity
(FLOPs per HBM byte) of a single iteration.  This module writes down the
iteration cost model for each storage layout —

* **dense / compact tableau** (``core/tableau.py``): the pivot update
  rewrites the whole (m+1, q) tableau every iteration.  FLOPs and bytes
  are both O(m·q), so intensity is a small constant (~0.4 flop/byte):
  firmly memory-bound, which is why the compact layout's 0.67x bytes is
  a wall-clock win, not just a capacity win.
* **pdhg** (``core/pdhg.py``): two matvecs against a per-LP ``A`` that
  must stream from HBM each iteration — same constant-intensity regime.
* **shared revised simplex** (``core/revised.py``): pricing reads the
  ONE shared ``A`` per *tile* of LPs, so its O(m·n) bytes amortize over
  ``tile_b`` LPs and the per-LP traffic collapses to the O(m²) basis
  state.  Intensity grows with ``tile_b`` — the only backend whose
  roofline position the batch size can move.

Peaks come from one table keyed by ``device_kind`` (:data:`PEAKS`); a
device kind without published peaks is an error, never a default.  The
v5e's 197 TFLOP/s is its bf16 matrix-unit peak, while every layout's
iteration is float32 elementwise work on the vector unit — so the
roofline fraction column, ``intensity / balance``, is a ceiling on
matrix-unit utilization, not a prediction of attainable speed.

This model lives in the library (not under ``benchmarks/``) because it
is the static feature source of the cost-model autotuner
(``runtime/autotune.py``): candidate configs are ranked by the
per-iteration FLOPs/bytes written down here before anything is timed.
``benchmarks/roofline.py`` re-exports it for the printed table, and
``benchmarks/fig_memory.py`` imports :func:`arithmetic_intensity` for
the intensity column of ``BENCH_memory.json``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    """Published per-chip peaks."""

    bf16_flops: float  # FLOP/s of the matrix unit in bfloat16
    hbm_bytes_per_s: float
    hbm_bytes: float


#: Published peaks keyed by ``jax.Device.device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB of HBM at
#: 819 GB/s per chip).
PEAKS = {"TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)}

#: The chip the cost model ranks candidates for, on any host.
REFERENCE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            "runtime/roofline.py:PEAKS with their source"
        ) from None


PEAK_FLOPS = peaks(REFERENCE_KIND).bf16_flops
HBM_BW = peaks(REFERENCE_KIND).hbm_bytes_per_s
MACHINE_BALANCE = PEAK_FLOPS / HBM_BW

SIZES = (5, 28, 100, 200, 500)

KINDS = ("dense", "compact", "pdhg", "shared")


def iteration_profile(
    kind: str,
    m: int,
    n: int,
    tile_b: int = 1,
    dtype_bytes: int = 4,
    device_kind: str = REFERENCE_KIND,
) -> Dict[str, float]:
    """FLOPs / HBM bytes / intensity for ONE lockstep iteration of one LP.

    ``tile_b`` only matters for ``kind="shared"``: the shared ``A`` block
    is fetched once per tile, so its bytes are divided by the tile size.
    Byte counts are steady-state HBM traffic (state read + written each
    iteration); FLOPs count multiply-adds as 2.  ``roofline_fraction``
    is against ``device_kind``'s peaks (:func:`peaks`).
    """
    if kind in ("dense", "compact"):
        q = 1 + n + (2 * m if kind == "dense" else m)
        rows = m + 1
        # pricing scan (1 pass), ratio column, rank-1 pivot update (2 ops/elem)
        flops = 3.0 * rows * q
        byts = 2.0 * rows * q * dtype_bytes  # tableau in + out
    elif kind == "pdhg":
        # x/y proximal steps: A x and A^T y matvecs + O(m + n) vector ops
        flops = 4.0 * m * n + 8.0 * (m + n)
        byts = (2.0 * m * n + 6.0 * (m + n)) * dtype_bytes  # A twice + vectors
    elif kind == "shared":
        # pricing w = c_B B^-1 (2m^2) + d = w.A (2mn) + ftran B^-1 a_e (2m^2)
        # + rank-1 binv/xb update (2m^2)
        flops = 2.0 * m * n + 6.0 * m * m
        # A once per TILE (amortized), binv read + written, O(m+n) vectors
        byts = (m * n / max(tile_b, 1) + 2.0 * m * m + 4.0 * (m + n)) * dtype_bytes
    else:
        raise ValueError(f"unknown kind {kind!r}; expected one of {KINDS}")
    ai = flops / byts
    chip = peaks(device_kind)
    return {
        "flops": flops,
        "bytes": byts,
        "intensity": ai,
        "roofline_fraction": ai / (chip.bf16_flops / chip.hbm_bytes_per_s),
    }


def arithmetic_intensity(
    kind: str, m: int, n: int, tile_b: int = 1, dtype_bytes: int = 4
) -> float:
    """Just the flop/byte number (the BENCH_memory.json column)."""
    return iteration_profile(kind, m, n, tile_b, dtype_bytes)["intensity"]

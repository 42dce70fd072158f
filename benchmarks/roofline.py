"""Roofline table benchmark — prints the analytic iteration cost model.

The model itself moved into the library (``repro/runtime/roofline.py``)
when the cost-model autotuner (``repro/runtime/autotune.py``) started
ranking candidate configs with it; this module keeps the historical
import surface (``benchmarks.roofline.iteration_profile`` etc.) and the
printed table over the paper's size grid.
"""

from __future__ import annotations

from repro.runtime.roofline import (  # noqa: F401  (re-exported surface)
    HBM_BW,
    KINDS,
    MACHINE_BALANCE,
    PEAK_FLOPS,
    REFERENCE_KIND,
    SIZES,
    arithmetic_intensity,
    iteration_profile,
)


def run(full: bool = False) -> None:
    """Print the roofline table over the paper's size grid.

    Purely analytic (no device work), so ``full`` only widens nothing —
    the whole grid is always printed.  Shared intensity is quoted at the
    auto-selected VMEM tile for a 4096-LP batch, i.e. the tile the
    dispatcher would actually launch.
    """
    from repro.kernels import ops

    print(
        "# roofline: name,us_per_call,m,n,kind,tile_b,flops_per_iter,"
        "bytes_per_iter,intensity,roofline_frac"
    )
    print(f"# machine balance ({REFERENCE_KIND}, bf16 peak): {MACHINE_BALANCE:.0f} flop/byte")
    for size in SIZES:
        for kind in KINDS:
            tile = 1
            if kind == "shared":
                tile = ops.revised_auto_tile_b(4096, size, size)
            p = iteration_profile(kind, size, size, tile_b=tile)
            print(
                f"roofline_{kind}_m{size},0.0,{size},{size},{kind},{tile},"
                f"{p['flops']:.3g},{p['bytes']:.3g},{p['intensity']:.3f},"
                f"{p['roofline_fraction']:.2e}"
            )


if __name__ == "__main__":
    run()

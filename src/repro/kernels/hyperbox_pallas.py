"""Pallas TPU kernel: streaming hyperbox-LP (support function) solver.

Paper Sec. 6: when the feasible region is a box, max l.x has a closed
form.  The op is a select + multiply + reduce — purely memory bound
(arithmetic intensity ~= 2 FLOPs per 12 bytes read).  The kernel's job is
simply to stream (lo, hi, l) tiles HBM->VMEM at full bandwidth and reduce
in-register.  The paper's boxes are narrow (dimension 5 in Table 1), so
the batch rides the 128-wide lane axis and the LP dimension the sublane
axis: inputs arrive transposed as (n, B), each tile reduces over
sublanes, and the output is one lane-dense (1, B) row — no lane is
spent on padding a 5-wide row out to 128.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(lo_ref, hi_ref, d_ref, out_ref):
    lo = lo_ref[...]
    hi = hi_ref[...]
    d = d_ref[...]
    # Padded sublanes (>= n) carry zeros in d, so they contribute nothing.
    pick = jnp.where(d < 0, lo, hi)
    out_ref[...] = jnp.sum(d * pick, axis=0, keepdims=True)


def hyperbox_pallas(
    lo: jnp.ndarray,  # (Np, B) transposed, padded
    hi: jnp.ndarray,
    directions: jnp.ndarray,
    *,
    tile_b: int,
    vmem_limit_bytes: int,
    interpret: bool = False,
):
    """Support values of transposed, padded boxes: (Np, B) x3 -> (1, B).

    ``tile_b`` directions per grid step; it must divide ``B`` and be a
    multiple of 128 or ``B`` itself (``kernels/ops.py:hyperbox_support``
    pads to that).
    """
    np_, bsz = lo.shape
    if bsz % tile_b != 0:
        raise ValueError(f"batch {bsz} is not a multiple of tile_b {tile_b}")
    block = pl.BlockSpec((np_, tile_b), lambda i: (0, i))
    return pl.pallas_call(
        _kernel,
        grid=(bsz // tile_b,),
        in_specs=[block, block, block],
        out_specs=pl.BlockSpec((1, tile_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, bsz), directions.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(lo, hi, directions)

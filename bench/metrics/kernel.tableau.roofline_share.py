"""The tableau kernel's share of its roofline on the chip, in percent.

The least time the chip could take for the algorithm's work, over the
kernel's device time in the traced window:

- device time: the summed durations of the kernel's events in the trace,
  found by ``KERNEL``: on the chip the Pallas launch is an HLO custom
  call named after the jitted entry around it (``kernels/ops.py``:
  ``_solve_jit``, ``_resume_jit``), e.g. ``_solve_jit.1``;
- operations: true pivots (the ``iterations`` the window's calls
  returned, summed: what ``SolveStats.simplex_iterations`` counts) times
  the operations of one pivot on the unpadded compact tableau
  (``work``);
- bytes: the least any implementation moves: each LP's ``A``, ``b``,
  ``c`` read once and its ``x``, objective, status and iteration count
  written once, in 4-byte words;
- the roofline time is the larger of operations over the peak operation
  rate and bytes over the peak bandwidth (``bench/peaks.json``); the run
  notes which of the two binds.

Moves ``lps_per_s``.
"""

import numpy as np

KERNEL = r"^_(solve|resume)_jit(\.\d+)*$"


def work(m: int, n: int, pivots: int, lps: int):
    """(operations, bytes) of ``pivots`` pivots over ``lps`` LPs of shape (m, n).

    One pivot on the compact tableau, ``m + 1`` rows of ``q = 1 + n + m``
    columns: the rank-1 elimination (a multiply and a subtract per
    entry), the pivot row's division, and the ratio test's ``m``
    divisions.
    """
    q = 1 + n + m
    ops = pivots * (2 * (m + 1) * q + q + m)
    nbytes = lps * 4 * (m * n + m + n + n + 3)
    return float(ops), float(nbytes)


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(KERNEL)
    calls = ctx.record.data.get("calls")
    if not seconds or not calls:
        return None
    m, n = ctx.cell.config["m"], ctx.cell.config["n"]
    pivots = sum(int(np.sum(out[3], dtype=np.int64)) for *_, out in calls)
    lps = sum(len(out[0]) for *_, out in calls)
    ops, nbytes = work(m, n, pivots, lps)
    t_ops = ops / ctx.peaks["flops_per_s"]
    t_bytes = nbytes / ctx.peaks["bytes_per_s"]
    binds = "operations" if t_ops >= t_bytes else "bytes"
    ctx.notes.append(f"kernel.tableau: {pivots} pivots, {ops:.6g} operations, {nbytes:.6g} "
                     f"bytes, kernel {seconds:.6g} s, roofline bound by {binds}")
    return 100.0 * max(t_ops, t_bytes) / seconds

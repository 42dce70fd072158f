"""The comparison that decides ``correct``.

It reads what the timed path returned (``bench.loops.Block``: the inputs
as the harness made them, and the program's status, objective, ``x`` and
iteration count per row) and gives these numbers, each held to the limit
in the configuration's ``limits``:

- ``unanswered``: rows without a final status (optimal, unbounded,
  infeasible), or with none at all.  Limit 0;
- ``status_mismatch``: rows of the sample whose status differs from the
  float64 reference's.  Limit 0;
- ``objective_rel_err``: over sampled rows both sides solve to optimal,
  the largest ``|obj - ref| / (1 + |ref|)``;
- ``primal_resid``: over every optimal row, the largest violation of
  ``A x <= b`` by the returned ``x``, each constraint relative to the
  size of its terms, ``1 + (|A| |x|)_i + |b_i|`` (the float32 rounding of
  ``A x`` grows with ``|A| |x|``, and some LPs of these classes have
  optima a thousand times larger than ``b``), and of ``x >= 0``
  relative to ``1 + max |x|``.

The sample is drawn from the seed, and holds besides the row of each
block that took the most pivots.  ``primal_resid`` is computed in float64
from the inputs on every row.  A block whose ``a`` is one ``(m, n)``
matrix shares it over its rows: it is read as that matrix for every row,
and never copied per row.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import reference

FINAL = (1, 2, 3)
_BLOCK_ROWS = 4096  # rows per float64 block of the all-row checks


def sample_rows(blocks, seed: int, size: int) -> List[tuple]:
    """``(block, row)`` pairs to compare with the reference."""
    sizes = [len(b.status) for b in blocks]
    flat = np.random.default_rng([int(seed), 11]).choice(
        sum(sizes), size=min(size, sum(sizes)), replace=False)
    bounds = np.cumsum([0] + sizes)
    picks = {(int(np.searchsorted(bounds, f, side="right") - 1),
              int(f - bounds[np.searchsorted(bounds, f, side="right") - 1])) for f in flat}
    for k, b in enumerate(blocks):
        if len(b.iterations):
            picks.add((k, int(np.argmax(b.iterations))))
    return sorted(picks)


def _a(block, rows):
    """``A`` of ``rows``: the block's shared ``(m, n)`` matrix, or its rows' own."""
    return block.a if block.a.ndim == 2 else block.a[rows]


def _primal_resid(block) -> float:
    """``primal_resid`` over the block's optimal rows."""
    resid = 0.0
    opt = np.nonzero(block.status == 1)[0]
    for lo in range(0, opt.size, _BLOCK_ROWS):
        rows = opt[lo : lo + _BLOCK_ROWS]
        a = _a(block, rows).astype(np.float64)
        b = block.b[rows].astype(np.float64)
        x = block.x[rows].astype(np.float64)
        if a.ndim == 2:
            ax = x @ a.T
            size = 1.0 + np.abs(x) @ np.abs(a).T + np.abs(b)
        else:
            ax = np.matmul(a, x[:, :, None])[:, :, 0]
            size = 1.0 + np.matmul(np.abs(a), np.abs(x)[:, :, None])[:, :, 0] + np.abs(b)
        rows_viol = np.max((ax - b) / size, axis=1)
        sign_viol = np.max(-x, axis=1) / (1.0 + np.max(np.abs(x), axis=1))
        r = np.max(np.maximum(np.maximum(rows_viol, sign_viol), 0.0))
        resid = max(resid, float(r) if np.isfinite(r) else np.inf)
    return resid


def numbers_against(blocks, picks, ref) -> Dict[str, float]:
    """The compared numbers, given reference answers for the picked rows."""
    ref_status, ref_obj, _ = ref
    out = {"unanswered": 0, "status_mismatch": 0, "objective_rel_err": 0.0,
           "primal_resid": 0.0}
    for b in blocks:
        n = len(b.b)
        answered = len(b.status) == n
        out["unanswered"] += n if not answered else int((~np.isin(b.status, FINAL)).sum())
        if answered:
            out["primal_resid"] = max(out["primal_resid"], _primal_resid(b))
    for (k, r), rs, ro in zip(picks, ref_status, ref_obj):
        st = int(blocks[k].status[r])
        if st not in FINAL:
            continue  # counted as unanswered
        if st != rs:
            out["status_mismatch"] += 1
        elif st == 1:
            obj = float(blocks[k].objective[r])
            err = abs(obj - ro) / (1.0 + abs(ro)) if np.isfinite(obj) else np.inf
            out["objective_rel_err"] = max(out["objective_rel_err"], float(err))
    return out


def reference_answers(blocks, picks, precision: str = "float64"):
    a = np.stack([_a(blocks[k], r) for k, r in picks])
    b = np.stack([blocks[k].b[r] for k, r in picks])
    c = np.stack([blocks[k].c[r] for k, r in picks])
    return reference.solve(a, b, c, precision)


def compare(blocks, config: dict, seed: int) -> Dict[str, float]:
    """Every number the configuration's ``limits`` name, for these answers."""
    picks = sample_rows(blocks, seed, int(config["check_sample"]))
    return numbers_against(blocks, picks, reference_answers(blocks, picks))


def control_blocks(blocks, picks, precision: str = "bfloat16"):
    """The picked rows as the reference at ``precision`` answers them.

    The control: the reference put in the program's place, computed in the
    precision below the configuration's.  Returns blocks of the picked
    rows alone (one row each) and their new picks.
    """
    status, objective, x = reference_answers(blocks, picks, precision)
    out = []
    for i, (k, r) in enumerate(picks):
        src = blocks[k]
        out.append(type(src)(_a(src, slice(r, r + 1)), src.b[r : r + 1], src.c[r : r + 1],
                             status[i : i + 1], objective[i : i + 1].astype(np.float32),
                             x[i : i + 1].astype(np.float32), np.zeros(1, np.int32)))
    return out, [(i, 0) for i in range(len(picks))]

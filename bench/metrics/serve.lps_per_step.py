"""Tickets completed per ``LPEngine.step()`` call: the mean of ``len(step())``.

Counted over every step the harness made in the window (it steps only
while requests are pending or in flight).  Moves ``latency_p95_ms``.
"""

import numpy as np


def read(ctx):
    per_step = ctx.record.data.get("per_step")
    if not per_step:
        return None
    return float(np.mean(per_step))

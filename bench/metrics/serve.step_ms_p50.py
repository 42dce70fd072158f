"""Median host-clock time of one ``LPEngine.step()`` call, in milliseconds.

From the harness's span around each call (admission, splicing, one
dispatch round per shape class, the status read-back, retirement).
Moves ``latency_p50_ms``.
"""

import numpy as np


def read(ctx):
    steps = [e - s for n, s, e in ctx.record.spans if n == "step"]
    if not steps:
        return None
    return 1e3 * float(np.percentile(steps, 50))

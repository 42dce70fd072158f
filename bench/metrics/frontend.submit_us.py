"""Mean host-clock time of one ``LPEngine.submit`` call, in microseconds.

From the harness's span around each call (input validation and
queueing).  Moves ``latency_p50_ms``.
"""

import numpy as np


def read(ctx):
    submits = [e - s for n, s, e in ctx.record.spans if n == "submit"]
    if not submits:
        return None
    return 1e6 * float(np.mean(submits))

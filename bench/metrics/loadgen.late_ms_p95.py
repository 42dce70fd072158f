"""95th percentile of how late the load generator submitted a request, in ms.

Submission time minus scheduled arrival, per request of the window.  A
starved generator shows here, not as a fast server.  Moves
``latency_p95_ms``.
"""

import numpy as np


def read(ctx):
    late = ctx.record.data.get("late_s")
    if late is None:
        return None
    late = np.asarray(late, np.float64)
    late = late[np.isfinite(late)]
    if not late.size:
        return None
    return 1e3 * float(np.percentile(late, 95))

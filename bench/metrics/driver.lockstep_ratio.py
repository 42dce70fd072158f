"""Lockstep pivots over true pivots: what the batch pays for its slowest LPs.

``SolveStats.lockstep_iterations / SolveStats.simplex_iterations``, read
from one untraced call per pool batch with ``stats=`` on (the counters
force a host sync per chunk, so they stay out of the window).  Each
dispatched chunk runs as many pivots as its slowest LP; 1.0 would mean
every LP of a chunk took as many pivots as the slowest.  Moves
``lps_per_s``.
"""


def read(ctx):
    stats = ctx.extra.get("stats")
    if stats is None or not stats.simplex_iterations:
        return None
    return stats.lockstep_iterations / stats.simplex_iterations

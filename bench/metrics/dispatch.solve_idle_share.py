"""Share of the traced window in which the chip is idle while the host is inside ``repro.solve``.

Inside a call the host canonicalises, stages each chunk's inputs to the
chip and dispatches the solve; the chip idles there only where staging
and dispatch are not hidden behind device work (the first chunk of a
call, or a chunk whose inputs arrive late).  The intersection of the
chip's idle gaps with the harness's ``solve`` spans, over the window,
averaged over the chips.  Moves ``lps_per_s``.

Host-to-device copies do not appear as operations on the chip's
timeline in the trace, so this reads the exposed part of staging from
the idle time it leaves, not from copy events.
"""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    idle = t.idle_within("solve")
    if idle is None:
        return None
    return 100.0 * idle / t.window_s

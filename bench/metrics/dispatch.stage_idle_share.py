"""Share of the traced window in which the chip is idle while the program stages inputs.

The program's ``dispatch.stage`` spans (``repro.runtime.trace``: one
per chunk handed to ``jax.device_put``), placed on the trace's clock
through the harness's spans (``bench/program_spans.py``), intersected
with the chip's idle gaps, averaged over the chips, over the window.
Staging inside ``repro.solve``, so it is at most
``dispatch.solve_idle_share``.  The run notes the idle time of the
window by the innermost program span, which splits the ``solve`` gap.
None where the program records no span.  Moves ``lps_per_s``.
"""

from bench import program_spans


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    idle = program_spans.idle_within(ctx, "dispatch.stage")
    if idle is None:
        return None
    split = program_spans.idle_by_span(ctx) or {}
    ctx.notes.append("idle by program span (s): " + ", ".join(
        f"{n} {v:.6f}" for n, v in sorted(split.items(), key=lambda kv: -kv[1])))
    return 100.0 * idle / t.window_s

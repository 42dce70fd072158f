"""Host-side rate of handing a call's inputs to the chip, in GB/s.

The ``bytes`` of the program's ``dispatch.stage`` spans inside the
traced window (what ``jax.device_put`` was given), summed, over their
summed duration (``bench/program_spans.py``).  It counts staging whether
or not the chip's work hides it.  None where the program records no
span.  Moves ``lps_per_s``.
"""

from bench import program_spans


def read(ctx):
    spans = program_spans.in_window(ctx, "dispatch.stage")
    if not spans:
        return None
    seconds = sum(e - s for s, e, _ in spans) / 1e9
    nbytes = sum(a.get("bytes", 0) for _, _, a in spans)
    if seconds <= 0 or not nbytes:
        return None
    return nbytes / seconds / 1e9

"""95th percentile of a request's wait from submit to admission, in ms.

The ``serve.queued`` intervals ``LPEngine`` records per ticket while
traced (``repro.runtime.trace``), inside the window
(``bench/program_spans.py``).  None where the program records none.
Moves ``latency_p95_ms``.
"""

from bench import loops, program_spans


def read(ctx):
    spans = program_spans.in_window(ctx, "serve.queued")
    if not spans:
        return None
    return 1e3 * loops.percentile([(e - s) / 1e9 for s, e, _ in spans], 95)

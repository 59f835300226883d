"""Session step loop: share of the traced window the host spent outside `serve.device_wait`, the blocking read of a step's sampled tokens (%); open-loop cells.

100 x (traced window - union of the `serve.device_wait` spans inside it)
/ traced window, from the program's span ring (`repro.serve.tracing`).
None where the program records no spans.
"""

# a wait that began this long before the traced window still counts from
# the window's start (a step's wait lasts well under a second)
LEAD_S = 1.0


def read(ctx):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    if ctx.get("traced") is None:
        return None
    t0, t1 = ctx["traced"]
    waits = sorted((max(s.start, t0), min(s.end, t1))
                   for s in tracing.spans(t0 - LEAD_S, t1,
                                          "serve.device_wait")
                   if s.end > t0)
    if not waits:
        return None
    waited, reach = 0.0, t0
    for s, e in waits:
        s = max(s, reach)
        if e > s:
            waited, reach = waited + e - s, e
    return 100.0 * (1.0 - waited / (t1 - t0))

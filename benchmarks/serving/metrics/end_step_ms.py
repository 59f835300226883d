"""Paged state: `serve.end_step` (tail counters, filled pages read back and put in the pool) per `serve.step` of the window, mean (ms)."""


def read(ctx):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    w0, w1 = ctx["window"]
    steps = {s.index for s in tracing.spans(w0, w1, "serve.step")}
    took = [s.elapsed for s in tracing.spans(w0, name="serve.end_step")
            if s.parent in steps]
    if not took:        # no such span under the window's steps
        return None
    return 1e3 * sum(took) / len(steps)

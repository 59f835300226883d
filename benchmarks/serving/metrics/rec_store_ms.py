"""Paged state: `serve.rec_store` (recurrent slot allocation, the fresh-row reset flags, releases) per `serve.step` of the window, mean (ms)."""


def read(ctx):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    w0, w1 = ctx["window"]
    steps = tracing.spans(w0, w1, "serve.step")
    took = [s.elapsed for s in tracing.spans(w0, w1, "serve.rec_store")]
    if not steps or not took:   # no recurrent store, or no such span
        return None
    return 1e3 * sum(took) / len(steps)

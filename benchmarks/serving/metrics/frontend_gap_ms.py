"""Front end: host time from the end of one `serve.step` to the start of the next, mean over the traced window's steps (ms): delivery into the stream handles and the event loop's other tasks."""


def read(ctx):
    # the traced window, not the whole one: starting the profiler holds
    # the event loop between two steps just before the traced window
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    if ctx.get("traced") is None:
        return None
    steps = tracing.spans(*ctx["traced"], "serve.step")
    gaps = [b.start - a.end for a, b in zip(steps, steps[1:])]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)

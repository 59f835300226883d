"""Session step loop: `serve.step` less its `serve.begin_step`, `serve.device_wait` and `serve.end_step`, mean over the window's steps (ms): admission, row preparation, dispatch, delivery."""

# the children whose time other metrics report
APART = ("serve.begin_step", "serve.device_wait", "serve.end_step")


def read(ctx):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    w0, w1 = ctx["window"]
    steps = {s.index: s.elapsed for s in tracing.spans(w0, w1, "serve.step")}
    if not steps:
        return None
    took = {name: [] for name in APART}
    for s in tracing.spans(w0):
        if s.parent in steps and s.name in took:
            took[s.name].append(s.elapsed)
    if not all(took.values()):  # a child is missing: nothing to subtract
        return None
    apart = sum(sum(t) for t in took.values())
    return 1e3 * (sum(steps.values()) - apart) / len(steps)

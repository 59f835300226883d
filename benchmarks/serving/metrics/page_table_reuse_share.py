"""Paged state: share of the live rows whose page-table row `serve.begin_step` reused (or appended a filled page to) rather than built from the pool, over the window's steps (%)."""


def read(ctx):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    w0, w1 = ctx["window"]
    steps = {s.index for s in tracing.spans(w0, w1, "serve.step")}
    rows = rebuilt = 0
    for s in tracing.spans(w0, name="serve.begin_step"):
        if s.parent in steps and "rows" in s.counts:
            rows += s.counts["rows"]
            rebuilt += s.counts["rebuilt"]
    if not rows:        # no counted rows under the window's steps
        return None
    return 100.0 * (1.0 - rebuilt / rows)

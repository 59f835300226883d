"""Kernels: share of the batch's page-table slots that `paged_attention` does not copy, 100 x (1 - sum `streamed` / sum `table`) of `serve.begin_step` over the window's steps (%)."""


def read(ctx):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    w0, w1 = ctx["window"]
    steps = {s.index for s in tracing.spans(w0, w1, "serve.step")}
    streamed = table = 0
    for s in tracing.spans(w0, name="serve.begin_step"):
        if s.parent in steps and "streamed" in s.counts:
            streamed += s.counts["streamed"]
            table += s.counts["table"]
    if not table:       # no counted slots under the window's steps
        return None
    return 100.0 * (1.0 - streamed / table)

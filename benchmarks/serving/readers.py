"""Arithmetic of the per-layer metrics. Each file in `metrics/` is one
metric: it names the function here that computes it. Every function takes
the run's context and returns a number, or None when the run gave it
nothing to read (no trace, no rows, no kernel of that name).

The context (built by `harness.run_cell`):

    window      (t0, t1) of the measured window, host clock
    traced      (t0, t1) of the traced window, or None
    steps       `steplog.Step`s that started in the window
    requests    the window's requests (`harness.Req`)
    trace       `trace_reduce.reduce` of the traced window, or None
    config      the configuration file; ``reference`` its module
    cell        the cell file; ``peaks`` the chip's peaks; ``chips``
"""
from __future__ import annotations

import math

from serving.stats import percentile


def _traced_steps(ctx):
    tr = ctx.get("traced")
    if tr is None:
        return None
    return [s for s in ctx["steps"] if tr[0] <= s.t0 and s.t1 <= tr[1]]


def queue_wait_p95_ms(ctx):
    """Admission (the program's own admit time) minus scheduled arrival,
    95th percentile over the window's requests; a request never admitted
    counts as +inf."""
    waits = [(r.admit - r.sched) * 1e3 if r.admit is not None else math.inf
             for r in ctx["requests"] if not r.rejected]
    return percentile(waits, 95) if waits else None


def batch_occupancy(ctx):
    """Rows holding a request, over ``max_active``, mean over the window's
    steps, in percent."""
    live = [s.live for s in ctx["steps"]]
    if not live or any(v is None for v in live):
        return None
    return 100.0 * sum(live) / len(live) / ctx["cell"]["max_active"]


def prefill_step_share(ctx):
    """Share of the window's wall time spent in steps that carried a
    prompt chunk, in percent."""
    steps = ctx["steps"]
    if not steps or any(s.wide is None for s in steps):
        return None
    t0, t1 = ctx["window"]
    return 100.0 * sum(s.t1 - s.t0 for s in steps if s.wide) / (t1 - t0)


def host_bookkeeping_ms(ctx):
    """Host time the paged state counted per step (page tables, syncs,
    page fills), mean over the window's steps."""
    g = [s.gather_s for s in ctx["steps"]]
    if not g or any(v is None for v in g):
        return None
    return 1e3 * sum(g) / len(g)


def model_flops(ctx, steps) -> float | None:
    """Forward FLOPs the model needed for the tokens fed in ``steps``."""
    ref = ctx["reference"]
    if any(s.tokens is None for s in steps):
        return None
    per_tok = ref.flops_per_token(ctx["config"])
    per_ctx = ref.flops_per_context_token(ctx["config"])
    return sum(s.tokens * per_tok + s.ctx * per_ctx for s in steps)


def mfu(ctx):
    """Model FLOPs of the tokens fed in the traced window over chips x
    peak bf16 FLOP/s x the traced window, in percent."""
    steps = _traced_steps(ctx)
    if not steps:
        return None
    flops = model_flops(ctx, steps)
    if flops is None:
        return None
    t0, t1 = ctx["traced"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / ((t1 - t0) * peak)


def paged_attention_roofline(ctx):
    """Least time the paged-attention calls of the traced window need,
    over the kernel's device time, in percent. Per step and attention
    layer, one call reads every working row's resident K and V at the
    compute dtype (bf16) plus its query and output rows, and spends 4 x
    heads x head_dim FLOPs per query token per context token; the least
    time is the larger of FLOPs over peak FLOP/s and bytes over peak
    bandwidth, summed over calls."""
    from serving.trace_reduce import kernel_seconds
    steps = _traced_steps(ctx)
    red = ctx.get("trace")
    ref, cfg = ctx["reference"], ctx["config"]
    layers = ref.attention_layers(cfg)
    if not steps or not red or not layers:
        return None
    if any(s.kv is None for s in steps):
        return None
    # the Pallas custom call that reads the paged pool: an operand of
    # [layers, slots, page_tokens, kv heads, head_dim], whatever its dtype
    t = ctx["config"]["assumed"]["page_tokens"]
    hkv, hd = ref.page_row(cfg)
    kern = kernel_seconds(red, r'custom_call_target="tpu_custom_call"',
                          rf"\[\d+,\d+,{t},{hkv},{hd}\]")
    if not kern:
        return None
    pk = ctx["peaks"]
    per_ctx = ref.flops_per_context_token(cfg) / layers
    kvb, qb = ref.kv_bytes_per_token(cfg), ref.q_bytes_per_token(cfg)
    least = 0.0
    for s in steps:
        if not s.tokens:
            continue
        flops = s.ctx * per_ctx
        nbytes = s.kv * kvb + s.tokens * qb
        least += layers * max(flops / pk["bf16_flops_per_s"],
                              nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / kern


def device_idle_share(ctx):
    """1 - union of device-op intervals / traced window, in percent
    (mean over the chips)."""
    red = ctx.get("trace")
    if not red or not red.get("window_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


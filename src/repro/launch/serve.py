"""Serving launcher: batched generation with any --arch (smoke config on
CPU; production shapes via the dry-run).

    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
        --batch 4 --prompt-len 32 --new-tokens 16 --smoke

    # continuous batching over a paged pool,
    # Sibyl placement learning from real gather latency:
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b \
        --smoke --paged --continuous --max-active 2 --sibyl

    # hybrid stacks (SSM / RG-LRU / sliding-window) serve through the
    # same paged fused path — recurrent layers hold O(1) state slots,
    # ring layers recycle O(window) pages (the launcher prints the
    # per-request paged-state budget):
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m \
        --smoke --paged --continuous --max-active 2
    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
        --smoke --paged --speculate 4

    # speculative multi-token decode: n-gram drafts, 4-token verify steps
    # through the fused paged graph (2 host syncs per accepted run):
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b \
        --smoke --paged --speculate 4 --draft ngram

    # async streaming front end over the same batch (open-loop lifecycle,
    # per-request p50/p99 latency summary):
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b \
        --smoke --frontend --max-active 2

    # replay a named synthetic traffic mix (see repro.serve.traffic.MIXES;
    # key=val overrides after ':'):
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b \
        --smoke --trace prefix_heavy:n_requests=24,arrival_rate=100
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import get_config, list_archs, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import PagedKVPool


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="serve decode attention from a PagedKVPool")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (implies --paged)")
    ap.add_argument("--max-active", type=int, default=4,
                    help="decode rows for --continuous")
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--fast-pages", type=int, default=1024,
                    help="fast-tier capacity before LRU int8 demotion")
    ap.add_argument("--sibyl", action="store_true",
                    help="Sibyl DQN tier placement (reward: gather latency"
                         " + slow-hit penalty)")
    ap.add_argument("--decode-mode", default="fused",
                    choices=("fused", "eager"),
                    help="fused = one jitted device-resident step per token"
                         " (default); eager = per-layer reference path")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decode: verify K-token runs per "
                         "fused step (requires --paged/--continuous and "
                         "--decode-mode fused; K <= --page-tokens)")
    ap.add_argument("--draft", default="ngram",
                    help="draft proposer for --speculate: 'ngram' / "
                         "'ngram:N' (prompt-lookup, order N) or 'self' "
                         "(the serving model drafts for itself)")
    ap.add_argument("--frontend", action="store_true",
                    help="stream the batch through the async front end "
                         "(implies --paged) and print the per-request "
                         "latency summary")
    ap.add_argument("--trace", default=None, metavar="SPEC",
                    help="replay a synthetic traffic mix through the "
                         "async front end (implies --frontend): "
                         "'uniform', 'prefix_heavy:arrival_rate=100', ... "
                         "— name from repro.serve.traffic.MIXES plus "
                         "key=val overrides")
    ap.add_argument("--max-queue", type=int, default=16,
                    help="front-end waiting-line bound: submissions past "
                         "it are rejected (reason queue_full), not blocked")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serving mesh 'data x model', e.g. 2x4: decode "
                         "rows shard over the data axis, attention/MLP "
                         "heads over the model axis (requires that many "
                         "devices — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N). "
                         "Default: the host mesh (single device -> the "
                         "unsharded stack)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="prefill prompts monolithically at admission "
                         "instead of streaming page-sized chunks through "
                         "the fused decode steps")
    ap.add_argument("--prefill-budget", type=int, default=1, metavar="N",
                    help="chunk rows that may ride one fused decode step "
                         "(default 1)")
    ap.add_argument("--no-radix", action="store_true",
                    help="disable the radix prefix cache (no cross-"
                         "request prompt-page adoption or pinning)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable SLO-aware preemption: more-urgent "
                         "arrivals wait for rows instead of parking "
                         "eligible active requests on the host tier")
    ap.add_argument("--sibyl-preempt", action="store_true",
                    help="rank preemption victims with the Sibyl DQN "
                         "(learned from decode latency + deadline-miss "
                         "penalties) instead of the deterministic "
                         "least-progress fallback")
    ap.add_argument("--knee-cache", default=None, metavar="PATH",
                    help="JSON cache of backend='auto' knee points (e.g. "
                         "<checkpoint-dir>/knee_cache.json): loaded at "
                         "engine construction, saved after serving, so "
                         "restarts skip re-tuning")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.external_embed:
        raise SystemExit(f"{args.arch} takes frame embeddings, not tokens; "
                         "see examples/serve_lm.py for the embedding path")
    if args.trace:
        args.frontend = True
    if args.frontend:
        args.paged = True
    pool = None
    if args.paged or args.continuous:
        policy = None
        if args.sibyl:
            from repro.serve.placement import SibylPlacement
            policy = SibylPlacement()
        pool = PagedKVPool(page_tokens=args.page_tokens,
                           fast_capacity_pages=args.fast_pages,
                           placement_policy=policy)
    if args.speculate > 1 and pool is None:
        raise SystemExit("--speculate needs --paged or --continuous")
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh
        try:
            d, m = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--mesh wants DxM (e.g. 2x4), got "
                             f"{args.mesh!r}")
        mesh = make_serve_mesh(d, m)
    eng = ServeEngine(cfg, kv_pool=pool, decode_mode=args.decode_mode,
                      knee_cache=args.knee_cache, speculate=args.speculate,
                      draft=args.draft, mesh=mesh)
    if pool is not None:
        # per-request paged-state budget for this arch at the launch shape
        from repro.serve.paged_state import StateLayout, supports_paged_layout
        if supports_paged_layout(cfg):
            lay = StateLayout(cfg, args.page_tokens)
            cap = args.prompt_len + args.new_tokens
            print(f"paged state: {lay.n_kv} kv/ring layers "
                  f"({lay.pages_needed(cap)} pages per request"
                  f"{' — ring-bounded at O(window)' if lay.has_ring else ''}"
                  f"), {lay.n_ssd + lay.n_rg} recurrent layers "
                  f"({lay.rec_state_bytes()} B O(1) state per request)")
    if args.frontend:
        _run_frontend(args, cfg, eng, pool)
        return
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size, size=args.prompt_len)
                    .astype(np.int32), args.new_tokens)
            for _ in range(args.batch)]
    t0 = time.time()
    if args.continuous:
        outs = eng.serve(reqs, max_active=args.max_active,
                         chunked_prefill=False
                         if args.no_chunked_prefill else None,
                         prefill_budget=args.prefill_budget,
                         radix=False if args.no_radix else None)
    else:
        outs = eng.generate(reqs)
    dt = time.time() - t0
    tok = sum(len(o) for o in outs if o is not None)
    print(f"generated {tok} tokens in {dt:.2f}s "
          f"({tok / dt:.1f} tok/s); first row: {outs[0][:8]}")
    if args.speculate > 1:
        for i, d in enumerate(eng.last_request_stats):
            rate = "n/a" if d["accept_rate"] is None \
                else f"{d['accept_rate']:.2f}"
            print(f"req {i}: {d['tokens']} tokens in {d['steps']} verify "
                  f"steps ({d['tokens_per_step']:.2f} tok/step, "
                  f"accept_rate={rate})")
    if pool is not None:
        print(f"kv pool: {pool.stats} live_pages={len(pool.pages)}")


def _print_summary(summary: dict) -> None:
    def ms(d):
        return "n/a" if d["p50_ms"] is None else \
            f"p50 {d['p50_ms']:.2f}ms  p99 {d['p99_ms']:.2f}ms"
    print(f"requests: {summary['n_done']} done, "
          f"{summary['n_cancelled']} cancelled, "
          f"{summary['n_rejected']} rejected, "
          f"{summary.get('n_errors', 0)} errors")
    if summary.get("slo_attainment") is not None:
        print(f"slo attainment: {summary['slo_attainment']:.2f} "
              f"({summary['deadline_misses']} misses)")
    if summary.get("preemptions"):
        rw = summary["resume_wait"]
        wait = "n/a" if rw["p50_ms"] is None else \
            f"p50 {rw['p50_ms']:.2f}ms p99 {rw['p99_ms']:.2f}ms"
        print(f"preemptions: {summary['preemptions']} "
              f"({summary.get('n_resumed', 0)} resumed, "
              f"swap out {summary.get('swap_out_bytes', 0)}B / "
              f"in {summary.get('swap_in_bytes', 0)}B, "
              f"resume wait {wait})")
    print(f"tokens: {summary['tokens']} in {summary['wall_s']:.2f}s "
          f"({summary['throughput_tok_s']:.1f} tok/s)")
    print(f"queue wait: {ms(summary['queue_wait'])}")
    print(f"ttft:       {ms(summary['ttft'])}")
    print(f"per-token:  {ms(summary['tpot'])}")
    if summary.get("accept_rate") is not None:
        print(f"accept rate: {summary['accept_rate']:.2f}")
    for key in ("mix", "peak_active", "peak_live_pages",
                "pool_shared_puts", "decode_steps"):
        if key in summary:
            print(f"{key}: {summary[key]}")


def _run_frontend(args, cfg, eng, pool):
    """Serve through `AsyncServeFrontend` — a named traffic mix when
    --trace is given, else the launcher's own synthetic batch — and
    print the `serve.metrics` p50/p99 summary."""
    import asyncio

    from repro.serve.frontend import AsyncServeFrontend
    from repro.serve.traffic import parse_spec, run_trace

    preempt_policy = None
    if args.sibyl_preempt:
        from repro.serve.placement import SibylPreemption
        preempt_policy = SibylPreemption()
    if args.trace:
        summary = run_trace(eng, parse_spec(args.trace),
                            max_active=args.max_active,
                            max_queue=args.max_queue,
                            chunked_prefill=False
                            if args.no_chunked_prefill else None,
                            prefill_budget=args.prefill_budget,
                            radix=False if args.no_radix else None,
                            preempt=not args.no_preempt,
                            preempt_policy=preempt_policy)
        _print_summary(summary)
        print(f"kv pool: {pool.stats} live_pages={len(pool.pages)}")
        return

    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size, size=args.prompt_len)
                    .astype(np.int32), args.new_tokens)
            for _ in range(args.batch)]

    async def go():
        async with AsyncServeFrontend(
                eng, capacity=args.prompt_len + args.new_tokens,
                max_active=args.max_active, max_queue=args.max_queue,
                speculate=args.speculate or None,
                chunked_prefill=False if args.no_chunked_prefill else None,
                prefill_budget=args.prefill_budget,
                radix=False if args.no_radix else None,
                preempt=not args.no_preempt,
                preempt_policy=preempt_policy) as front:
            handles = [await front.submit(r) for r in reqs]
            outs = [await h.result() for h in handles]
            return front.metrics.summary(), outs

    summary, outs = asyncio.run(go())
    _print_summary(summary)
    print(f"first row: {outs[0][:8]}")
    print(f"kv pool: {pool.stats} live_pages={len(pool.pages)}")


if __name__ == "__main__":
    main()

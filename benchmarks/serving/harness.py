"""One run of one cell: build the deployment, warm it, drive its traffic
through the async front end, measure a window, check the answers.

`run_cell` takes the devices it is given and never looks for a chip: the
command line (`run.py`) does that. Tests call it on the CPU at a tiny size.

Order of a run:

1. weights from the seed, made on the device by the config's reference
   module; the engine, page pool and front end at the cell's sizes;
2. warm-up: two short requests through the front end compile (or load
   from the compile cache) the fused step at both widths it will run,
   k = 1 and the 16-wide prompt-chunk step, and the pool's small programs;
3. the cell's traffic from t = 0: ``fill_s`` seconds bring the batch to
   its steady state, then the measured window of ``seconds``; an open loop
   keeps arriving until every request of the window has its first token
   (at most ``drain_s`` more seconds), then whatever still runs is
   cancelled;
4. the device's peak memory, then the program's state is freed and the
   reference checks a sample of the finished requests.

``setup_s`` is process start to window start: weights, engine, warm-up,
fill. Tails count every request scheduled inside the window, timed from
its scheduled arrival; one that never got its first token counts as +inf
and as failed. The gaps between tokens are those the window's requests
received before the run ended.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from serving import check, gen, spec, stats, steplog, trace_reduce

# A traced run traces the last TRACE_S seconds of its window: the trace's
# writing and reading grow with its length, and a run must end in time.
TRACE_S = 20.0


@dataclasses.dataclass
class Req:
    index: int
    prompt_len: int
    output_len: int
    sched: float                  # scheduled arrival (host clock)
    segment: str
    submit: float | None = None
    admit: float | None = None
    rejected: str | None = None
    handle: object = None
    metric: object = None         # the front end's RequestMetrics
    deliveries: list = dataclasses.field(default_factory=list)
    served: object = None         # np.ndarray once finished
    status: str = "pending"
    prompt: object = None
    end: float | None = None

    @property
    def first(self) -> float | None:
        return self.deliveries[0][0] if self.deliveries else None


class CompileCounter:
    """Times of XLA compilations, from JAX's monitoring events."""

    def __init__(self):
        self.times: list[float] = []

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


def _mesh(devices, shape):
    from jax.sharding import Mesh
    dp, tp = shape
    return Mesh(np.asarray(devices[:dp * tp]).reshape(dp, tp),
                ("data", "model"))


def pool_budget(cfg: dict, cell: dict, kv_layers: int) -> int | None:
    """The page budget the cell's device slots hold, for the page pool's
    ``capacity_pages``: every slot but the trash slot and one spill slot
    per row may hold a reserved or prefix-cached page, so admission can
    never make the device pool grow (a growth recompiles the step inside
    the window). The program lays out ``ceil(capacity / page_tokens) + 2``
    table slots per row (a tail and a spill slot), rounded up to a
    multiple of 8, and ``rows x`` that many device slots, rounded up to a
    power of two (at least 8). None where the model keeps no KV pages."""
    if not kv_layers:
        return None
    t = int(cfg["assumed"]["page_tokens"])
    rows, cap = int(cell["max_active"]), int(cell["capacity"])
    per_row = -(-(-(-cap // t) + 2) // 8) * 8
    slots = 1 << (max(8, per_row * rows) - 1).bit_length()
    return kv_layers * (slots - 1 - rows)


def check_pool(session, budget: int | None, kv_layers: int) -> None:
    """Fails unless the program laid out the device slots `pool_budget`
    counted on: a budget larger than the slots would let the pool grow
    inside the window, a smaller one would refuse work the chip holds."""
    if budget is None:
        return
    dev = getattr(getattr(session, "state", None), "_device", None)
    if dev is None or not hasattr(dev, "capacity"):
        raise RuntimeError("the session has no device page pool "
                           "(state._device.capacity) to check the page "
                           "budget against")
    want = kv_layers * (dev.capacity - dev.shards - int(session.max_active))
    if want != budget:
        raise RuntimeError(f"page budget {budget} does not match the "
                           f"{dev.capacity} device slots the program laid "
                           f"out ({want} pages)")


def build(wl: dict, seed: int, devices):
    """Weights, engine and page pool of the cell; returns (params, engine,
    pool, program config). The pool's page budget and float tier are
    sized from the cell's rows and capacity."""
    from repro.configs import get_config
    from repro.serve.engine import ServeEngine
    from repro.serve.kvcache import PagedKVPool
    cfg, cell = wl["config"], wl["cell"]
    prog = cfg["program"]
    pcfg = get_config(prog["arch"], **prog.get("overrides", {}))
    ref = spec.reference(cfg)
    budget = pool_budget(cfg, cell, ref.attention_layers(cfg))
    with jax.default_device(devices[0]):
        params = ref.make_params(cfg, seed)
        jax.block_until_ready(params)
        # the float tier spans the whole budget: the device pool keeps a
        # float32 and an int8 copy of every slot either way, so a smaller
        # float tier saves no device memory; it would only demote pages
        sizes = {} if budget is None else {"capacity_pages": budget,
                                           "fast_capacity_pages": budget}
        pool = PagedKVPool(page_tokens=cfg["assumed"]["page_tokens"],
                           **sizes)
        engine = ServeEngine(pcfg, params=params, kv_pool=pool,
                             decode_mode="fused",
                             mesh=_mesh(devices, cell.get("mesh", (1, 1))))
    return params, engine, pool, pcfg


async def _sleep_until(t: float):
    d = t - time.perf_counter()
    if d > 0:
        await asyncio.sleep(d)


async def _consume(handle, req: Req, log: steplog.StepLog):
    """Token arrival times, one delivery per session step."""
    try:
        async for _tok in handle:
            now, step = time.perf_counter(), log.count
            if req.deliveries and req.deliveries[-1][2] == step:
                req.deliveries[-1][1] += 1
            else:
                req.deliveries.append([now, 1, step])
        req.served = await handle.result()
        req.end = time.perf_counter()
        req.status = "cancelled" if handle.cancelled else "done"
    except Exception as e:      # noqa: BLE001 - a failed request, not a crash
        req.status = f"error: {e}"


async def _submit(front, seed, req: Req, vocab, reqs, consumers, log):
    """Submit one request; returns its consumer task (None if refused)."""
    from repro.serve.scheduler import Request
    req.prompt = gen.prompt_tokens(seed, req.index, req.prompt_len, vocab)
    req.submit = time.perf_counter()
    h = await front.submit(Request(req.prompt,
                                   max_new_tokens=req.output_len))
    req.handle = h
    req.metric = front.metrics.requests[-1]
    reqs.append(req)
    if h.rejected:
        req.rejected = h.admission.reason
        req.status = "rejected"
        return None
    task = asyncio.ensure_future(_consume(h, req, log))
    consumers.append(task)
    return task


async def _open_loop(front, seed, t0, arrivals, vocab, reqs, consumers, log):
    for i, a in enumerate(arrivals):
        await _sleep_until(t0 + a.t)
        await _submit(front, seed, Req(i, a.prompt, a.output, t0 + a.t,
                                           a.segment),
                      vocab, reqs, consumers, log)


async def _closed_client(front, seed, c, stream, clients, vocab, reqs,
                         consumers, log, segment_of):
    """One client of a closed loop: its next request goes in when the
    previous one has finished."""
    for j, (p, o) in enumerate(stream):
        now = time.perf_counter()
        req = Req(j * clients + c, p, o, now, segment_of(now))
        task = await _submit(front, seed, req, vocab, reqs, consumers,
                             log)
        if task is not None:
            await asyncio.shield(task)


async def drive(front, wl, seed, seconds, trace_dir, log, vocab):
    """Warm-up, fill, window, drain. Returns the run's timing record."""
    cell, mix = wl["cell"], wl["traffic"]
    t_page = wl["config"]["assumed"]["page_tokens"]
    warm_reqs, warm_cons = [], []
    for i in range(2):
        await _submit(front, seed,
                      Req((1 << 30) + i, t_page + 4, 4, time.perf_counter(), "warm"),
                      vocab, warm_reqs, warm_cons, log)
    await asyncio.gather(*warm_cons)
    bad = [r for r in warm_reqs if r.status != "done"]
    if bad:
        raise RuntimeError(f"warm-up requests failed: "
                           f"{[(r.status, r.rejected) for r in bad]}")

    fill, drain = float(cell["fill_s"]), float(cell.get("drain_s", 60))
    t0 = time.perf_counter()
    w0, w1 = t0 + fill, t0 + fill + seconds
    reqs, consumers, tasks = [], [], []
    closed = mix["loop"] == "closed"

    def segment_of(t):
        return "fill" if t < w0 else ("window" if t < w1 else "drain")

    if closed:
        clients = int(cell["clients"])
        streams = gen.closed_streams(mix, clients, 256)
        for c in range(clients):
            tasks.append(asyncio.ensure_future(_closed_client(
                front, seed, c, streams[c], clients, vocab, reqs,
                consumers, log, segment_of)))
    else:
        arrivals = gen.open_schedule(
            mix, float(cell["rate_rps"]),
            [("fill", fill), ("window", seconds), ("drain", drain)])
        tasks.append(asyncio.ensure_future(_open_loop(
            front, seed, t0, arrivals, vocab, reqs, consumers, log)))

    rec = {"t0": t0, "w0": w0, "w1": w1, "traced": None}
    await _sleep_until(max(w0, w1 - TRACE_S) if trace_dir else w0)
    ann = None
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
        ann = jax.profiler.TraceAnnotation("bench.window")
        ann.__enter__()
        rec["traced"] = [time.perf_counter(), None]
    await _sleep_until(w1)
    if ann is not None:
        rec["traced"][1] = time.perf_counter()
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    if not closed:
        # the drain waits for every window request's first token; tokens
        # keep streaming (and arrivals keep coming) meanwhile. It starts
        # once the trace is written, which holds the loop for a while.
        deadline = time.perf_counter() + drain
        while time.perf_counter() < deadline and any(
                r.status == "pending" and not r.deliveries
                for r in reqs if r.segment == "window"):
            await asyncio.sleep(0.05)
    rec["end"] = time.perf_counter()
    for t in tasks:
        t.cancel()
    for r in reqs:
        if r.status == "pending" and r.handle is not None \
                and not r.handle.done:
            r.handle.cancel()
    await asyncio.gather(*tasks, *consumers, return_exceptions=True)
    for r in reqs:
        if r.metric is not None:
            r.admit = r.metric.admit_s
        r.handle = r.metric = None      # nothing may keep the session alive
    rec["reqs"] = reqs
    return rec


def _fmt(x) -> str:
    return repr(float(x))


def serve(wl: dict, seed: int, seconds: float, trace_dir, devices, say,
          built=None):
    """Build the cell's deployment (or take ``built``, the tuple `build`
    returns) and drive its traffic. Returns the run record, the step log,
    the device's peak memory and the weights; every object of the program
    is unreachable once it returns, unless the caller holds ``built``."""
    from repro.serve.frontend import AsyncServeFrontend
    from repro.serve.metrics import MetricsRegistry
    cfg, cell = wl["config"], wl["cell"]
    rows, cap = int(cell["max_active"]), int(cell["capacity"])
    gc.collect()        # a previous run's device arrays must be gone
    params, engine, pool, pcfg = built or build(wl, seed, devices)
    say(f"built {cfg['name']} ({pcfg.num_layers} layers)")
    log = steplog.StepLog()

    async def main():
        front = AsyncServeFrontend(
            engine, capacity=cap, max_active=rows,
            max_queue=int(cell.get("max_queue", 1 << 16)),
            speculate=cell.get("speculate"), seed=seed,
            metrics=MetricsRegistry())
        check_pool(front.session, pool.capacity_pages,
                   spec.reference(cfg).attention_layers(cfg))
        say(f"page pool budget: {pool.capacity_pages} pages, "
            f"{pool.fast_capacity} in the float tier")
        log.wrap(front.session)
        async with front:
            return await drive(front, wl, seed, seconds, trace_dir, log,
                               pcfg.vocab_size)

    rec = asyncio.run(main())
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return rec, log, peak, params


def run_cell(wl: dict, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, t_start: float, log_file=sys.stderr,
             control: bool = False) -> dict:
    """One run; returns the result object the contract prints last. With
    ``control`` the result also carries ``control``: the same comparison
    made of the fp8 control's choices at the served positions."""

    def say(msg):
        print(msg, file=log_file, flush=True)

    cfg, cell = wl["config"], wl["cell"]
    ref = spec.reference(cfg)
    chips = len(devices)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    with CompileCounter() as counter:
        rec, log, peak, params = serve(wl, seed, seconds, trace_dir,
                                       devices, say)
    gc.collect()
    w0, w1 = rec["w0"], rec["w1"]
    window = [r for r in rec["reqs"] if r.segment == "window"]

    # -- end-to-end -------------------------------------------------------
    ttft = [(r.first - r.sched) * 1e3 if r.first is not None else math.inf
            for r in window]
    itl = [g * 1e3 for r in window
           for g in stats.itl_samples([(t, n) for t, n, _ in r.deliveries])]
    out_tokens = sum(stats.tokens_between([(t, n) for t, n, _ in
                                           r.deliveries], w0, w1)
                     for r in rec["reqs"])
    e2e = {
        "ttft_p90_ms": stats.percentile(ttft, 90) if ttft else math.inf,
        "itl_p95_ms": stats.percentile(itl, 95) if itl else math.inf,
        "output_tok_s": out_tokens / (w1 - w0),
        "setup_s": w0 - t_start,
    }
    if wl["traffic"]["loop"] == "closed":
        # clients stop when the window closes: a request still running
        # then was cut short by the benchmark, not failed by the server
        failed = sum(r.status == "rejected" or r.status.startswith("error")
                     for r in window)
    else:
        # unserved: no first token by the end of the drain
        failed = sum(r.status == "rejected" or r.status.startswith("error")
                     or not r.deliveries for r in window)
    compiles = counter.between(w0, w1)
    late = [r.submit - r.sched for r in window if r.submit is not None]
    say(f"window {w1 - w0:.3f} s: {len(window)} requests scheduled, "
        f"{failed} failed, {out_tokens} output tokens, {compiles} "
        f"compilations inside it, {len(counter.times)} in the run; "
        f"generator lateness p50/max "
        f"{_fmt(np.median(late) if late else 0)} / "
        f"{_fmt(max(late) if late else 0)} s")
    say(f"window prompt lengths {gen.describe([r.prompt_len for r in window])}"
        f"; output lengths {gen.describe([r.output_len for r in window])}")

    # -- per-layer --------------------------------------------------------
    red = None
    if trace:
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            tr = trace_reduce.load(path)
            span = [(s, e) for n, s, e in tr.host if n == "bench.window"]
            red = trace_reduce.reduce(tr, span[0] if span else None)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"window": (w0, w1), "traced": rec["traced"],
           "steps": steplog.in_window(log.steps, w0, w1),
           "requests": window, "trace": red, "config": cfg,
           "reference": ref, "cell": cell, "peaks": peaks,
           "chips": chips}

    # -- correctness ------------------------------------------------------
    cc = cell["correct"]
    finished = [r for r in rec["reqs"]
                if r.status == "done" and r.end is not None and r.end >= w0]
    sample = check.sample([(r.prompt, r.served) for r in finished], seed,
                          cc["min_tokens"], cc["min_requests"],
                          cc["max_requests"])
    short = sum(len(r.served) != r.output_len for r in finished)
    t_ref = time.perf_counter()
    with jax.default_device(devices[0]):
        gap = check.served_gap(ref, cfg, params, sample,
                               int(cell["capacity"])) \
            if sample else math.inf
        ctl = check.control_gap(ref, cfg, params, sample,
                                int(cell["capacity"])) \
            if control and sample else math.inf
    say(f"reference over {len(sample)} of {len(finished)} finished requests "
        f"({sum(len(s) for _, s in sample)} served tokens) took "
        f"{time.perf_counter() - t_ref:.1f} s")
    correct, compared = judge(gap, short, cc)

    metrics = {}
    if trace:
        for m in wl["per_layer"]:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in wl["end_to_end"]:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    dev = devices[0]
    result = {
        "correct": bool(correct),
        "attempted": len(window),
        "failed": int(failed),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": chips, "memory_peak_bytes": int(peak)},
    }
    if red:
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = trace_reduce.breakdown(red)
    if control:
        # the reference at fp8 put in the program's place, judged by the
        # same comparison (`control.py`; benchmark runs never do this)
        c_correct, c_compared = judge(ctl, short, cc)
        result["control"] = {"correct": c_correct,
                             "compared": _listed(c_compared)}
    for k, (v, lim) in compared.items():
        say(f"compared {k}: {v!r} (limit {lim!r})")
    result["compared"] = _listed(compared)
    return result


def judge(gap: float, short: int, cc: dict):
    """(correct, {name: (number, limit)}): the widest logit gap of a
    served token under the reference, and the finished requests that
    stopped short of their length."""
    compared = {"logit_gap_max": (gap, cc["limit"]),
                "short_outputs": (short, 0)}
    return all(v <= lim for v, lim in compared.values()), compared


def _listed(compared: dict) -> dict:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}

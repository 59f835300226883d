"""Smoke run of the serving main path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the 2x2 serving mesh against one chip

One chip: starcoder2-7b at its published widths, cut to 16 of its 32
layers (7.9 GB of bf16 weights; the full depth would leave under 2 GB of a
16 GB v5e for the page pool and activations), with random weights from a
seed. The run checks the compiled `paged_attention` kernel against its jnp
reference at the served shapes, serves a continuous batch and a
speculative batch through `ServeEngine`, shows the kernel inside the
compiled fused step, and repeats the serve pass to count compilations in
it (there should be none). Timings it prints are from a smoke run, not a
benchmark.

Four chips: the same weights and requests served on a dp x tp = 2 x 2 mesh
(`make_serve_mesh(2, 2)`, as `repro.launch.serve --mesh 2x2` builds it)
and on one device, compared request by request. No other phase runs.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed. Without a TPU the script exits non-zero
before any phase. Each phase is a function of a config and a device, so
the tests run them on the CPU at `smoke_config` size.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import api, registry  # noqa: E402
from repro.serve.engine import (Request, ServeEngine,  # noqa: E402
                                ServeSession)
from repro.serve.kvcache import PagedKVPool  # noqa: E402

ARCH = "starcoder2-7b"
LAYERS = 16
PAGE_TOKENS = 16
PROMPT_LENS = (128, 256, 384, 512)
MAX_NEW = 32
SPECULATE = 4


def chip_config():
    """starcoder2-7b at published widths, 16 of its 32 layers."""
    return get_config(ARCH, num_layers=LAYERS)


@contextlib.contextmanager
def count_compiles():
    """Counts XLA backend compilations inside the block (``n[0]``)."""
    n = [0]

    def on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield n
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def one_device_mesh(device):
    """An explicit 1x1 serving mesh on ``device``: the engine never
    spreads over whatever else the host has."""
    from jax.sharding import Mesh
    return Mesh(np.asarray([device]).reshape(1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# Phase 1: the paged_attention kernel against its reference
# ---------------------------------------------------------------------------
def kernel_phase(cfg, device, *, batch=len(PROMPT_LENS),
                 page_tokens=PAGE_TOKENS,
                 capacity=max(PROMPT_LENS) + MAX_NEW, ks=(1, SPECULATE),
                 seed=0) -> dict:
    """``paged_attention`` with the tile ``backend="auto"`` resolves,
    against ``backend="ref"``, at the shapes the fused step serves: q in
    the compute dtype, the layer-stacked float32 page pool of
    `DevicePagePool` with both tiers populated, a page table as wide as
    the session's, for 1 query row and for ``SPECULATE`` verify rows.

    Tolerance: the spec's ``tol`` for q's dtype, unchanged. The kernel
    and the reference both round their output to q's dtype (bf16: 2^-8
    relative on outputs of magnitude below one); the reference runs its
    matmuls at the highest precision, so the bound also covers the
    kernel's in-VMEM float32 math."""
    spec = registry.get("paged_attention")
    slots = -(-(-(-capacity // page_tokens) + 2) // 8) * 8
    dtype = jnp.dtype(cfg.compute_dtype)
    tol = spec.tol[dtype.name]
    out = {}
    with jax.default_device(device):
        for k in ks:
            shape = {"b": batch, "pages": batch * slots,
                     "page_tokens": page_tokens, "slots": slots,
                     "hq": cfg.num_heads, "hkv": cfg.num_kv_heads,
                     "d": cfg.head_dim, "k": k}
            layers = [spec.example_inputs(shape, seed=seed + i)
                      for i in range(cfg.num_layers)]
            last = layers[-1]
            pools = [jnp.asarray(np.stack([lay[n] for lay in layers]))
                     for n in spec.arg_names[1:7]]
            args = (jnp.asarray(last["q"], dtype), *pools,
                    jnp.asarray(last["page_table"]),
                    jnp.asarray(last["lengths"]),
                    jnp.int32(cfg.num_layers - 1))
            tile = api.resolve_tile(spec, args)
            got = np.asarray(api.run(spec.name, *args, backend="auto"),
                             np.float32)
            with jax.default_matmul_precision("highest"):
                want = np.asarray(api.run(spec.name, *args, backend="ref"),
                                  np.float32)
            err = np.abs(got - want)
            excess = float(np.max(err - tol * (1 + np.abs(want))))
            print(f"kernel k={k}: tile {tile}, q {dtype.name} "
                  f"{tuple(args[0].shape)}, pool {tuple(pools[0].shape)} "
                  f"float32, max |pallas - ref| = {float(err.max())!r}, "
                  f"tol {tol} (|err| <= tol * (1 + |ref|) everywhere: "
                  f"{excess <= 0})")
            if excess > 0:
                raise AssertionError(
                    f"paged_attention k={k}: error exceeds tol {tol} by "
                    f"{excess}")
            out[k] = {"tile": tile, "max_err": float(err.max())}
    return out


# ---------------------------------------------------------------------------
# Phase 2: serving through ServeEngine
# ---------------------------------------------------------------------------
def make_requests(cfg, *, prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
                  speculate=None, seed=0) -> list:
    """One request per prompt length. Speculative prompts repeat a short
    motif, so the n-gram draft has something to propose."""
    rng = np.random.default_rng(seed)
    reqs = []
    for plen in prompt_lens:
        if speculate:
            motif = rng.integers(0, cfg.vocab_size, 16)
            prompt = np.resize(motif, plen)
        else:
            prompt = rng.integers(0, cfg.vocab_size, plen)
        reqs.append(Request(prompt.astype(np.int32), max_new,
                            speculate=speculate))
    return reqs


def make_engine(cfg, device, *, params=None, mesh=None,
                page_tokens=PAGE_TOKENS, seed=0):
    """A fused-decode engine over a page pool large enough that no page
    is demoted to the int8 tier. ``mesh`` defaults to one device."""
    pool = PagedKVPool(page_tokens=page_tokens,
                       fast_capacity_pages=1 << 16)
    with jax.default_device(device):
        return ServeEngine(cfg, params=params, seed=seed, kv_pool=pool,
                           decode_mode="fused",
                           mesh=mesh if mesh is not None
                           else one_device_mesh(device))


def check_outputs(cfg, eng, reqs, outs, label) -> None:
    """Every request returns exactly its ``max_new`` in-vocabulary tokens,
    with no rejection and no per-request error."""
    bad = [v for v in eng.last_rejections if v is not None]
    if bad:
        raise AssertionError(f"{label}: rejected {bad}")
    for i, (r, o, st) in enumerate(zip(reqs, outs,
                                       eng.last_request_stats)):
        if o is None or st is None or "error" in st:
            raise AssertionError(f"{label}: request {i} failed: {st}")
        if len(o) != r.max_new_tokens:
            raise AssertionError(f"{label}: request {i} returned {len(o)} "
                                 f"tokens, wanted {r.max_new_tokens}")
        if o.min() < 0 or o.max() >= cfg.vocab_size:
            raise AssertionError(f"{label}: request {i} has ids outside "
                                 f"[0, {cfg.vocab_size})")


def serve_pass(cfg, eng, reqs, label, max_active) -> tuple:
    """Serve ``reqs`` to completion; returns (outputs, wall seconds)."""
    t0 = time.perf_counter()
    outs = eng.serve(reqs, max_active=max_active)
    wall = time.perf_counter() - t0
    check_outputs(cfg, eng, reqs, outs, label)
    n = sum(len(o) for o in outs)
    print(f"{label}: {len(reqs)} requests complete, {n} tokens, prompts "
          f"{[len(r.prompt) for r in reqs]}")
    return outs, wall


def fused_step_text(eng, capacity, max_active) -> str:
    """Compiled text of the engine's one-token fused step at a session's
    shapes (all rows idle, so nothing is written)."""
    sess = ServeSession(eng, capacity=capacity, max_active=max_active)
    state = sess.state
    n_rows = state.batch_hint
    control = state.begin_step([-1] * n_rows, np.zeros(n_rows, np.int32))
    step = eng._fused_step_fn(state.slots, True, 1.0, k=1)
    lowered = step.lower(eng.params, state.device_arrays,
                         jnp.asarray(control), jax.random.PRNGKey(0))
    sess.close()
    return lowered.compile().as_text()


def serve_phase(cfg, device, *, prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
                page_tokens=PAGE_TOKENS, speculate=SPECULATE,
                seed=0) -> dict:
    """Continuous batching, then a speculative n-gram batch, on one
    device; then the kernel's presence in the compiled fused step; then
    both passes again, counting compilations."""
    n = len(prompt_lens)
    t0 = time.perf_counter()
    eng = make_engine(cfg, device, page_tokens=page_tokens, seed=seed)
    jax.block_until_ready(eng.params)
    print(f"engine: {cfg.param_count() / 1e9:.3f} B parameters in "
          f"{cfg.param_dtype}, initialised in "
          f"{time.perf_counter() - t0:.1f} s")

    def passes(label):
        plain = make_requests(cfg, prompt_lens=prompt_lens,
                              max_new=max_new, seed=seed)
        spec = make_requests(cfg, prompt_lens=prompt_lens, max_new=max_new,
                             speculate=speculate, seed=seed + 1)
        outs, wall = serve_pass(cfg, eng, plain, f"{label} serve", n)
        spec_outs, spec_wall = serve_pass(
            cfg, eng, spec, f"{label} speculate={speculate}", n)
        rates = [st["accept_rate"] for st in eng.last_request_stats]
        print(f"{label} speculate={speculate}: accept rates {rates}")
        return outs, wall, spec_outs, spec_wall

    with count_compiles() as cold:
        t0 = time.perf_counter()
        outs, _, spec_outs, _ = passes("cold")
        cold_s = time.perf_counter() - t0
    print(f"cold passes: {cold[0]} compilations, {cold_s:.1f} s "
          f"including compilation")

    text = fused_step_text(eng, max(prompt_lens) + max_new, n)
    kernel_calls = text.count("tpu_custom_call")
    print(f"fused step: {kernel_calls} tpu_custom_call in its compiled "
          f"text")
    if device.platform == "tpu" and not kernel_calls:
        raise AssertionError("the compiled fused step holds no Pallas "
                             "kernel: paged_attention did not compile")

    with count_compiles() as warm:
        outs2, wall, spec_outs2, spec_wall = passes("warm")
    print(f"warm passes: {warm[0]} compilations")
    if warm[0]:
        raise AssertionError(f"{warm[0]} compilations in the warm passes")
    for a, b in zip(outs + spec_outs, outs2 + spec_outs2):
        if not np.array_equal(a, b):
            raise AssertionError("the warm pass generated other tokens "
                                 "than the cold pass")
    tokens = sum(len(o) for o in outs2)
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"smoke run, not a benchmark: warm serve pass {wall:.3f} s, "
          f"{tokens / wall:.1f} tok/s ({tokens} tokens); warm speculative "
          f"pass {spec_wall:.3f} s; peak_bytes_in_use "
          f"{peak if peak is not None else 'not reported'}")
    return {"outputs": outs, "spec_outputs": spec_outs,
            "cold_compiles": cold[0], "warm_compiles": warm[0],
            "kernel_calls": kernel_calls, "peak_bytes": peak}


# ---------------------------------------------------------------------------
# Four chips: the dp x tp serving mesh against one device
# ---------------------------------------------------------------------------
def mesh_phase(cfg, devices, *, data=2, model=2, prompt_lens=PROMPT_LENS,
               max_new=MAX_NEW, page_tokens=PAGE_TOKENS, seed=0) -> dict:
    """Serve the same weights and requests on one device and on the
    ``data x model`` mesh over ``devices``.

    Tolerance: the first generated token of every request agrees. Later
    tokens may part: the mesh sums the two model shards' bf16 partial
    products of the attention and MLP output projections in another
    order than the one-device matmul, and a greedy argmax over the
    near-tied logits of random weights can flip; one flip changes every
    later token. The first divergence is printed per request."""
    from repro.launch.mesh import make_serve_mesh
    first = devices[0]
    ref = make_engine(cfg, first, page_tokens=page_tokens, seed=seed)
    reqs = make_requests(cfg, prompt_lens=prompt_lens, max_new=max_new,
                         seed=seed)
    want, wall_one = serve_pass(cfg, ref, reqs, "one device",
                                len(prompt_lens))
    params = ref.params
    del ref
    mesh = make_serve_mesh(data, model)
    eng = make_engine(cfg, first, params=params, mesh=mesh,
                      page_tokens=page_tokens)
    del params          # the one-device copy: free it before serving
    gc.collect()
    reqs = make_requests(cfg, prompt_lens=prompt_lens, max_new=max_new,
                         seed=seed)
    got, wall_mesh = serve_pass(cfg, eng, reqs, f"mesh {data}x{model}",
                                len(prompt_lens))
    diverge = []
    for i, (a, b) in enumerate(zip(want, got)):
        d = np.flatnonzero(a != b)
        diverge.append(int(d[0]) if d.size else None)
        print(f"request {i}: first divergence at token "
              f"{diverge[-1] if d.size else 'none'} of {len(a)}")
    if any(d == 0 for d in diverge):
        raise AssertionError(f"first tokens differ between the mesh and "
                             f"one device: {diverge}")
    print(f"smoke run, not a benchmark: one device {wall_one:.3f} s, mesh "
          f"{data}x{model} {wall_mesh:.3f} s (first passes, compilation "
          f"included)")
    return {"diverge": diverge}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 serving mesh against one chip")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing "
              f"was run", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    cfg = chip_config()
    full = get_config(ARCH)
    print(f"device: {dev.device_kind} x {len(devices)}, jax "
          f"{jax.__version__}, compile cache {cache}")
    print(f"model: {cfg.name} cut to {cfg.num_layers} of "
          f"{full.num_layers} layers at published widths (d_model "
          f"{cfg.d_model}, {cfg.num_heads} q / {cfg.num_kv_heads} kv heads "
          f"of {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}), "
          f"{cfg.param_count() / 1e9:.2f} B of {full.param_count() / 1e9:.2f}"
          f" B parameters, random weights")
    if args.four_chips:
        mesh_phase(cfg, devices[:4])
    else:
        kernel_phase(cfg, dev)
        serve_phase(cfg, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving step functions (prefill / decode) for jit + dry-run lowering."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax.sharding import Mesh

from repro.models import Model
from repro.models.layers import lm_head_apply, rms_norm
from repro.sharding.partition import with_shardings


def prefill_all_positions(model: Model, params, batch):
    """`forward_prefill` variant returning logits at *every* position.
    Continuous admission (and the draft models of the speculative path)
    right-pad prompts to a power-of-two bucket (causal masking keeps
    prefix K/V and logits exact), so a jitted wrapper compiles once per
    bucket instead of once per distinct prompt length; the caller reads
    ``logits[:, prompt_len - 1]``."""
    x = model._embed_in(params, batch)
    b, sl = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(sl, dtype=jnp.int32), (b, sl))
    x, _, caches = model._run_stack(params, x, mode="prefill",
                                    positions=positions, caches=None,
                                    cross_embeds=None)
    x = rms_norm(x, params["final_norm"])
    return lm_head_apply(model.cfg, params["embed"], x), caches


def make_prefill_step(model: Model):
    def prefill_step(params, batch):
        logits, caches = model.forward_prefill(params, batch)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, caches
    return prefill_step


def make_decode_step(model: Model):
    def decode_step(params, caches, batch, pos):
        logits, new_caches = model.forward_decode(params, batch, caches, pos)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, new_caches
    return decode_step


def make_paged_decode_step(model: Model, state, backend: str = "auto"):
    """Paged analogue of `make_decode_step`, closed over a host-side
    `PagedKVState` in its per-layer *eager* mode. The page tables are
    data-dependent (they change as pages fill and requests retire), so
    the step as a whole is not jit-lowerable — the kernel dispatch inside
    is jitted; this wrapper exists so launch-layer drivers consume one
    step-function shape for both paths. `pos` may be a scalar (lockstep)
    or (b,) per-sequence positions; `seq_ids` may carry -1 padding rows."""
    from repro.serve.paged_decode import paged_decode_step

    def decode_step(params, tokens, seq_ids, pos):
        logits = paged_decode_step(model, params, tokens, state, seq_ids,
                                   pos, backend=backend)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits
    return decode_step


def abstract_params_sharded(model: Model, mesh: Optional[Mesh], rules=None):
    a = model.abstract_params()
    if mesh is None:
        return a
    return with_shardings(a, model.logical(), mesh, rules)


def abstract_caches_sharded(model: Model, batch: int, capacity: int,
                            mesh: Optional[Mesh], rules=None):
    a, log = model.cache_spec(batch, capacity)
    if mesh is None:
        return a
    return with_shardings(a, log, mesh, rules)

"""Decoder stack assembly: per-layer block dispatch + scan over layer groups.

Layers are grouped by the config's pattern period; each group's params are
stacked along a leading "layers" dim and the stack is driven by lax.scan
(bounded HLO size & compile time even at 126 layers). A non-divisible tail
(e.g. recurrentgemma's 26 = 8*3 + 2) runs unscanned.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, CROSS_ATTN, LOCAL_ATTN, MIXER_NONE, MLA,
                                MLP_DENSE, MLP_MOE, MLP_NONE, RGLRU, SSD,
                                ModelConfig)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ParamSpec, abstract, logical_tree, materialize, stack_specs
from repro.models.layers import (embed_apply, embed_spec, lm_head_apply,
                                 mlp_apply, mlp_spec, norm_spec, rms_norm)
from repro.sharding.partition import constrain


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------
def layer_spec(cfg: ModelConfig, mixer: str, mlp: str):
    d = cfg.d_model
    s = {} if mixer == MIXER_NONE else {"norm1": norm_spec(d)}
    if mixer in (ATTN, LOCAL_ATTN):
        s["attn"] = attn.attn_spec(cfg)
    elif mixer == CROSS_ATTN:
        s["attn"] = attn.attn_spec(cfg, cross=True)
    elif mixer == MLA:
        s["mla"] = attn.mla_spec(cfg)
    elif mixer == SSD:
        s["ssm"] = ssm_mod.ssm_spec(cfg)
    elif mixer == RGLRU:
        s["rglru"] = rglru_mod.rglru_spec(cfg)
    elif mixer != MIXER_NONE:
        raise ValueError(mixer)
    if mlp == MLP_DENSE:
        s["norm2"] = norm_spec(d)
        s["mlp"] = mlp_spec(cfg)
    elif mlp == MLP_MOE:
        s["norm2"] = norm_spec(d)
        s["moe"] = moe_mod.moe_spec(cfg)
    return s


def mlp_tail(cfg: ModelConfig, kind, p, x):
    """Post-mixer half of a layer (norm2 + dense/MoE MLP residual) —
    shared by `layer_apply` and the serve layer's paged decode path.
    Returns (x, aux)."""
    mixer, mlp = kind
    aux = jnp.zeros((), jnp.float32)
    if mlp != MLP_NONE:
        h = rms_norm(x, p["norm2"])
        if mlp == MLP_MOE:
            y, aux = moe_mod.moe_apply(cfg, p["moe"], h)
        else:
            y = mlp_apply(cfg, p["mlp"], h)
        if mixer == CROSS_ATTN and "gate_ffn" in p["attn"]:
            y = jnp.tanh(p["attn"]["gate_ffn"]).astype(y.dtype) * y
        x = constrain(x + y, ("batch", "seq", None))
    return x, aux


def layer_apply(cfg: ModelConfig, kind, p, x, *, mode, positions=None,
                cache=None, cross_embeds=None):
    """Returns (x, new_cache, aux). A layer with no mixer has an empty
    cache ({}) wherever the others have one."""
    mixer, mlp = kind
    if mixer == MIXER_NONE:
        x, aux = mlp_tail(cfg, kind, p, x)
        return x, ({} if mode in ("prefill", "decode") else None), aux
    h = rms_norm(x, p["norm1"])
    if mixer in (ATTN, LOCAL_ATTN, CROSS_ATTN):
        window = cfg.window if mixer == LOCAL_ATTN else 0
        y, new_cache = attn.attn_apply(
            cfg, p["attn"], h, mode=mode, positions=positions, cache=cache,
            window=window,
            cross_embeds=cross_embeds if mixer == CROSS_ATTN else None)
    elif mixer == MLA:
        y, new_cache = attn.mla_apply(cfg, p["mla"], h, mode=mode,
                                      positions=positions, cache=cache)
    elif mixer == SSD:
        y, new_cache = ssm_mod.ssm_apply(cfg, p["ssm"], h, mode=mode,
                                         cache=cache)
    elif mixer == RGLRU:
        y, new_cache = rglru_mod.rglru_apply(cfg, p["rglru"], h, mode=mode,
                                             cache=cache)
    else:
        raise ValueError(mixer)
    x = constrain(x + y, ("batch", "seq", None))
    x, aux = mlp_tail(cfg, kind, p, x)
    return x, new_cache, aux


def layer_cache_spec(cfg: ModelConfig, kind, batch: int, capacity: int):
    """Abstract cache for one layer: (ShapeDtypeStruct tree, logical tree)."""
    mixer, _ = kind
    cdt = jnp.dtype(cfg.compute_dtype)
    hkv, hd = cfg.num_kv_heads, cfg.head_dim

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    if mixer == MIXER_NONE:
        return {}, {}
    if mixer == ATTN:
        shp = (batch, capacity, hkv, hd)
        log = ("batch", "kv_seq", "kv_heads", "head_dim")
        return ({"k": sds(shp, cdt), "v": sds(shp, cdt)},
                {"k": log, "v": log})
    if mixer == LOCAL_ATTN:
        cap = min(cfg.window, capacity)
        shp = (batch, cap, hkv, hd)
        log = ("batch", "kv_seq", "kv_heads", "head_dim")
        return ({"k": sds(shp, cdt), "v": sds(shp, cdt)},
                {"k": log, "v": log})
    if mixer == CROSS_ATTN:
        shp = (batch, cfg.n_img_tokens, hkv, hd)
        log = ("batch", None, "kv_heads", "head_dim")
        return ({"xk": sds(shp, cdt), "xv": sds(shp, cdt)},
                {"xk": log, "xv": log})
    if mixer == MLA:
        return ({"ckv": sds((batch, capacity, cfg.kv_lora_rank), cdt),
                 "krope": sds((batch, capacity, cfg.qk_rope_dim), cdt)},
                {"ckv": ("batch", "kv_seq", None),
                 "krope": ("batch", "kv_seq", None)})
    if mixer == SSD:
        din, nh, conv_dim = ssm_mod.ssm_dims(cfg)
        k = cfg.ssm_conv_width
        return ({"conv": sds((batch, k - 1, conv_dim), cdt),
                 "state": sds((batch, nh, cfg.ssm_head_dim, cfg.ssm_state),
                              jnp.float32)},
                {"conv": ("batch", None, "ssm_inner"),
                 "state": ("batch", "ssm_heads", None, None)})
    if mixer == RGLRU:
        w = cfg.lru_width
        return ({"h": sds((batch, w), jnp.float32),
                 "conv": sds((batch, 3, w), jnp.float32)},
                {"h": ("batch", "lru"), "conv": ("batch", None, "lru")})
    raise ValueError(mixer)


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------
class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.kinds = cfg.layer_kinds()
        gs = cfg.group_size()
        self.n_groups = cfg.num_layers // gs
        self.group_kinds = self.kinds[:gs]
        self.tail_kinds = self.kinds[self.n_groups * gs:]

    # -- parameter specs ---------------------------------------------------
    def spec(self):
        cfg = self.cfg
        group = {f"l{i}": layer_spec(cfg, *k)
                 for i, k in enumerate(self.group_kinds)}
        s = {
            "embed": embed_spec(cfg),
            "groups": stack_specs(group, self.n_groups),
            "final_norm": norm_spec(cfg.d_model),
        }
        if self.tail_kinds:
            s["tail"] = {f"t{i}": layer_spec(cfg, *k)
                         for i, k in enumerate(self.tail_kinds)}
        return s

    def init(self, key):
        return materialize(self.spec(), key, jnp.dtype(self.cfg.param_dtype))

    def abstract_params(self):
        return abstract(self.spec(), jnp.dtype(self.cfg.param_dtype))

    def logical(self):
        return logical_tree(self.spec())

    def param_count(self) -> int:
        import numpy as np
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.abstract_params()))

    # -- caches --------------------------------------------------------------
    def cache_spec(self, batch: int, capacity: int):
        """(abstract cache tree, logical tree) in the scan layout."""
        g_abs, g_log = {}, {}
        for i, k in enumerate(self.group_kinds):
            a, lg = layer_cache_spec(self.cfg, k, batch, capacity)
            g_abs[f"l{i}"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((self.n_groups,) + s.shape,
                                               s.dtype), a)
            g_log[f"l{i}"] = jax.tree.map(lambda t: ("layers",) + tuple(t), lg,
                                          is_leaf=lambda t: isinstance(t, tuple))
        out_abs, out_log = {"groups": g_abs}, {"groups": g_log}
        if self.tail_kinds:
            t_abs, t_log = {}, {}
            for i, k in enumerate(self.tail_kinds):
                a, lg = layer_cache_spec(self.cfg, k, batch, capacity)
                t_abs[f"t{i}"], t_log[f"t{i}"] = a, lg
            out_abs["tail"], out_log["tail"] = t_abs, t_log
        return out_abs, out_log

    def init_cache(self, batch: int, capacity: int):
        a, _ = self.cache_spec(batch, capacity)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), a)

    # -- forward -------------------------------------------------------------
    def _embed_in(self, params, batch_in):
        cfg = self.cfg
        if cfg.external_embed:
            x = batch_in["embeds"].astype(jnp.dtype(cfg.compute_dtype))
        else:
            x = embed_apply(cfg, params["embed"], batch_in["tokens"])
        return constrain(x, ("batch", "seq", None))

    def _run_stack(self, params, x, *, mode, positions, caches, cross_embeds):
        cfg = self.cfg
        gk = self.group_kinds

        def group_body(carry, xs):
            x, aux = carry
            if mode == "decode":
                gp, gc = xs
            else:
                gp, gc = xs, None
            new_caches = {}
            for i, kind in enumerate(gk):
                c_in = gc[f"l{i}"] if gc is not None else None
                x, c_out, a = layer_apply(
                    cfg, kind, gp[f"l{i}"], x, mode=mode, positions=positions,
                    cache=c_in, cross_embeds=cross_embeds)
                aux = aux + a
                if c_out is not None:
                    new_caches[f"l{i}"] = c_out
            return (x, aux), (new_caches if new_caches else None)

        body = group_body
        if mode == "train" and cfg.remat != "none":
            body = jax.checkpoint(group_body, prevent_cse=False)

        xs = (params["groups"], caches["groups"]) if mode == "decode" \
            else params["groups"]
        (x, aux), out_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)

        tail_caches = {}
        for i, kind in enumerate(self.tail_kinds):
            c_in = caches["tail"][f"t{i}"] if mode == "decode" else None
            x, c_out, a = layer_apply(
                cfg, kind, params["tail"][f"t{i}"], x, mode=mode,
                positions=positions, cache=c_in, cross_embeds=cross_embeds)
            aux = aux + a
            if c_out is not None:
                tail_caches[f"t{i}"] = c_out

        new_cache_tree = None
        if mode in ("prefill", "decode") and out_caches is not None:
            new_cache_tree = {"groups": out_caches}
            if tail_caches:
                new_cache_tree["tail"] = tail_caches
        return x, aux, new_cache_tree

    def forward_train(self, params, batch_in):
        """Returns (logits (b,s,V), aux)."""
        cfg = self.cfg
        x = self._embed_in(params, batch_in)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        cross = batch_in.get("image_embeds")
        if cross is not None:
            cross = cross.astype(x.dtype)
        x, aux, _ = self._run_stack(params, x, mode="train",
                                    positions=positions, caches=None,
                                    cross_embeds=cross)
        x = rms_norm(x, params["final_norm"])
        logits = lm_head_apply(cfg, params["embed"], x)
        return logits, aux

    def forward_prefill(self, params, batch_in):
        """Returns (last-position logits (b,V), caches)."""
        cfg = self.cfg
        x = self._embed_in(params, batch_in)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        cross = batch_in.get("image_embeds")
        if cross is not None:
            cross = cross.astype(x.dtype)
        x, _, caches = self._run_stack(params, x, mode="prefill",
                                       positions=positions, caches=None,
                                       cross_embeds=cross)
        x = rms_norm(x[:, -1:, :], params["final_norm"])
        logits = lm_head_apply(cfg, params["embed"], x)[:, 0]
        return logits, caches

    def forward_decode(self, params, batch_in, caches, pos):
        """One token step. Returns (logits (b,V), new caches)."""
        cfg = self.cfg
        x = self._embed_in(params, batch_in)      # (b, 1, d)
        x, _, new_caches = self._run_stack(params, x, mode="decode",
                                           positions=pos, caches=caches,
                                           cross_embeds=None)
        x = rms_norm(x, params["final_norm"])
        logits = lm_head_apply(cfg, params["embed"], x)[:, 0]
        return logits, new_caches

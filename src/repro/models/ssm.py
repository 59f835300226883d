"""Mamba2 SSD (state-space duality) mixer — chunked parallel form + step form.

The chunked jnp implementation is also the oracle for the ssd_scan Pallas
kernel (repro/kernels/ssd_scan).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec


def ssm_dims(cfg: ModelConfig):
    din = cfg.ssm_expand * cfg.d_model
    nh = din // cfg.ssm_head_dim
    conv_dim = din + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return din, nh, conv_dim


def ssm_spec(cfg: ModelConfig):
    d = cfg.d_model
    din, nh, conv_dim = ssm_dims(cfg)
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    # in_proj/conv carry the "ssm_proj" logical axis (not "ssm_inner"):
    # training shards both over "model", but the serve rules replicate
    # "ssm_proj" so the fused decode step can compute the projection at
    # full width and slice each shard's head block locally (the B/C
    # channels are shared by every head and cannot split by head).
    return {
        "in_proj": ParamSpec((d, 2 * din + 2 * g * n + nh), ("embed", "ssm_proj"),
                             init="fan_in"),
        "conv_w": ParamSpec((cfg.ssm_conv_width, conv_dim), (None, "ssm_proj"),
                            init="fan_in"),
        "conv_b": ParamSpec((conv_dim,), ("ssm_proj",), init="zeros"),
        "dt_bias": ParamSpec((nh,), ("ssm_heads",), init="zeros", dtype="float32"),
        "a_log": ParamSpec((nh,), ("ssm_heads",), init="alog", dtype="float32"),
        "d_skip": ParamSpec((nh,), ("ssm_heads",), init="ones", dtype="float32"),
        "gate_norm": ParamSpec((din,), ("ssm_inner",), init="zeros",
                               dtype="float32"),
        "out_proj": ParamSpec((din, d), ("ssm_inner", "embed"), init="fan_in"),
    }


def _split_proj(cfg: ModelConfig, proj):
    din, nh, _ = ssm_dims(cfg)
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    z = proj[..., :din]
    xbc = proj[..., din:din + din + 2 * g * n]
    dt = proj[..., -nh:]
    return z, xbc, dt


def ssd_chunked(x, b_mat, c_mat, dt, a, chunk: int, bf16_intra: bool = False):
    """SSD parallel scan.

    x: (B, S, H, P); b_mat/c_mat: (B, S, G, N); dt: (B, S, H) (post-softplus);
    a: (H,) negative reals. Returns y: (B, S, H, P), final state (B, H, P, N).
    bf16_intra: store the O(Q^2) intra-chunk decay/score tensors in bf16
    (halves the dominant HBM traffic; cumsums/exponents stay f32).
    """
    B, S, H, P = x.shape
    G, N = b_mat.shape[2], b_mat.shape[3]
    rep = H // G
    Q = min(chunk, S)
    if S % Q:
        Q = S
    nc = S // Q

    xc = x.reshape(B, nc, Q, H, P)
    bc = b_mat.reshape(B, nc, Q, G, N)
    cc = c_mat.reshape(B, nc, Q, G, N)
    dtc = dt.reshape(B, nc, Q, H).astype(jnp.float32)
    da = dtc * a[None, None, None, :]                     # (B,nc,Q,H)
    cum = jnp.cumsum(da, axis=2)                          # (B,nc,Q,H)

    # intra-chunk: S[i,j,h] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j, j<=i
    cb = jnp.einsum("bcqgn,bckgn->bcgqk", cc, bc,
                    preferred_element_type=jnp.float32)   # (B,nc,G,Q,Q)
    cb = jnp.repeat(cb, rep, axis=2)                      # (B,nc,H,Q,Q)
    ii, jj = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    # mask the exponent BEFORE exp: i<j entries would overflow to +inf and
    # poison gradients through the where
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]            # (B,nc,Q,Q,H)
    diff = jnp.where((ii >= jj)[None, None, :, :, None], diff, -jnp.inf)
    decay = jnp.exp(diff)
    dt_k = dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]    # (B,nc,H,1,Q)
    s_mat = cb * decay.transpose(0, 1, 4, 2, 3) * dt_k    # (B,nc,H,Q,Q)
    if bf16_intra:
        s_mat = s_mat.astype(jnp.bfloat16)
        y_intra = jnp.einsum("bchqk,bckhp->bcqhp", s_mat,
                             xc.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
    else:
        y_intra = jnp.einsum("bchqk,bckhp->bcqhp", s_mat,
                             xc.astype(jnp.float32))

    # chunk-final states: sum_j exp(cum_last - cum_j) dt_j B_j x_j
    bc_h = jnp.repeat(bc, rep, axis=3).astype(jnp.float32)  # (B,nc,Q,H,N)
    dec_last = jnp.exp(cum[:, :, -1:, :] - cum)           # (B,nc,Q,H)
    dtx = (dec_last * dtc)[..., None] * xc.astype(jnp.float32)   # (B,nc,Q,H,P)
    states = jnp.einsum("bcqhn,bcqhp->bchpn", bc_h, dtx)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cum[:, :, -1, :])               # (B,nc,H)

    def scan_fn(h_prev, inp):
        st, dec = inp                                     # (B,H,P,N), (B,H)
        h = h_prev * dec[..., None, None] + st
        return h, h_prev

    h0 = jnp.zeros((B, H, P, N), jnp.float32)
    h_final, h_prevs = jax.lax.scan(
        scan_fn, h0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)            # (B,nc,H,P,N)

    # inter-chunk contribution: C_i . (exp(cum_i) * h_prev)
    c_rep = jnp.repeat(cc, rep, axis=3) if G != H else cc
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp", c_rep.astype(jnp.float32),
                         h_prevs) * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(B, S, H, P)
    return y, h_final


def _conv1d(xbc, w, bias):
    """Causal depthwise conv along seq. xbc: (B,S,C); w: (K,C)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(k))
    return out + bias


def group_rms_norm(x, scale, groups: int, eps: float = 1e-6):
    """RMSNorm taken over each of ``groups`` equal blocks of the last
    axis (Mamba-2's gated norm with ``ngroups`` groups); one group is
    `rms_norm`."""
    shape = x.shape
    x32 = x.astype(jnp.float32).reshape(shape[:-1]
                                        + (groups, shape[-1] // groups))
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = (x32 * jax.lax.rsqrt(var + eps)).reshape(shape)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _gated_out(cfg: ModelConfig, p, y, z, dtype, tp: int):
    """``y * silu(z)``, the gate norm per group, the out projection.
    y, z: this shard's channels. Under ``tp > 1`` the groups either lie
    whole on each shard (``ngroups % tp == 0``: a local norm) or there is
    one group over all shards (one psum completes its mean square); the
    row-sharded out projection ends in a psum."""
    din, _, _ = ssm_dims(cfg)
    g = cfg.ssm_ngroups
    y = y * jax.nn.silu(z.astype(jnp.float32))
    if tp > 1 and g % tp:
        # one group over the FULL din: one psum completes the mean square
        y32 = y.astype(dtype).astype(jnp.float32)
        var = jax.lax.psum(jnp.sum(y32 * y32, axis=-1, keepdims=True),
                           "model") / din
        y = y32 * jax.lax.rsqrt(var + 1e-6)
        y = (y * (1.0 + p["gate_norm"].astype(jnp.float32))).astype(dtype)
    else:
        y = group_rms_norm(y.astype(dtype), p["gate_norm"], g // tp)
    out = y @ p["out_proj"]
    return jax.lax.psum(out, "model") if tp > 1 else out


def _heads(cfg: ModelConfig, p, xbc, dt_raw, tp: int):
    """This shard's heads of the post-conv channels ``xbc`` (B, S, C) and
    raw ``dt`` (B, S, H): ``(x (B,S,H_l,P), B and C (B,S,G_l,N) f32 of
    the G_l groups those heads read, dt (B,S,H_l) f32 after softplus,
    A (H_l,), first head)``. Each group is shared by H_l / G_l
    consecutive heads."""
    din, nh, _ = ssm_dims(cfg)
    g, n, P = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_head_dim
    Bsz, S = xbc.shape[0], xbc.shape[1]
    nh_l = p["a_log"].shape[0]            # local heads ("ssm_heads" shard)
    per = nh // g                         # heads a group serves
    g_l = max(1, nh_l // per)             # groups this shard's heads read
    h0 = jax.lax.axis_index("model") * nh_l if tp > 1 else 0

    def local(a, start, size):
        return a if tp == 1 else \
            jax.lax.dynamic_slice_in_dim(a, start, size, axis=2)

    dt = jax.nn.softplus(local(dt_raw, h0, nh_l).astype(jnp.float32)
                         + p["dt_bias"])
    xs = local(xbc[..., :din].reshape(Bsz, S, nh, P), h0, nh_l)
    bm = xbc[..., din:din + g * n].reshape(Bsz, S, g, n)
    cm = xbc[..., din + g * n:].reshape(Bsz, S, g, n)
    bm = local(bm.astype(jnp.float32), h0 // per, g_l)
    cm = local(cm.astype(jnp.float32), h0 // per, g_l)
    return xs, bm, cm, dt, -jnp.exp(p["a_log"]), h0


def _local_z(cfg: ModelConfig, z, h0, nh_l: int, tp: int):
    if tp == 1:
        return z
    P = cfg.ssm_head_dim
    return jax.lax.dynamic_slice_in_dim(z, h0 * P, nh_l * P, axis=2)


def vmap_rows(state, step, inputs):
    """The cores' default per-row map: ``step(S_i, inputs_i) -> (out_i,
    S_i')`` vmapped over a (B, H, P, N) ``state``. Returns (outs, new
    state)."""
    return jax.vmap(step)(state, inputs)


def _decode_state_step(s, inp):
    """One row, one token: S' = exp(dt A) S + dt x B^T; y = S' C."""
    da, dt, b, x, c = inp
    dbx = dt[:, None, None] * b[:, None, :] * x[:, :, None]    # (H,P,N)
    s = s * da[..., None, None] + dbx
    return jnp.einsum("hpn,hn->hp", s, c), s


def ssd_decode_core(cfg: ModelConfig, p, x, conv, state, *, tp: int = 1,
                    map_rows=vmap_rows):
    """One-token SSD step shared by the dense decode-cache path and the
    serve layer's fused paged step (the serving hot path traces this
    inside its jitted graph, so dense decode and fused serving agree by
    construction).

    x: (B, 1, d); conv: (B, K-1, conv_dim) raw pre-conv inputs; state:
    what ``map_rows(state, step, inputs) -> (outs, new state)`` advances
    row by row: a (B, H, P, N) fp32 array under `vmap_rows`, or the serve
    layer's store under its in-place row loop. Returns ``(y (B, 1, d),
    new_conv, new_state)``.

    ``tp > 1`` is the tensor-parallel form, valid only inside a shard_map
    body with a "model" axis: the in-projection and conv run replicated at
    full width ("ssm_proj" params replicate under SERVE_RULES — the B/C
    channels are group-shared and cannot split by head), the head block
    local to this shard is sliced out (state stays head-sharded, like
    attention heads), and the gate norm / out projection complete their
    reductions as `_gated_out` says.
    """
    B = x.shape[0]
    from repro.sharding.partition import constrain
    proj = constrain(x @ p["in_proj"], ("batch", "seq", "ssm_inner"))
    z, xbc, dt_raw = _split_proj(cfg, proj)
    window = jnp.concatenate([conv, xbc], axis=1)     # (B, K, C)
    xbc_t = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    xbc_t = jax.nn.silu(xbc_t)[:, None, :]
    new_conv = window[:, 1:, :]

    xs, bm, cm, dt, a, h0 = _heads(cfg, p, xbc_t, dt_raw, tp)
    nh_l = a.shape[0]
    rep = nh_l // bm.shape[2]
    # broadcast groups to heads
    bm_h = jnp.repeat(bm[:, 0], rep, axis=1)          # (B,H,N)
    cm_h = jnp.repeat(cm[:, 0], rep, axis=1)
    da = jnp.exp(dt[:, 0, :] * a)                     # (B,H)
    x32 = xs[:, 0].astype(jnp.float32)
    y, new_state = map_rows(state, _decode_state_step,
                            (da, dt[:, 0], bm_h, x32, cm_h))
    y = y + p["d_skip"][None, :, None] * x32
    y = y.reshape(B, 1, nh_l * cfg.ssm_head_dim)
    out = _gated_out(cfg, p, y, _local_z(cfg, z, h0, nh_l, tp), x.dtype, tp)
    return out, new_conv, new_state


def _chunk_state_step(s, inp):
    """One row, one chunk, from the state it starts at: y_i gains
    exp(cum_i) C_i . S, and S' = exp(cum_k) S + U (U: the chunk's own
    contribution)."""
    c, cum, last, xw, b = inp      # (k,G,N) (k,G,R) (G,R) (k,G,R,P) (k,G,N)
    g, r = last.shape
    s5 = s.reshape((g, r) + s.shape[1:])
    y = jnp.einsum("ign,grpn->igrp", c, s5) * jnp.exp(cum)[..., None]
    s5 = s5 * jnp.exp(last)[..., None, None] \
        + jnp.einsum("jgrp,jgn->grpn", xw, b)
    return y, s5.reshape(s.shape)


def ssd_chunk_core(cfg: ModelConfig, p, x, conv, state, n, *, tp: int = 1,
                   map_rows=vmap_rows):
    """The serve layer's k-token SSD step (a prompt chunk, with decode
    rows riding it): row i feeds the first ``n[i]`` of its k tokens; the
    rest are padding, whose dt is 0 (the state stays as it was) and which
    never enter the conv window. The chunk is computed in its matmul
    (dual) form from the state it starts at, which is read once and
    written once: no per-token state is ever formed.

    x: (B, k, d); conv: (B, K-1, conv_dim); state and ``map_rows`` as in
    `ssd_decode_core`; n: (B,) int32 in [1, k]. Returns ``(y (B, k, d),
    conv and state after n[i] tokens)``. ``tp`` as in
    `ssd_decode_core`."""
    K, P = cfg.ssm_conv_width, cfg.ssm_head_dim
    B, k = x.shape[0], x.shape[1]
    from repro.sharding.partition import constrain
    proj = constrain(x @ p["in_proj"], ("batch", "seq", "ssm_inner"))
    z, xbc, dt_raw = _split_proj(cfg, proj)
    window = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=1)
    taps = jnp.stack([window[:, i:i + k] for i in range(K)], axis=2)
    xbc_t = jnp.einsum("bjkc,kc->bjc", taps, p["conv_w"]) + p["conv_b"]
    xbc_t = jax.nn.silu(xbc_t)                         # (B, k, C)
    last_taps = n[:, None] + jnp.arange(K - 1, dtype=n.dtype)[None, :]
    new_conv = jnp.take_along_axis(window, last_taps[:, :, None], axis=1)

    xs, bm, cm, dt, a, h0 = _heads(cfg, p, xbc_t, dt_raw, tp)
    nh_l, g_l = a.shape[0], bm.shape[2]
    r = nh_l // g_l                                    # heads per group
    live = jnp.arange(k)[None, :] < n[:, None]         # (B, k)
    dt = jnp.where(live[..., None], dt, 0.0)           # (B, k, H)
    cum = jnp.cumsum(dt * a, axis=1)                   # (B, k, H)
    # heads as (group, head within it), so B and C stay per group
    x5 = xs.astype(jnp.float32).reshape(B, k, g_l, r, P)
    dt5, cum5 = dt.reshape(B, k, g_l, r), cum.reshape(B, k, g_l, r)
    # within the chunk: y_i = sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
    ii, jj = jnp.arange(k)[:, None], jnp.arange(k)[None, :]
    diff = cum5.transpose(0, 2, 3, 1)[..., :, None] \
        - cum5.transpose(0, 2, 3, 1)[..., None, :]     # (B, G, R, i, j)
    decay = jnp.exp(jnp.where(ii >= jj, diff, -jnp.inf))
    scores = jnp.einsum("bign,bjgn->bgij", cm, bm)[:, :, None] * decay \
        * dt5.transpose(0, 2, 3, 1)[..., None, :]
    y = jnp.einsum("bgrij,bjgrp->bigrp", scores, x5)
    # the state's part, and the state after the chunk: exp(cum_k) S +
    # sum_j exp(cum_k - cum_j) dt_j x_j B_j^T (padding adds nothing)
    last = cum5[:, -1]                                 # (B, G, R)
    w = jnp.exp(last[:, None] - cum5) * dt5            # (B, k, G, R)
    y_s, new_state = map_rows(state, _chunk_state_step,
                              (cm, cum5, last, x5 * w[..., None], bm))
    y = y + y_s + p["d_skip"].reshape(g_l, r)[None, None, :, :, None] * x5
    y = y.reshape(B, k, nh_l * P)
    out = _gated_out(cfg, p, y, _local_z(cfg, z, h0, nh_l, tp), x.dtype, tp)
    return out, new_conv, new_state


def ssm_apply(cfg: ModelConfig, p, x, *, mode: str, cache=None):
    """Returns (y, new_cache). cache = {"conv": (B,K-1,C), "state": (B,H,P,N)}."""
    if mode == "decode":
        y, new_conv, new_state = ssd_decode_core(cfg, p, x, cache["conv"],
                                                 cache["state"])
        return y, {"conv": new_conv, "state": new_state}

    din, nh, conv_dim = ssm_dims(cfg)
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    P = cfg.ssm_head_dim
    B = x.shape[0]
    a = -jnp.exp(p["a_log"])

    from repro.sharding.partition import constrain
    proj = constrain(x @ p["in_proj"], ("batch", "seq", "ssm_inner"))
    z, xbc_raw, dt = _split_proj(cfg, proj)
    xbc = xbc_raw
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])

    xbc = jax.nn.silu(_conv1d(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[..., :din].reshape(B, -1, nh, P)
    bm = xbc[..., din:din + g * n].reshape(B, -1, g, n)
    cm = xbc[..., din + g * n:].reshape(B, -1, g, n)
    y, h_final = ssd_chunked(xs, bm, cm, dt, a, cfg.ssm_chunk,
                             bf16_intra=cfg.ssm_bf16_intra)
    y = y + p["d_skip"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, x.shape[1], din)
    if mode == "prefill":
        k = cfg.ssm_conv_width
        new_cache = {"conv": xbc_raw[:, -(k - 1):, :], "state": h_final}
    else:
        new_cache = None

    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = group_rms_norm(y.astype(x.dtype), p["gate_norm"], cfg.ssm_ngroups)
    return y @ p["out_proj"], new_cache

"""Plain float32 reference of a Nemotron-H stage as the program serves it,
and the seeded weights both of them read.

Follows Nemotron-H (arXiv:2504.03624; huggingface.co/nvidia/
Nemotron-H-47B-Base-8K): a stack of layers ``h <- h + F(RMSNorm(h))``
whose F is given per layer by ``hybrid_override_pattern``:

- ``M``: Mamba-2. ``W_in x`` splits into z, xBC and dt; xBC goes through
  a causal depthwise conv of width 4 (with bias) and SiLU and splits into
  x (heads of 64 channels), B and C (``n_groups`` groups of the state
  size, each shared by consecutive heads); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``, no clamp (``time_step_limit`` is 0 to
  infinity); per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` and
  ``y_t = S_t C_t + D x_t``; then ``y * silu(z)`` normed per group of
  ``d_inner / n_groups`` channels; ``W_out``.
- ``*``: grouped-query attention without bias and without position
  encoding (the config has no RoPE key).
- ``-``: ``W_down relu(W_up x)^2``, no gate, no bias.

After the last layer a final RMSNorm and an untied head. The stage is a
contiguous stretch of the published pattern (the config file's
``program.overrides``: ``first_layer`` and ``num_layers``). The SSD is
computed here chunk by chunk (chunks of ``chunk_size`` positions, the
quadratic form inside a chunk, the state carried between chunks), so a
whole request at full width fits beside the weights. Departures the
program makes and this reference follows are listed in the config file
(RMSNorm epsilon 1e-6 and weight stored as ``1 + delta``).

One sequence of ``P`` tokens at a time, float32 at the highest matmul
precision. Imports nothing of the program.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from serving import refmath as rm


class Dims(NamedTuple):
    d: int
    stage: str            # the layer kinds held here, e.g. "M-M*-"
    vocab: int
    ff: int
    hq: int
    hkv: int
    hd: int
    heads: int            # Mamba-2 heads
    headdim: int
    state: int
    groups: int
    conv: int
    chunk: int
    eps: float

    @property
    def din(self) -> int:
        return self.heads * self.headdim

    @property
    def conv_dim(self) -> int:
        return self.din + 2 * self.groups * self.state

    @property
    def proj(self) -> int:
        return 2 * self.din + 2 * self.groups * self.state + self.heads

    @property
    def vocab_padded(self) -> int:
        """The program's embedding rows (vocabulary rounded up to 256)."""
        return -(-self.vocab // 256) * 256


def dims(cfg: dict) -> Dims:
    ov = cfg["program"]["overrides"]
    first = int(ov.get("first_layer", 0))
    stage = cfg["hybrid_override_pattern"][first:first
                                           + cfg["num_hidden_layers"]]
    return Dims(d=cfg["hidden_size"], stage=stage, vocab=cfg["vocab_size"],
                ff=cfg["intermediate_size"],
                hq=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"],
                hd=cfg["attention_head_dim"], heads=cfg["mamba_num_heads"],
                headdim=cfg["mamba_head_dim"], state=cfg["ssm_state_size"],
                groups=cfg["n_groups"], conv=cfg["conv_kernel"],
                chunk=cfg["chunk_size"],
                eps=float(cfg["as_run"]["rms_norm_eps"]))


def _layer(n: Dims, kind: str, key):
    """One layer's weights in the program's layout, with the scan's
    leading axis of one group."""
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(key, 12))
    d = n.d
    inv = 1.0 / math.sqrt(d)
    if kind == "-":
        return {"norm2": rm.uniform(next(ks), (1, d), 0.1, f32),
                "mlp": {"up": rm.stacked(next(ks), 1, (d, n.ff), inv, bf),
                        "down": rm.stacked(next(ks), 1, (n.ff, d),
                                           1.0 / math.sqrt(n.ff), bf)}}
    out = {"norm1": rm.uniform(next(ks), (1, d), 0.1, f32)}
    if kind == "*":
        out["attn"] = {
            "wq": rm.stacked(next(ks), 1, (d, n.hq, n.hd), inv, bf),
            "wk": rm.stacked(next(ks), 1, (d, n.hkv, n.hd), inv, bf),
            "wv": rm.stacked(next(ks), 1, (d, n.hkv, n.hd), inv, bf),
            "wo": rm.stacked(next(ks), 1, (n.hq, n.hd, d),
                             1.0 / math.sqrt(n.hq * n.hd), bf)}
        return out
    nh = n.heads
    dt = jnp.exp(jax.random.uniform(next(ks), (1, nh), f32,
                                    math.log(1e-3), math.log(1e-1)))
    out["ssm"] = {
        "in_proj": rm.stacked(next(ks), 1, (d, n.proj), inv, bf),
        "conv_w": rm.uniform(next(ks), (1, n.conv, n.conv_dim), 0.3, bf),
        "conv_b": rm.uniform(next(ks), (1, n.conv_dim), 0.1, bf),
        # inverse softplus of dt drawn log-uniform in [time_step_min, max]
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jax.random.uniform(next(ks), (1, nh), f32, 1.0,
                                            16.0)),
        "d_skip": 1.0 + rm.uniform(next(ks), (1, nh), 0.1, f32),
        "gate_norm": rm.uniform(next(ks), (1, n.din), 0.1, f32),
        "out_proj": rm.stacked(next(ks), 1, (n.din, d),
                               1.0 / math.sqrt(n.din), bf)}
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _make(n: Dims, key):
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = jax.random.split(key, len(n.stage) + 3)
    return {
        "embed": {"tok": rm.uniform(ks[0], (n.vocab_padded, n.d), 0.02, bf),
                  "lm_head": rm.uniform(ks[1], (n.d, n.vocab_padded),
                                        1.0 / math.sqrt(n.d), bf)},
        "groups": {f"l{i}": _layer(n, kind, ks[3 + i])
                   for i, kind in enumerate(n.stage)},
        "final_norm": rm.uniform(ks[2], (n.d,), 0.1, f32),
    }


def make_params(cfg: dict, seed: int):
    """The served weights (bf16 matrices and conv, float32 per-head and
    norm parameters) from the seed."""
    return _make(dims(cfg), rm.key_from_seed(seed))


def _ssd(n: Dims, x, b, c, dt, a):
    """The SSD recurrence over P positions, chunk by chunk. x: (P, H, hp);
    b, c: (P, G, N); dt: (P, H); a: (H,). Returns y: (P, H, hp)."""
    P = x.shape[0]
    Q = n.chunk
    pad = -P % Q
    rep = n.heads // n.groups

    def chunks(v):
        v = jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        return v.reshape((-1, Q) + v.shape[1:])

    i, j = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]

    def one(s, inp):                      # s: (H, hp, N), the state so far
        xc, bc, cc, dc = inp
        bh = jnp.repeat(bc, rep, axis=1)                  # (Q, H, N)
        ch = jnp.repeat(cc, rep, axis=1)
        cum = jnp.cumsum(dc * a, axis=0)                  # (Q, H)
        seg = jnp.where((j <= i)[None], cum.T[:, :, None]
                        - cum.T[:, None, :], -jnp.inf)    # (H, Q, Q)
        m = jnp.exp(seg) * jnp.einsum("ihn,jhn->hij", ch, bh,
                                      precision=rm.HIGHEST) * dc.T[:, None, :]
        y = jnp.einsum("hij,jhp->ihp", m, xc, precision=rm.HIGHEST)
        y = y + jnp.exp(cum)[:, :, None] * jnp.einsum(
            "ihn,hpn->ihp", ch, s, precision=rm.HIGHEST)
        w = jnp.exp(cum[-1][None] - cum) * dc             # (Q, H)
        s = jnp.exp(cum[-1])[:, None, None] * s + jnp.einsum(
            "jhp,jhn->hpn", xc * w[:, :, None], bh, precision=rm.HIGHEST)
        return s, y

    s0 = jnp.zeros((n.heads, n.headdim, n.state), jnp.float32)
    _, ys = jax.lax.scan(one, s0, (chunks(x), chunks(b), chunks(c),
                                   chunks(dt)))
    return ys.reshape((-1,) + ys.shape[2:])[:P]


def _mamba(n: Dims, p, h, fp8: bool):
    P = h.shape[0]
    gn = n.groups * n.state
    proj = rm.matmul(h, p["in_proj"][0], fp8)
    z, xbc, dt = proj[:, :n.din], proj[:, n.din:n.din + n.conv_dim], \
        proj[:, -n.heads:]
    w = p["conv_w"][0].astype(jnp.float32)
    pad = jnp.pad(xbc, ((n.conv - 1, 0), (0, 0)))
    xbc = sum(pad[i:i + P] * w[i] for i in range(n.conv)) \
        + p["conv_b"][0].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :n.din].reshape(P, n.heads, n.headdim)
    b = xbc[:, n.din:n.din + gn].reshape(P, n.groups, n.state)
    c = xbc[:, n.din + gn:].reshape(P, n.groups, n.state)
    dt = jax.nn.softplus(dt + p["dt_bias"][0])
    y = _ssd(n, xs, b, c, dt, -jnp.exp(p["a_log"][0]))
    y = y + p["d_skip"][0][None, :, None] * xs
    y = y.reshape(P, n.din) * jax.nn.silu(z)
    # the gated RMSNorm per group of din / n_groups channels
    yg = y.reshape(P, n.groups, n.din // n.groups)
    var = jnp.mean(yg * yg, axis=-1, keepdims=True)
    y = (yg * jax.lax.rsqrt(var + n.eps)).reshape(P, n.din) \
        * (1.0 + p["gate_norm"][0])
    return rm.matmul(y, p["out_proj"][0], fp8)


def _attention(n: Dims, p, h, fp8: bool):
    P = h.shape[0]
    g = n.hq // n.hkv
    pos = jnp.arange(P)
    q = rm.matmul(h, p["wq"][0].reshape(n.d, -1), fp8).reshape(
        P, n.hkv, g, n.hd)
    k = rm.matmul(h, p["wk"][0].reshape(n.d, -1), fp8).reshape(
        P, n.hkv, n.hd)
    v = rm.matmul(h, p["wv"][0].reshape(n.d, -1), fp8).reshape(
        P, n.hkv, n.hd)
    s = jnp.einsum("qngd,knd->ngqk", q, k,
                   precision=rm.HIGHEST) / math.sqrt(n.hd)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), v,
                   precision=rm.HIGHEST).reshape(P, n.hq * n.hd)
    return rm.matmul(o, p["wo"][0].reshape(n.hq * n.hd, n.d), fp8)


def _mlp(n: Dims, p, h, fp8: bool):
    u = jnp.square(jax.nn.relu(rm.matmul(h, p["up"][0], fp8)))
    return rm.matmul(u, p["down"][0], fp8)


def _logits(n: Dims, params, tokens, fp8: bool):
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    for i, kind in enumerate(n.stage):
        p = params["groups"][f"l{i}"]
        if kind == "-":
            x = x + _mlp(n, p["mlp"], rm.rms_norm(x, p["norm2"][0], n.eps),
                         fp8)
            continue
        h = rm.rms_norm(x, p["norm1"][0], n.eps)
        x = x + (_attention(n, p["attn"], h, fp8) if kind == "*"
                 else _mamba(n, p["ssm"], h, fp8))
    x = rm.rms_norm(x, params["final_norm"], n.eps)
    return rm.matmul(x, params["embed"]["lm_head"][:, :n.vocab], fp8)


@functools.partial(jax.jit, static_argnums=(0,))
def _stats(n: Dims, params, tokens):
    return rm.logit_stats(_logits(n, params, tokens, False), tokens)


@functools.partial(jax.jit, static_argnums=(0,))
def _control(n: Dims, params, tokens):
    ref = _logits(n, params, tokens, False)
    return rm.control_stats(ref, _logits(n, params, tokens, True))


def stats(cfg: dict, params, tokens):
    """(best, logit of the next token, argmax) per position."""
    return _stats(dims(cfg), params, jnp.asarray(tokens, jnp.int32))


def control(cfg: dict, params, tokens):
    """(reference best, reference logit of the fp8 control's argmax)."""
    return _control(dims(cfg), params, jnp.asarray(tokens, jnp.int32))


def flops_per_token(cfg: dict) -> float:
    """Forward FLOPs of one token outside attention's context term (2 per
    multiply-add): every projection, the conv, the SSD state update and
    its read-out (5 per state element: decay, dt x B outer product, add,
    and the multiply-add of C), the MLPs and the head."""
    n = dims(cfg)
    mamba = 2 * n.d * n.proj + 2 * n.din * n.d + 2 * n.conv * n.conv_dim \
        + 5 * n.heads * n.headdim * n.state
    attn = 2 * n.d * (n.hq + 2 * n.hkv) * n.hd + 2 * n.hq * n.hd * n.d
    mlp = 4 * n.d * n.ff
    per = {"M": mamba, "*": attn, "-": mlp}
    return float(sum(per[k] for k in n.stage) + 2 * n.d * n.vocab)


def flops_per_context_token(cfg: dict) -> float:
    """Attention FLOPs one query spends per token of context it attends
    (q.k and p.v in every attention layer)."""
    n = dims(cfg)
    return 4.0 * attention_layers(cfg) * n.hq * n.hd


def attention_layers(cfg: dict) -> int:
    return dims(cfg).stage.count("*")


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> float:
    """Bytes of K and V one token holds in one attention layer at the
    compute dtype (bf16)."""
    n = dims(cfg)
    return 2.0 * n.hkv * n.hd * dtype_bytes


def q_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> float:
    """Bytes of one query row in and one output row out of attention."""
    n = dims(cfg)
    return 2.0 * n.hq * n.hd * dtype_bytes


def page_row(cfg: dict) -> tuple[int, int]:
    """(kv heads, head_dim) of one token's row in a KV page."""
    n = dims(cfg)
    return n.hkv, n.hd


def ssd_layers(cfg: dict) -> int:
    return dims(cfg).stage.count("M")


def ssd_state_shape(cfg: dict) -> tuple[int, int, int]:
    """(heads, head channels, state size) of one row's float32 SSD state
    in one layer: the trailing dims of the program's state store."""
    n = dims(cfg)
    return n.heads, n.headdim, n.state


def ssd_bytes_per_row(cfg: dict, conv_bytes: int = 2) -> float:
    """Bytes of one row's recurrent state over every SSD layer: the
    float32 state and the conv taps (K - 1 positions of the conv
    channels, at the compute dtype)."""
    n = dims(cfg)
    per = n.heads * n.headdim * n.state * 4 \
        + (n.conv - 1) * n.conv_dim * conv_bytes
    return float(ssd_layers(cfg) * per)

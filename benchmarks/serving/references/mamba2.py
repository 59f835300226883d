"""Plain float32 reference of the Mamba-2 language model as the program
serves it, and the seeded weights both of them read.

Follows Mamba-2 (arXiv:2405.21060; huggingface.co/state-spaces/mamba2-780m):
pre-norm blocks of one SSD mixer each (in-projection to z, x, B, C and dt;
causal depthwise conv of width 4 with SiLU over x, B and C; a scalar decay
per head; skip D; gated RMSNorm ``norm(y * silu(z))``; out-projection),
tied embeddings. The SSD layer is computed here in its quadratic "dual"
form, y = (L o C B^T) (dt x) + D x with L[i, j] = exp(cum_i - cum_j) for
j <= i, a different algorithm from the program's token-by-token state
recurrence. Departures the program makes, and this reference follows
(listed in the config file): RMSNorm epsilon 1e-6, RMSNorm weight stored
as ``1 + delta``, the residual stream in the compute dtype.

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
    layers: int
    vocab: int
    state: int
    conv: int
    expand: int
    headdim: int
    groups: int
    eps: float

    @property
    def din(self) -> int:
        return self.expand * self.d

    @property
    def heads(self) -> int:
        return self.din // self.headdim

    @property
    def conv_dim(self) -> int:
        return self.din + 2 * self.groups * self.state

    @property
    def vocab_padded(self) -> int:
        """The program's embedding rows (vocabulary rounded up to 256)."""
        return -(-self.vocab // 256) * 256


def dims(cfg: dict) -> Dims:
    a = cfg["assumed"]
    return Dims(d=cfg["d_model"], layers=cfg["n_layer"],
                vocab=cfg["vocab_size"], state=a["d_state"],
                conv=a["d_conv"], expand=a["expand"],
                headdim=a["headdim"], groups=a["ngroups"],
                eps=float(cfg["as_run"]["norm_epsilon"]))


@functools.partial(jax.jit, static_argnums=(0,))
def _make(n: Dims, key):
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(key, 16))
    d, L, nh = n.d, n.layers, n.heads
    proj = 2 * n.din + 2 * n.groups * n.state + nh
    dt = jnp.exp(jax.random.uniform(next(ks), (L, nh), f32,
                                    math.log(1e-3), math.log(1e-1)))
    layer = {
        "norm1": rm.uniform(next(ks), (L, d), 0.1, f32),
        "ssm": {
            "in_proj": rm.stacked(next(ks), L, (d, proj),
                                  1.0 / math.sqrt(d), bf),
            "conv_w": rm.uniform(next(ks), (L, n.conv, n.conv_dim), 0.3, bf),
            "conv_b": rm.uniform(next(ks), (L, n.conv_dim), 0.1, bf),
            # inverse softplus of dt drawn log-uniform in [1e-3, 1e-1]
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(next(ks), (L, nh), f32,
                                                1.0, 16.0)),
            "d_skip": 1.0 + rm.uniform(next(ks), (L, nh), 0.1, f32),
            "gate_norm": rm.uniform(next(ks), (L, n.din), 0.1, f32),
            "out_proj": rm.stacked(next(ks), L, (n.din, d),
                                   1.0 / math.sqrt(n.din), bf),
        },
    }
    tok = rm.uniform(next(ks), (n.vocab_padded, d), 0.02, bf)
    return {"embed": {"tok": tok, "lm_head": tok.T},      # tied
            "groups": {"l0": layer},
            "final_norm": rm.uniform(next(ks), (d,), 0.1, f32)}


def make_params(cfg: dict, seed: int):
    """The served weights (bf16 matrices, float32 per-head and norm
    parameters) from the seed. The head is the embedding, transposed."""
    return _make(dims(cfg), rm.key_from_seed(seed))


def _mixer(n: Dims, p, h, fp8: bool):
    """One SSD mixer over h: (P, d) float32."""
    P = h.shape[0]
    gn = n.groups * n.state
    proj = rm.matmul(h, p["in_proj"], fp8)
    z, xbc, dt = proj[:, :n.din], proj[:, n.din:n.din + n.conv_dim], \
        proj[:, -n.heads:]
    w = p["conv_w"].astype(jnp.float32)
    pad = jnp.pad(xbc, ((n.conv - 1, 0), (0, 0)))
    xbc = sum(pad[i:i + P] * w[i] for i in range(n.conv)) \
        + p["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :n.din].reshape(P, n.heads, n.headdim)
    rep = n.heads // n.groups
    b = jnp.repeat(xbc[:, n.din:n.din + gn].reshape(P, n.groups, n.state),
                   rep, axis=1)                                # (P, H, N)
    c = jnp.repeat(xbc[:, n.din + gn:].reshape(P, n.groups, n.state),
                   rep, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # (P, H)
    cum = jnp.cumsum(dt * -jnp.exp(p["a_log"]), axis=0)        # (P, H)
    i, j = jnp.arange(P)[:, None], jnp.arange(P)[None, :]
    seg = jnp.where((j <= i)[None], cum.T[:, :, None] - cum.T[:, None, :],
                    -jnp.inf)                                  # (H, P, P)
    cb = jnp.einsum("ihn,jhn->hij", c, b, precision=rm.HIGHEST)
    m = jnp.exp(seg) * cb * dt.T[:, None, :]
    y = jnp.einsum("hij,jhp->ihp", m, xs, precision=rm.HIGHEST)
    y = y + p["d_skip"][None, :, None] * xs
    y = y.reshape(P, n.din) * jax.nn.silu(z)
    y = rm.rms_norm(y, p["gate_norm"], n.eps)
    return rm.matmul(y, p["out_proj"], fp8)


def _logits(n: Dims, params, tokens, fp8: bool):
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def layer(x, p):
        return x + _mixer(n, p["ssm"], rm.rms_norm(x, p["norm1"], n.eps),
                          fp8), None

    x, _ = jax.lax.scan(layer, x, params["groups"]["l0"])
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
    """Forward FLOPs of one token: in- and out-projections, the conv, the
    state update (decay, dt*B*x outer product, add) and its read-out
    (C.h, D x), and the head."""
    n = dims(cfg)
    proj = 2 * n.din + 2 * n.groups * n.state + n.heads
    mats = n.d * proj + n.din * n.d
    ssd = n.heads * n.headdim * n.state * 5
    return 2.0 * n.layers * mats + n.layers * (2 * n.conv * n.conv_dim
                                               + ssd) + 2.0 * n.d * n.vocab


def flops_per_context_token(cfg: dict) -> float:
    """No attention: a token's work does not grow with its context."""
    return 0.0


def attention_layers(cfg: dict) -> int:
    return 0

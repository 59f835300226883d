"""nemotron-h-47b [hybrid] — Mamba-2, GQA and squared-ReLU MLP layers in
the order of `hybrid_override_pattern` [arXiv:2504.03624;
huggingface.co/nvidia/Nemotron-H-47B-Base-8K].

Each of the 98 layers is ``h + F(RMSNorm(h))`` with one F: a Mamba-2
mixer (``M``), grouped-query attention without position encoding
(``*``: the config has no RoPE key; the Mamba layers carry position), or
an un-gated squared-ReLU MLP (``-``)."""
from repro.configs.base import (ATTN, MIXER_NONE, MLP_DENSE, MLP_NONE, SSD,
                                ModelConfig, register)

# hybrid_override_pattern of the published config.json
PATTERN = ("M-M-M-M-M-M-M-M-M*-M-M-M-M-M-M-M-M-M-M*-M-M-M-M-M*-M-M-M-M-M*-"
           "M-M-M-M-M-M-M---MM---M-M*-M-M-M-M-M-")

LAYER = {"M": (SSD, MLP_NONE), "*": (ATTN, MLP_NONE),
         "-": (MIXER_NONE, MLP_DENSE)}


def kinds(pattern: str) -> tuple:
    """(mixer, mlp) per layer of a pattern string of M, * and -."""
    return tuple(LAYER[c] for c in pattern)


@register("nemotron-h-47b")
def config() -> ModelConfig:
    return ModelConfig(
        name="nemotron-h-47b",
        family="hybrid",
        num_layers=98,
        d_model=8192,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        d_ff=30720,
        mlp_act="relu2",
        rope=False,
        vocab_size=131072,
        ssm_state=256,
        ssm_expand=2,             # d_inner = 16384 = 256 heads x 64
        ssm_head_dim=64,
        ssm_ngroups=8,
        ssm_conv_width=4,
        ssm_chunk=128,
        pattern=kinds(PATTERN),
    )

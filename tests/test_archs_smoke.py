"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU; output shapes + finiteness (assignment requirement f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs, shapes_for, smoke_config
from repro.data.pipeline import TokenPipeline
from repro.models import Model
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import init_state, make_train_step

ARCHS = list_archs()


def test_all_ten_archs_registered():
    # the assignment's ten, and nemotron-h-47b since
    assert len(ARCHS) == 11


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    expected = {
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
        "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "mamba2-780m": (48, 1536, 0, 0, 0, 50280),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "nemotron-h-47b": (98, 8192, 64, 8, 30720, 131072),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 32
    pipe = TokenPipeline(cfg, s, b, seed=1)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    logits, aux = model.forward_train(params, batch)
    assert logits.shape == (b, s, cfg.padded_vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg = smoke_config(arch)
    model = Model(cfg)
    oc = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    step = jax.jit(make_train_step(model, oc))
    state = init_state(model, oc, jax.random.PRNGKey(0))
    pipe = TokenPipeline(cfg, 32, 2, seed=2)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    state, metrics = step(state, batch)
    assert np.isfinite(metrics["loss"])
    assert np.isfinite(metrics["grad_norm"])
    assert int(state["opt"]["step"]) == 1
    for g in jax.tree.leaves(state["params"]):
        assert bool(jnp.isfinite(g).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_assigned_shape_cells(arch):
    cfg = get_config(arch)
    names = [s.name for s in shapes_for(cfg)]
    assert names[:3] == ["train_4k", "prefill_32k", "decode_32k"]
    if arch in ("mamba2-780m", "recurrentgemma-2b"):
        assert "long_500k" in names      # sub-quadratic archs
    else:
        assert "long_500k" not in names  # skipped per assignment


def test_param_counts_sane():
    # spec-tree param counts should track the analytic ModelConfig counts
    for arch in ARCHS:
        cfg = get_config(arch)
        analytic = cfg.param_count()
        spec = Model(cfg).param_count()
        ratio = spec / analytic
        assert 0.9 < ratio < 1.15, (arch, analytic, spec)

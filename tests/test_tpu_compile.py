"""Compile-only rehearsals for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, so `paged_attention` and the
fused decode step are compiled here at starcoder2-7b's served widths
exactly as the chip would compile them: a tile that Mosaic refuses, or a
step that outgrows the chip, fails these tests without any chip time.
The topology is described inside a module fixture (never at import),
because only one process at a time may load the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.autotune import autotune_kernel
from repro.kernels import api, registry

# the starcoder2 chat cell as served: 32 rows, a 104-slot page table, a
# pool of 4096 slots (capacity 1536 tokens)
BATCH, PAGE_TOKENS, SLOTS, CAPACITY_SLOTS = 32, 16, 104, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip writes a cache entry that no CPU run
    # can read back: keep any persistent cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _served_args(one_chip, k, q_dtype, pool_dtype, model="starcoder2"):
    """Argument shapes of the fused step's kernel call: starcoder2-7b cut
    to 16 layers (36 q / 4 kv heads), or the Nemotron-H stage's one
    attention layer (64 q / 8 kv heads); layer-stacked pools."""
    if model == "starcoder2":
        cfg = get_config("starcoder2-7b", num_layers=16)
        layers = cfg.num_layers
    else:
        cfg = get_config("nemotron-h-47b", num_layers=11, first_layer=40,
                         vocab_size=16384)
        layers = 1
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pool = (layers, CAPACITY_SLOTS, PAGE_TOKENS, hkv, d)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    q = (BATCH, hq, d) if k == 1 else (BATCH, k, hq, d)
    return (sds(q, q_dtype), sds(pool, pool_dtype), sds(pool, pool_dtype),
            sds(pool, "int8"), sds(pool, "int8"),
            sds(pool[:-1], pool_dtype), sds(pool[:-1], pool_dtype),
            sds((BATCH, SLOTS), "int32"), sds((BATCH,), "int32"),
            sds((), "int32"))


def _compile(args, tile):
    spec = registry.get("paged_attention")
    fn = functools.partial(spec.pallas_fn, **tile, interpret=False)
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("q_dtype,pool_dtype", [
    ("bfloat16", "bfloat16"), ("float32", "float32"),
    ("bfloat16", "float32")])   # the last is what the fused step serves
@pytest.mark.parametrize("k", [1, 4, 16])
def test_paged_attention_auto_tile_compiles(one_chip, k, q_dtype,
                                            pool_dtype):
    args = _served_args(one_chip, k, q_dtype, pool_dtype)
    tile = api.resolve_tile("paged_attention", args)
    text = _compile(args, tile).as_text()
    assert "tpu_custom_call" in text


def _every_tile_compiles(one_chip, k, model):
    spec = registry.get("paged_attention")
    args = _served_args(one_chip, k, "bfloat16", "float32", model)
    grid = spec.grid_of(*args)
    tiles = [c.params for c in
             autotune_kernel(spec, grid, dtype="bfloat16")["candidates"]]
    assert tiles
    for tile in tiles:
        _compile(args, tile)


@pytest.mark.parametrize("k", [1, 4, 16])
def test_every_tune_space_tile_compiles(one_chip, k):
    """Every tile the autotuner can pick at the served shapes (those the
    cost model does not reject) is one the TPU accepts: bf16 q, float32
    and int8 pools of starcoder2's 16 layers."""
    _every_tile_compiles(one_chip, k, "starcoder2")


@pytest.mark.parametrize("k", [1, 16])
def test_every_tile_compiles_for_the_nemotron_layer(one_chip, k):
    """The same for the Nemotron-H stage's attention layer: 64 q / 8 kv
    heads, one layer's pool."""
    _every_tile_compiles(one_chip, k, "nemotron")


@pytest.mark.parametrize("k", [1, 4])
def test_fused_step_compiles_at_16_layers(one_chip, monkeypatch, k):
    """The whole fused decode step (plain and 4-row verify) of the
    16-layer starcoder2-7b cut compiles for one chip with the kernel as
    a Mosaic custom call, and its arguments fit the chip's 16 GB."""
    from repro.models import Model
    from repro.serve.paged_decode import build_fused_step
    from repro.serve.paged_state import StateLayout

    # api.run would see the CPU and interpret; compile as the chip would
    monkeypatch.setattr(api, "default_interpret", lambda: False)
    cfg = get_config("starcoder2-7b", num_layers=16)
    model = Model(cfg)
    lay = StateLayout(cfg, PAGE_TOKENS)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    args = _served_args(one_chip, 1, "bfloat16", "float32")[1:7]
    control = sds((BATCH, lay.cols(SLOTS, k).width), "int32")
    key = sds((2,), "uint32")
    step = build_fused_step(model, SLOTS, k=k, layout=lay)
    compiled = step.lower(params, args, control, key).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("k", [1, 16])
def test_nemotron_stage_step_fits_one_chip(one_chip, monkeypatch, k):
    """The fused step of the 11-layer Nemotron-H-47B stage (published
    layers 40-50, 16384 vocabulary rows) at 32 rows compiles for one chip
    and fits it: plain decode, and the 16-wide prompt-chunk step, whose
    SSD layers read and write each row's state once (k stacked states of
    84.5 MB a row would not fit beside the 2.8 GB store)."""
    from repro.models import Model
    from repro.serve.paged_decode import build_fused_step
    from repro.serve.paged_state import StateLayout, rec_array_names
    from repro.models.ssm import ssm_dims

    monkeypatch.setattr(api, "default_interpret", lambda: False)
    cfg = get_config("nemotron-h-47b", num_layers=11, first_layer=40,
                     vocab_size=16384)
    model = Model(cfg)
    lay = StateLayout(cfg, PAGE_TOKENS)
    rows, slots, pool_slots = BATCH, SLOTS, CAPACITY_SLOTS

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    pool = (lay.n_kv, pool_slots, PAGE_TOKENS, cfg.num_kv_heads,
            cfg.head_dim)
    _din, nh, conv_dim = ssm_dims(cfg)
    store = {"ssd_state": sds((lay.n_ssd, rows + 1, nh, cfg.ssm_head_dim,
                               cfg.ssm_state), "float32"),
             "ssd_conv": sds((lay.n_ssd, rows + 1, cfg.ssm_conv_width - 1,
                              conv_dim), "bfloat16")}
    arrays = (sds(pool, "float32"), sds(pool, "float32"),
              sds(pool, "int8"), sds(pool, "int8"),
              sds(pool[:-1], "float32"), sds(pool[:-1], "float32")) \
        + tuple(store[n] for n in rec_array_names(lay))
    control = sds((rows, lay.cols(slots, k).width), "int32")
    key = sds((2,), "uint32")
    step = build_fused_step(model, slots, k=k, layout=lay, drafts=False)
    compiled = step.lower(params, arrays, control, key).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9, \
        (mem.argument_size_in_bytes, mem.temp_size_in_bytes)

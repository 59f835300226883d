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

BATCH, PAGE_TOKENS, CAPACITY_SLOTS = 4, 16, 256


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


def _served_args(one_chip, k, q_dtype, pool_dtype):
    """Argument shapes of the fused step's kernel call for starcoder2-7b
    cut to 16 layers: layer-stacked pools, a 544-token page table."""
    cfg = get_config("starcoder2-7b", num_layers=16)
    slots = 40
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pool = (cfg.num_layers, CAPACITY_SLOTS, PAGE_TOKENS, hkv, d)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    q = (BATCH, hq, d) if k == 1 else (BATCH, k, hq, d)
    return (sds(q, q_dtype), sds(pool, pool_dtype), sds(pool, pool_dtype),
            sds(pool, "int8"), sds(pool, "int8"),
            sds(pool[:-1], pool_dtype), sds(pool[:-1], pool_dtype),
            sds((BATCH, slots), "int32"), sds((BATCH,), "int32"),
            sds((), "int32"))


def _compile(args, tile):
    spec = registry.get("paged_attention")
    fn = functools.partial(spec.pallas_fn, **tile, interpret=False)
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("q_dtype,pool_dtype", [
    ("bfloat16", "bfloat16"), ("float32", "float32"),
    ("bfloat16", "float32")])   # the last is what the fused step serves
@pytest.mark.parametrize("k", [1, 4])
def test_paged_attention_auto_tile_compiles(one_chip, k, q_dtype,
                                            pool_dtype):
    args = _served_args(one_chip, k, q_dtype, pool_dtype)
    tile = api.resolve_tile("paged_attention", args)
    text = _compile(args, tile).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [1, 4])
def test_every_tune_space_tile_compiles(one_chip, k):
    """Every tile the autotuner can pick at the served shapes (those the
    cost model does not reject) is one the TPU accepts."""
    spec = registry.get("paged_attention")
    args = _served_args(one_chip, k, "bfloat16", "float32")
    grid = spec.grid_of(*args)
    tiles = [c.params for c in
             autotune_kernel(spec, grid, dtype="bfloat16")["candidates"]]
    assert tiles
    for tile in tiles:
        _compile(args, tile)


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
    slots = 40

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          model.abstract_params())
    args = _served_args(one_chip, 1, "bfloat16", "float32")[1:7]
    control = sds((BATCH, lay.cols(slots, k).width), "int32")
    key = sds((2,), "uint32")
    step = build_fused_step(model, slots, k=k, layout=lay)
    inputs = (params, args, sds((BATCH,), "int32"), control, key) \
        if k == 1 else (params, args, control, key)
    compiled = step.lower(*inputs).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9

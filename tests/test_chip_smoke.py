"""`chip_smoke.py`'s phases at smoke size on the CPU (kernels interpreted),
and its refusal to run without a TPU. The four-chip mesh phase runs in a
child process over four forced host devices."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import smoke_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("starcoder2-7b")


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    assert "ok" not in capsys.readouterr().out


def test_kernel_phase(smoke, cfg):
    out = smoke.kernel_phase(cfg, jax.devices()[0], batch=2, capacity=40,
                             page_tokens=8, ks=(1, 4))
    assert set(out) == {1, 4}
    for r in out.values():
        assert r["tile"]["head_block"] == cfg.num_kv_heads


def test_serve_phase(smoke, cfg):
    out = smoke.serve_phase(cfg, jax.devices()[0], prompt_lens=(6, 9, 13),
                            max_new=5, page_tokens=4, speculate=4)
    assert out["warm_compiles"] == 0
    assert [len(o) for o in out["outputs"]] == [5, 5, 5]
    assert [len(o) for o in out["spec_outputs"]] == [5, 5, 5]
    # interpreted on the CPU: the kernel is not a Mosaic custom call here
    assert out["kernel_calls"] == 0


def test_mesh_phase_on_four_host_devices():
    code = (
        "import jax, json, importlib.util\n"
        "from repro.configs import smoke_config\n"
        "s = importlib.util.spec_from_file_location('chip_smoke', "
        f"{str(ROOT / 'chip_smoke.py')!r})\n"
        "m = importlib.util.module_from_spec(s); s.loader.exec_module(m)\n"
        "out = m.mesh_phase(smoke_config('starcoder2-7b'), jax.devices(), "
        "prompt_lens=(6, 9, 13, 7), max_new=5, page_tokens=4)\n"
        "print(json.dumps(out))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    run = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    # float32 at smoke size: the mesh reproduces one device exactly
    assert out["diverge"] == [None] * 4


def test_compile_cache_placement():
    """The cache follows JAX_COMPILATION_CACHE_DIR when it is set and
    otherwise lands in the checkout's git-ignored .jax_cache/."""
    from repro.launch.compile_cache import cache_dir
    assert cache_dir({}) == ROOT / ".jax_cache"
    assert cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x/y"}) == Path("/x/y")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache(), "
            "jax.config.jax_compilation_cache_dir)\n")
    for set_dir in (None, "/x/y"):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
        if set_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = set_dir
        run = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert run.returncode == 0, run.stderr[-2000:]
        want = set_dir or str(ROOT / ".jax_cache")
        assert run.stdout.split() == [want, want]

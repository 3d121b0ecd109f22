"""``chip_smoke.py`` off the chip: its phases at reduced widths on the CPU
(the Pallas interpreter for the kernels, 4 virtual devices for the mesh),
its refusal to run anywhere but a TPU, and the compile-cache helper the
smoke and the launchers share.
"""
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from conftest import REPO, run_subprocess
from repro.configs.base import get_config

SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def cfg():
    return get_config(chip_smoke.ARCH).reduced()


def _run_smoke(path, cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_refuses_cpu():
    """No TPU → non-zero exit before any work, and never an ok line."""
    res = _run_smoke(SMOKE, REPO, {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stderr


def test_smoke_alone_fails(tmp_path):
    """Copied away from the repo it cannot import the program: it fails."""
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, lone)
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    res = _run_smoke(str(lone), str(tmp_path), env)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


def test_kernel_phase_interpreted(cfg):
    r = chip_smoke.kernel_phase(cfg, interpret=True, batch=2, max_pages=4,
                                prefill_q=64, prefill_k=128)
    assert r["paged_err"] <= chip_smoke.KERNEL_TOL
    assert r["flash_err"] <= chip_smoke.KERNEL_TOL


def test_serve_phase_reduced_widths(cfg):
    """Phase (c) through the launcher's engine on the CPU: every request
    completes, a shared prefix run is adopted, and paged decode is
    bit-exact with the dense arena."""
    r = chip_smoke.serve_phase(cfg)
    assert r["tokens"] == r["tokens_equal"] > 0
    assert r["paged"]["page_pool"].stats()["shared_adoptions"] > 0
    assert set(r["paged"]["tokens"]) == set(r["dense"]["tokens"])


def test_mesh_phase_on_virtual_devices():
    """Phase (d) on 4 virtual CPU devices: a 2x2 mesh, bit-exact with 1x1,
    slabs and logits spread over all four."""
    out = run_subprocess('''
import dataclasses
import chip_smoke
from repro.configs.base import get_config
cfg = dataclasses.replace(get_config("qwen2.5-3b").reduced(), n_heads=16)
r = chip_smoke.mesh_phase(cfg, 2, 2, n_requests=4, max_new=4)
assert r["tokens_equal"] == r["tokens"] > 0, r
assert r["slab_devices"] == r["logit_devices"] == 4, r
print("MESH_OK")
''', devices=4)
    assert "MESH_OK" in out


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """Entries land in $JAX_COMPILATION_CACHE_DIR when it is set (and
    nowhere else), else in the fixed <root>/.jax_cache."""
    env_dir = tmp_path / "env_cache"
    root = tmp_path / "root"
    code = f'''
import os, jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache({str(root)!r}))
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
'''
    env = dict(os.environ, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    want, other = (env_dir, root / ".jax_cache") if from_env \
        else (root / ".jax_cache", env_dir)
    assert res.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir())
    assert not other.exists()

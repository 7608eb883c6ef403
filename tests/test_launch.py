"""The training launcher: sharded against unsharded, mesh checks, cache."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_sharded_launcher_matches_unsharded(tmp_path):
    """2 reduced steps on a 2 x 2 mesh of virtual CPU devices: step 0 sees
    the same weights and batch as the one-device run, so the same loss."""
    script = textwrap.dedent(f"""
        from repro.launch import train
        base = ["--arch", "qwen2-0.5b", "--steps", "2", "--batch", "4",
                "--seq", "32"]
        assert train.main(base + ["--data", "2", "--model-par", "2",
                                  "--log", {str(tmp_path / "mesh.jsonl")!r}]) == 0
        assert train.main(base + ["--log", {str(tmp_path / "one.jsonl")!r}]) == 0
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": SRC,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]

    def losses(name):
        with open(tmp_path / f"{name}.jsonl") as f:
            return {r["step"]: r["loss"] for r in map(json.loads, f)}

    mesh, one = losses("mesh"), losses("one")
    assert sorted(mesh) == sorted(one) == [0, 1]
    assert mesh[0] == pytest.approx(one[0], rel=1e-5)  # fp32 reduced size
    assert mesh[1] == pytest.approx(one[1], rel=1e-4)


def test_host_mesh_refuses_more_devices_than_exist():
    n = len(jax.devices())
    assert make_host_mesh(n, 1).devices.size == n
    with pytest.raises(ValueError, match="available"):
        make_host_mesh(n + 1, 1)
    with pytest.raises(ValueError, match="available"):
        make_host_mesh(1, 2 * n)


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    root = os.path.abspath(os.path.join(SRC, ".."))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_honours_environment(monkeypatch, restore_cache_dir,
                                           tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # JAX reads the var

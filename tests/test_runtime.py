"""Process-level setup: the compile-cache placement, Auto-axis meshes,
and ``chip_smoke.py`` refusing to run without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.sharding import AxisType

from repro import runtime

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache directory after a test moves it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(monkeypatch, cache_config, tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own setting stands:
    the helper reports it and changes nothing."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_repo_path(monkeypatch, cache_config):
    """Without it, the cache goes to one fixed directory in the
    checkout — the same path on every call and in every process."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.setup_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.setup_compile_cache() == path


def test_meshes_have_auto_axes():
    mesh = runtime.cc_mesh(1)
    assert mesh.axis_names == ("cc",)
    assert mesh.axis_types == (AxisType.Auto,)
    mesh2 = runtime.make_mesh((1, 1), ("data", "model"))
    assert mesh2.axis_types == (AxisType.Auto, AxisType.Auto)


def test_force_cpu_devices_only_on_cpu(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    runtime.force_cpu_devices(4)
    assert "XLA_FLAGS" not in os.environ
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    runtime.force_cpu_devices(4)
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=4"


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    """On the CPU — in the checkout, and copied alone into an empty
    directory — the smoke test exits non-zero and prints no result."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "FAIL" in out.stderr

"""The chip smoke script refuses to run without a TPU, and the compile cache
goes where the deployment says or to one fixed place in the checkout."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    script = os.path.join(_REPO_ROOT, "chip_smoke.py")
    if where == "alone":   # a directory holding the script and nothing else
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=120, cwd=tmp_path, env=env)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


@pytest.mark.parametrize("platform", ["tpu", "cpu", "gpu"])
def test_kernels_interpret_only_on_cpu(monkeypatch, platform):
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import attention

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if platform != "gpu":
        assert ops.interpret_mode() is (platform == "cpu")
        return
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()
    monkeypatch.setattr(attention, "_ATTN_IMPL", "pallas")
    x = jnp.zeros((1, 16, 2, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="gpu"):
        attention.blockwise_attention(x, x, x, causal=True)


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_in_checkout_from_any_cwd(monkeypatch, tmp_path,
                                                restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO_ROOT, ".jax_cache")
    for cwd in (tmp_path, _REPO_ROOT):
        monkeypatch.chdir(cwd)
        assert compile_cache.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want

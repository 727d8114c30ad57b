"""The chip entry points' host-side contract: the compile-cache location,
``chip_smoke.py``'s refusal to run without a TPU, and its kernel census."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test moves it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_keeps_the_environment_directory(monkeypatch,
                                                       cache_config,
                                                       tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_ignored_repo_directory(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR.parent == Path(REPO)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert f"{REPO_CACHE_DIR.name}/" in f.read().split()


def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert "no TPU found" in res.stderr
    assert '"ok"' not in res.stdout


def test_chip_smoke_kernel_census():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    def call(op_name):
        return ('%a = f32[8,128] custom-call(%x), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="{op_name}"}}')
    hlo = "\n".join([
        call("jit(f)/vmap(jvp(layer_0))/jit(spmm)/jit(halo_spmm)/"
             "jit(halo_spmm_stream_pallas)/pallas_call"),
        call("jit(f)/vmap(jvp(layer_0))/jit(halo_spmm)/"
             "jit(halo_spmm_skip_pallas)/pallas_call"),
        call("jit(f)/layer_2/jit(halo_spmm)/jit(halo_spmm_pallas)/"
             "pallas_call"),
        call("jit(f)/jit(halo_spmm)/jit(halo_spmm_stream_pallas)/"
             "pallas_call"),
        call("jit(f)/jit(halo_spmm)/jit(halo_spmm_stream_pallas)/"
             "pallas_call"),
        '%d = f32[8,128] fusion(%x), metadata={op_name="jit(f)/layer_1/'
        'jit(halo_spmm_skip_pallas)"}',
    ])
    assert chip_smoke.kernel_calls(hlo) == {
        (0, "local", "stream"): 1, (0, "halo", "skip"): 1,
        (2, "halo", "resident"): 1, (-1, "halo", "stream"): 2}

"""What the platform decides: kernel backend and interpret mode (never an
emulation on a TPU), the pools' dtype (the params'), and where the
persistent compilation cache lives."""
import jax
import jax.numpy as jnp
import pytest

from repro import compile_cache
from repro.configs import get_config
from repro.engine import runner as R
from repro.engine.server import HydraServer
from repro.kernels import resolve_interpret
from repro.launch.serve import parse_disagg
from repro.models import model as M


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_interpret_default_follows_platform(monkeypatch):
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False


@pytest.mark.parametrize("env", ["", "kernel"])
def test_tpu_runs_compiled_kernels(on_tpu, monkeypatch, env):
    monkeypatch.setenv("REPRO_PAGED_IMPL", env)
    assert R.default_attn_impl() == "kernel"


@pytest.mark.parametrize("env", ["interpret", "ref", "bogus"])
def test_tpu_rejects_other_backends(on_tpu, monkeypatch, env):
    monkeypatch.setenv("REPRO_PAGED_IMPL", env)
    with pytest.raises(ValueError, match="REPRO_PAGED_IMPL"):
        R.default_attn_impl()


def test_cpu_backend_choice(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.delenv("REPRO_PAGED_IMPL", raising=False)
    assert R.default_attn_impl() == "interpret"
    monkeypatch.setenv("REPRO_PAGED_IMPL", "ref")
    assert R.default_attn_impl() == "ref"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_pools_take_the_params_dtype(dtype):
    cfg = get_config("llava-1.5-7b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype)
    srv = HydraServer(cfg, params, parse_disagg("E1,P1,D1"), kv_blocks=8,
                      img_blocks=2)
    for inst in srv.instances:
        assert inst.caches.kv.data.dtype == dtype
        assert inst.caches.img.data.dtype == dtype


def test_compile_cache_dir_from_env(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_fixed_in_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.REPO_CACHE_DIR.parent.joinpath(
            "src", "repro", "compile_cache.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

"""``chip_smoke.py`` on the CPU: its serve phase at the smoke config, its
refusal to run without a TPU, and where the compile cache it enables
lives."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache
from repro.models.config import get_smoke_config
from repro.models.transformer import Model

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _child_env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_serve_phase_at_smoke_config():
    """All 16 requests complete with their 32 tokens, and request 0 served
    alone matches its batched tokens (lane isolation)."""
    smoke = _load_smoke()
    cfg = get_smoke_config(smoke.ARCH)
    model = Model(cfg)
    params = model.init(smoke.SEED)
    requests = smoke.make_requests(cfg.vocab)
    assert len(requests) == smoke.N_REQUESTS
    assert all(smoke.PROMPT_LENS[0] <= len(r.prompt) <= smoke.PROMPT_LENS[1]
               for r in requests)
    stats = smoke.serve_and_check(model, params, requests)
    assert stats["tokens"] == smoke.N_REQUESTS * smoke.NEW_TOKENS
    # prompts stream through decode: every request costs len(prompt) +
    # max_new_tokens - 1 lane-steps, spread over the slots
    lane_steps = sum(r.total_steps for r in requests)
    assert lane_steps / smoke.BATCH_SLOTS <= stats["steps"] <= lane_steps
    assert stats["solo"]["steps"] == requests[0].total_steps


def test_check_served_rejects_short_output():
    smoke = _load_smoke()
    req = smoke.make_requests(vocab=256, n=1)[0]
    req.out, req.done = [1] * (req.max_new_tokens - 1), True
    with pytest.raises(AssertionError, match="expected"):
        smoke.check_served([req], vocab=256)
    req.out = [1] * (req.max_new_tokens - 1) + [256]
    with pytest.raises(AssertionError, match="outside"):
        smoke.check_served([req], vocab=256)


def test_main_fails_without_tpu(tmp_path):
    res = subprocess.run(
        [sys.executable, str(SMOKE)], cwd=tmp_path, capture_output=True,
        text=True, timeout=300,
        env=_child_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_compile_cache_dir_follows_environment(monkeypatch):
    import jax
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = str(ROOT / ".jax_cache")
        assert compile_cache.enable_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    cache = tmp_path / "cache"
    default = compile_cache.DEFAULT_CACHE_DIR
    before = sorted(default.iterdir()) if default.exists() else []
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()\n")
    subprocess.run([sys.executable, "-c", code], cwd=tmp_path, check=True,
                   timeout=300,
                   env=_child_env(JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert any(cache.iterdir())
    after = sorted(default.iterdir()) if default.exists() else []
    assert after == before

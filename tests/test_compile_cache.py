"""Placement of JAX's persistent compilation cache (entry points only)."""
from pathlib import Path

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_var_is_honoured():
    path, from_env = compile_cache.cache_dir(
        {compile_cache.CACHE_ENV: "/cache/from/env"})
    assert (path, from_env) == ("/cache/from/env", True)


def test_fallback_is_fixed_and_inside_the_checkout():
    path, from_env = compile_cache.cache_dir({})
    assert not from_env
    assert Path(path) == REPO / ".jax_cache"
    # the same directory every time: no temp name, pid or time in it
    assert compile_cache.cache_dir({})[0] == path
    assert compile_cache.cache_dir({compile_cache.CACHE_ENV: ""})[0] == path

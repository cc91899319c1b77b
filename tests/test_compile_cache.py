"""Shared compile cache: bucketing math + jit-pool compile accounting,
and where the persistent compilation cache is placed."""
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.core import compile_cache as cc
from repro.core import fed_engine


def test_next_pow2():
    assert [cc.next_pow2(n) for n in (1, 2, 3, 4, 5, 17, 64)] \
        == [1, 2, 4, 4, 8, 32, 64]
    with pytest.raises(ValueError):
        cc.next_pow2(0)


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_persistent_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache sits at the fixed <checkout>/.jax_cache."""
    before = jax.config.jax_compilation_cache_dir
    checkout = pathlib.Path(__file__).resolve().parents[1]
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert cc.use_persistent_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = cc.use_persistent_cache()
            assert path == str(checkout / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_bucket_for_clamps_and_caps():
    assert cc.bucket_for(1, 8, 64) == 8      # clamped up to min_bucket
    assert cc.bucket_for(8, 8, 64) == 8
    assert cc.bucket_for(9, 8, 64) == 16
    assert cc.bucket_for(64, 8, 64) == 64
    assert cc.bucket_for(40, 8, 48) == 48    # capped at non-pow2 max_len
    with pytest.raises(ValueError):
        cc.bucket_for(65, 8, 64)             # doesn't fit the cache
    with pytest.raises(ValueError):
        cc.bucket_for(0, 8, 64)


def test_bucket_ladder_covers_every_bucket_for():
    assert cc.bucket_ladder(8, 64) == (8, 16, 32, 64)
    assert cc.bucket_ladder(8, 48) == (8, 16, 32, 48)
    assert cc.bucket_ladder(8, 8) == (8,)
    for min_bucket, max_len in ((8, 64), (4, 48), (16, 100)):
        ladder = set(cc.bucket_ladder(min_bucket, max_len))
        for P in range(1, max_len + 1):
            assert cc.bucket_for(P, min_bucket, max_len) in ladder


def test_jit_cache_counts_shapes_per_entry():
    cache = cc.JitCache()

    def dbl(x):
        return x * 2

    def neg(x):
        return -x

    cache.call("dbl", dbl, (), (jnp.zeros((2,)),))
    cache.call("dbl", dbl, (), (jnp.zeros((3,)),))   # new shape, same entry
    cache.call("dbl", dbl, (), (jnp.zeros((3,)),))   # cached
    cache.call(("tag", 1), neg, (), (jnp.zeros((2,)),))
    assert cache.count("dbl") == 2
    assert cache.count("tag") == 1       # tuple-named entries match by head
    assert cache.count("missing") == 0
    assert cache.num_compiled == 3


def test_jit_cache_counts_survive_missing_private_api():
    """Compile counts read jax.jit's private _cache_size(); if a jax
    release drops it, counts fall back to the recorded argument-signature
    sets instead of raising from every assertion at once."""
    cache = cc.JitCache()

    def dbl(x):
        return x * 2

    cache.call("dbl", dbl, (), (jnp.zeros((2,)),))
    cache.call("dbl", dbl, (), (jnp.zeros((3,)),))
    cache.call("dbl", dbl, (), (jnp.zeros((3,)),))   # cached shape
    assert cache.count("dbl") == 2
    # simulate the private API vanishing: the stored wrapper no longer
    # has a working _cache_size()
    cache._jits[("dbl", ())] = object()
    assert cache.count("dbl") == 2          # falls back to signatures
    assert cache.num_compiled == 2


def test_fed_engine_runs_on_the_shared_cache():
    """The engine's jit pool IS compile_cache.JitCache (the extraction
    changed the import, not the behavior — parity/compile-count tests in
    test_fed_engine.py pin the behavior itself)."""
    assert fed_engine._JitCache is cc.JitCache

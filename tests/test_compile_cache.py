"""Persistent XLA compilation cache plumbing (utils.compile_cache)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from pytorch_multiprocessing_distributed_tpu.utils import compile_cache
from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
    enable_compilation_cache,
)


@pytest.fixture
def on_tpu(monkeypatch, tmp_path):
    """Steer the platform detection (in the test, not through an option
    of the program) and point the in-checkout default at a temp dir;
    the cache machinery itself is platform-agnostic, so exercising it
    on the CPU is representative. Restores jax's cache config after."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_platform", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "CACHE_DIR",
                        str(tmp_path / "jax_cache"))
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield tmp_path
    for k, v in keep.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _cache_dir_updates(monkeypatch):
    """Record every ``jax.config.update`` of the cache directory."""
    seen = []
    real = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return seen


def test_env_var_set_means_no_cache_dir_update_in_code(on_tpu,
                                                       monkeypatch):
    """JAX_COMPILATION_CACHE_DIR placed the cache from outside: jax
    already uses it, and the program sets no directory of its own."""
    outside = str(on_tpu / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    seen = _cache_dir_updates(monkeypatch)
    assert enable_compilation_cache() == outside
    assert seen == []


def test_env_var_unset_means_the_fixed_in_checkout_path(on_tpu,
                                                        monkeypatch):
    seen = _cache_dir_updates(monkeypatch)
    assert enable_compilation_cache() == compile_cache.CACHE_DIR
    assert seen == [compile_cache.CACHE_DIR]


def test_default_cache_dir_is_one_path_across_processes(tmp_path):
    """The path is part of the cache key: it must not depend on the
    process, its cwd, HOME, TMPDIR or the time — only on the checkout."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    here = compile_cache.CACHE_DIR
    assert here == os.path.join(repo, ".jax_cache")
    env = dict(os.environ, HOME=str(tmp_path), TMPDIR=str(tmp_path),
               PYTHONPATH=repo)
    there = subprocess.run(
        [sys.executable, "-c",
         "from pytorch_multiprocessing_distributed_tpu.utils import "
         "compile_cache as c; print(c.CACHE_DIR)"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        check=True, timeout=120).stdout.strip()
    assert there == here


def test_cpu_platform_skips_cache(monkeypatch):
    # the test env pins JAX_PLATFORMS=cpu (conftest): detection alone
    # must decline — XLA:CPU AOT reloads embed host features (SIGILL
    # hazard) and CPU compiles are cheap
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = _cache_dir_updates(monkeypatch)
    assert enable_compilation_cache() is None
    assert seen == []


def test_cache_writes_compiled_executables_and_logs_the_hit(on_tpu):
    assert enable_compilation_cache() == compile_cache.CACHE_DIR
    # drop the min-compile-time bar: CPU test compiles are sub-0.1 s
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log = compile_cache.CompileLog()

    def program():  # a fresh jit each call, the same HLO each time
        return jax.jit(lambda x: (x @ x.T).sum() + 41.0)

    program()(jnp.ones((64, 64))).block_until_ready()
    entries = [
        name
        for _, _, files in os.walk(compile_cache.CACHE_DIR)
        for name in files
    ]
    assert entries, "compile cache directory stayed empty"
    program()(jnp.ones((64, 64))).block_until_ready()
    cold, warm = log.programs[-2:]
    assert (cold[2], warm[2]) == (False, True)
    summary = log.summary()
    log.close()
    assert summary["cache_hits"] == 1
    assert summary["compiles"] == len(log.programs) >= 2
    assert summary["compile_s"] > 0
    assert summary["longest"][0]["name"]


def test_jit_cache_keys_tracks_static_shapes():
    """record_jit_key attributes each fresh trace (new static arg /
    new shape) to the caller's key; steady-state calls record nothing.
    This is what lets the serving tests pin WHICH decode windows
    compiled, not just how many."""
    from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
        jit_cache_keys, jit_cache_size, record_jit_key)

    from functools import partial

    @partial(jax.jit, static_argnames=("window",))
    def f(x, *, window):
        return x[:window].sum()

    x = jnp.arange(8.0)
    f(x, window=4)
    assert record_jit_key(f, ("decode", 4))
    f(x, window=4)
    assert not record_jit_key(f, ("decode", 4))  # cache hit: no entry
    f(x, window=8)
    assert record_jit_key(f, ("decode", 8))
    assert jit_cache_keys(f) == (("decode", 4), ("decode", 8))
    assert jit_cache_size(f) == 2


def test_lowered_cost_analysis_shared_path():
    """The one lowering path bench.compile_step and the graftcheck
    auditor share: compiles (never runs), returns the executable plus
    XLA's cost dict as a plain dict (utils.compat.cost_analysis_dict)."""
    from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
        lowered_cost_analysis)

    @jax.jit
    def f(a, b):
        return (a @ b).sum()

    a = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 8), jnp.float32)
    compiled, cost = lowered_cost_analysis(f, a, b)
    # abstract args are enough — nothing executed, but the executable
    # is real (the auditor reads its HLO text)
    assert "dot" in compiled.as_text() or "convolution" in compiled.as_text()
    if cost is not None:  # cost model optional per backend
        assert isinstance(cost, dict)
        assert float(cost.get("flops", 0)) >= 0


def test_cost_analysis_dict_is_a_plain_dict_or_none():
    from pytorch_multiprocessing_distributed_tpu.utils.compat import (
        cost_analysis_dict)

    class Compiled:
        def __init__(self, analyses):
            self.analyses = analyses

        def cost_analysis(self):
            if isinstance(self.analyses, Exception):
                raise self.analyses
            return self.analyses

    assert cost_analysis_dict(Compiled({"flops": 7.0})) == {"flops": 7.0}
    # no cost model for the executable: None IS the record
    assert cost_analysis_dict(Compiled(None)) is None
    assert cost_analysis_dict(Compiled({})) is None
    # an error from the call itself is a bug to see, not a missing model
    with pytest.raises(RuntimeError):
        cost_analysis_dict(Compiled(RuntimeError("broken backend")))

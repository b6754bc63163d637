"""Autotuner: JSON cache round-trip, shape-bucket collisions, and parity of
tuned vs default block configs through the ops dispatch layer."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref
from repro.kernels.autotune import Autotuner, bucket_shape, cache_key


@pytest.fixture
def tuner_path(tmp_path):
    return str(tmp_path / "autotune.json")


@pytest.fixture
def installed_tuner(tuner_path):
    """A tmp-backed tuner installed as the process-global one."""
    t = Autotuner(tuner_path, sweep=False)
    autotune.set_tuner(t)
    yield t
    autotune.set_tuner(None)


def test_cache_round_trip_no_resweep(tuner_path):
    calls = []

    def measure(cfg):
        calls.append(cfg)
        return float(cfg["block"])  # smallest candidate wins

    t = Autotuner(tuner_path, sweep=True)
    cfg = t.get("axpy", (4, 1000), "float32", "interpret", measure=measure)
    assert cfg == {"block": 256}
    assert len(calls) == len(autotune.CANDIDATES["axpy"])
    assert t.sweeps_run == 1
    assert os.path.exists(tuner_path)

    # fresh tuner over the same file: hit from disk, measure NEVER called
    def boom(cfg):
        raise AssertionError("re-sweep on a cache hit")

    t2 = Autotuner(tuner_path, sweep=True)
    assert t2.get("axpy", (4, 1000), "float32", "interpret", measure=boom) == {
        "block": 256
    }
    assert t2.sweeps_run == 0
    assert len(json.load(open(tuner_path))) == 1


def test_shape_bucket_collision(tuner_path):
    t = Autotuner(tuner_path, sweep=False)
    win = {"block_m": 64, "block_n": 64, "block_k": 64}
    t.store("matmul", (100, 70, 130), "float32", "interpret", win)
    # (100, 70, 130) and (128, 128, 200) share the (128, 128, 256) bucket
    assert bucket_shape((100, 70, 130)) == bucket_shape((128, 128, 200))
    assert t.lookup("matmul", (128, 128, 200), "float32", "interpret") == win
    # a different bucket, dtype, or backend is a distinct cell
    assert t.lookup("matmul", (300, 70, 130), "float32", "interpret") is None
    assert t.lookup("matmul", (100, 70, 130), "bfloat16", "interpret") is None
    assert t.lookup("matmul", (100, 70, 130), "float32", "pallas") is None


def test_cache_key_is_versioned_and_stable():
    k1 = cache_key("matmul", (100, 70, 130), "float32", "interpret")
    assert k1 == cache_key("matmul", (128, 128, 256), np.float32, "interpret")
    assert k1.startswith(f"v{autotune._SCHEMA_VERSION}|matmul|")


def test_miss_without_sweep_returns_default_and_writes_nothing(tuner_path):
    t = Autotuner(tuner_path, sweep=False)
    cfg = t.get("matmul", (64, 64, 64), "float32", "interpret")
    assert cfg == autotune.DEFAULTS["matmul"]
    assert not os.path.exists(tuner_path)


def test_corrupt_cache_is_cold_not_fatal(tuner_path):
    with open(tuner_path, "w") as f:
        f.write("{not json")
    t = Autotuner(tuner_path, sweep=False)
    assert t.lookup("matmul", (64, 64, 64), "float32", "interpret") is None


def test_tuned_vs_default_parity_matmul(installed_tuner, monkeypatch, rng):
    """A tuned (non-default) block plan must be USED by ops.matmul and still
    match the oracle bit-for-tolerance."""
    tuned = {"block_m": 16, "block_n": 16, "block_k": 16}
    installed_tuner.store("matmul", (40, 24, 56), "float32", "interpret", tuned)

    seen = {}
    orig = ops._matmul_k.matmul

    def spy(a, b, **kw):
        seen.update(kw)
        return orig(a, b, **kw)

    monkeypatch.setattr(ops._matmul_k, "matmul", spy)
    a = jnp.asarray(rng.standard_normal((40, 24)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((24, 56)), jnp.float32)
    out_tuned = ops.matmul(a, b, mode="interpret")
    assert (seen["block_m"], seen["block_n"], seen["block_k"]) == (16, 16, 16)
    out_default = ops.matmul(a, b, mode="interpret", block=32)
    expect = ref.matmul(a, b)
    np.testing.assert_allclose(np.asarray(out_tuned), np.asarray(expect), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out_default), np.asarray(expect), rtol=2e-4, atol=2e-4)


def test_tuned_vs_default_parity_flash(installed_tuner, monkeypatch, rng):
    b, h, s, hd = 2, 2, 48, 16
    tuned = {"block_q": 16, "block_k": 16}
    installed_tuner.store(
        "flash_attention", (b * h, s, hd), "float32", "interpret", tuned
    )

    seen = {}
    orig = ops._flash_k.flash_attention

    def spy(q, k, v, **kw):
        seen.update(kw)
        return orig(q, k, v, **kw)

    monkeypatch.setattr(ops._flash_k, "flash_attention", spy)
    q = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    out_tuned = ops.flash_attention(q, k, v, causal=True, mode="interpret")
    assert (seen["block_q"], seen["block_k"]) == (16, 16)
    out_default = ops.flash_attention(q, k, v, causal=True, mode="interpret", block=48)
    expect = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_tuned), np.asarray(expect), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out_default), np.asarray(expect), rtol=2e-4, atol=2e-4)


def test_sweep_picks_measured_winner_and_persists(tuner_path):
    t = Autotuner(tuner_path, sweep=True)
    # cost function prefers block_rows == 64
    cfg = t.get(
        "softmax", (200, 128), "float32", "interpret",
        measure=lambda c: abs(c["block_rows"] - 64),
    )
    assert cfg == {"block_rows": 64}
    t2 = Autotuner(tuner_path, sweep=False)
    assert t2.lookup("softmax", (200, 128), "float32", "interpret") == cfg


def test_env_var_cache_path(monkeypatch, tmp_path):
    p = str(tmp_path / "custom" / "cache.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", p)
    t = Autotuner()
    assert t.path == p
    t.store("dotp", (1, 4096), "float32", "interpret", {"block": 512})
    assert os.path.exists(p)


def test_default_cache_path_stays_in_checkout(monkeypatch):
    """Without ``REPRO_AUTOTUNE_CACHE`` the winners file lives in the
    checkout: no block size may come from a file under the home directory."""
    from repro.common.utils import REPO_ROOT

    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert Autotuner().path == str(REPO_ROOT / ".autotune.json")


def test_enable_compile_cache_defaults_to_repo(monkeypatch):
    """Unset ``JAX_COMPILATION_CACHE_DIR``: the entry points' cache is the
    fixed ``<repo>/.jax_cache``."""
    import jax

    from repro.common.utils import REPO_ROOT, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO_ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    """Set ``JAX_COMPILATION_CACHE_DIR``: the helper keeps JAX's setting and
    a compiled program's entry lands there (fresh process: the cache
    directory is fixed at a process's first compile)."""
    import subprocess
    import sys

    from repro.common.utils import REPO_ROOT

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=str(REPO_ROOT / "src"),
    )
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.common.utils import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path), "no cache entry was written"

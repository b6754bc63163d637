"""Block-size autotuner for the Pallas dispatch layer (``ops.py``).

The paper's lever is matching the fabric configuration to the kernel
(split/merge around the workload mix); ours is matching tile/block
configuration to (op, shape, dtype, backend) instead of paying one
hardcoded ``block=128`` for every call. The tuner:

* buckets shapes to powers of two so one sweep covers a family of nearby
  shapes (a 1000-wide matmul and a 1024-wide one share a winner),
* sweeps a per-op candidate list, timing the real kernel on synthetic
  inputs, and
* persists winners to a JSON cache so later processes hit without
  re-sweeping.

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``<repo>/.autotune.json`` (inside the checkout, never the home directory). Sweeping is opt-in via
``REPRO_AUTOTUNE=1`` (a sweep in interpret mode on CPU is expensive);
without it, a cache miss returns the per-op heuristic default and nothing
is written. Entries are keyed on a schema version — bump
``_SCHEMA_VERSION`` to invalidate every cached winner at once.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Optional

import numpy as np

_SCHEMA_VERSION = 1

Config = dict[str, int]

# Heuristic defaults: what ops.py hardcoded before the tuner existed.
DEFAULTS: dict[str, Config] = {
    "matmul": {"block_m": 128, "block_n": 128, "block_k": 128},
    "flash_attention": {"block_q": 128, "block_k": 128},
    "gqa_flash_attention": {"block_q": 128, "block_k": 128},
    "decode_attention": {"block_s": 256},
    "ragged_attention": {"block_s": 256},
    "axpy": {"block": 1024},
    "dotp": {"block": 2048},
    "softmax": {"block_rows": 128},
    "rmsnorm": {"block_rows": 128},
    "fft": {"block_rows": 64},
    "conv2d": {"block_h": 8},
}

CANDIDATES: dict[str, list[Config]] = {
    "matmul": [
        {"block_m": m, "block_n": n, "block_k": k}
        for (m, n, k) in [
            (64, 64, 64), (128, 128, 64), (128, 128, 128),
            (128, 256, 128), (256, 128, 128), (256, 256, 128),
        ]
    ],
    "flash_attention": [
        {"block_q": q, "block_k": k}
        for (q, k) in [(64, 64), (128, 128), (128, 256), (256, 128), (256, 256)]
    ],
    "gqa_flash_attention": [
        {"block_q": q, "block_k": k}
        for (q, k) in [(64, 64), (128, 128), (128, 256), (256, 128), (256, 256)]
    ],
    "decode_attention": [{"block_s": s} for s in (128, 256, 512, 1024)],
    "ragged_attention": [{"block_s": s} for s in (128, 256, 512, 1024)],
    "axpy": [{"block": b} for b in (256, 512, 1024, 2048, 4096)],
    "dotp": [{"block": b} for b in (512, 1024, 2048, 4096)],
    "softmax": [{"block_rows": r} for r in (32, 64, 128, 256)],
    "rmsnorm": [{"block_rows": r} for r in (32, 64, 128, 256)],
    "fft": [{"block_rows": r} for r in (16, 32, 64, 128)],
    "conv2d": [{"block_h": h} for h in (4, 8, 16)],
}


def bucket_dim(n: int) -> int:
    """Round a dimension up to the next power of two (floor 8)."""
    n = max(int(n), 1)
    b = 8
    while b < n:
        b *= 2
    return b


def bucket_shape(shape: tuple[int, ...]) -> tuple[int, ...]:
    return tuple(bucket_dim(d) for d in shape)


def cache_key(
    op: str, shape: tuple[int, ...], dtype: Any, backend: str,
    kv_dtype: Any = None,
) -> str:
    """Stable string key over the bucketed shape: nearby shapes collide by
    design so one sweep serves the whole bucket. ``kv_dtype`` (the KV-cache
    storage dtype, when it differs from the compute path — e.g. int8
    quantized serving) appends a ``|kv<name>`` component ONLY when present,
    so every pre-existing key string is unchanged (no schema bump)."""
    dims = "x".join(str(d) for d in bucket_shape(shape))
    key = f"v{_SCHEMA_VERSION}|{op}|{dims}|{np.dtype(dtype).name}|{backend}"
    if kv_dtype is not None:
        key += f"|kv{np.dtype(kv_dtype).name}"
    return key


def sweep_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "0") not in ("", "0", "false")


def default_cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    from repro.common.utils import REPO_ROOT

    return str(REPO_ROOT / ".autotune.json")


class Autotuner:
    """JSON-backed (op, shape-bucket, dtype, backend) -> block-config cache."""

    def __init__(self, path: Optional[str] = None, *, sweep: Optional[bool] = None):
        self.path = path or default_cache_path()
        self.sweep = sweep_enabled() if sweep is None else sweep
        self._entries: Optional[dict[str, Config]] = None
        self.sweeps_run = 0  # observability: how many sweeps this process ran

    # ------------------------------------------------------------ persistence

    def _load(self) -> dict[str, Config]:
        if self._entries is None:
            self._entries = {}
            try:
                with open(self.path) as f:
                    raw = json.load(f)
                if isinstance(raw, dict):
                    self._entries = {
                        k: v for k, v in raw.items() if isinstance(v, dict)
                    }
            except (OSError, ValueError):
                pass  # missing/corrupt cache == cold cache
        return self._entries

    def save(self) -> None:
        if self._entries is None:
            return
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self._entries, f, indent=2, sort_keys=True)
        os.replace(tmp, self.path)  # atomic vs concurrent readers

    # ----------------------------------------------------------------- lookup

    def lookup(self, op, shape, dtype, backend, kv_dtype=None) -> Optional[Config]:
        return self._load().get(cache_key(op, shape, dtype, backend, kv_dtype))

    def store(self, op, shape, dtype, backend, config: Config, kv_dtype=None) -> None:
        self._load()[cache_key(op, shape, dtype, backend, kv_dtype)] = dict(config)
        self.save()

    def get(
        self,
        op: str,
        shape: tuple[int, ...],
        dtype: Any,
        backend: str,
        measure: Optional[Callable[[Config], float]] = None,
        kv_dtype: Any = None,
    ) -> Config:
        """Cached winner, or (if sweeping is enabled) sweep-measure-persist,
        or the heuristic default. ``measure`` maps a candidate config to a
        wall-clock cost; ``None`` disables sweeping for this call."""
        hit = self.lookup(op, shape, dtype, backend, kv_dtype)
        if hit is not None:
            return dict(hit)  # copy: callers must not mutate the cache
        if not self.sweep or measure is None:
            return dict(DEFAULTS[op])
        best_cfg, best_t = None, float("inf")
        for cfg in CANDIDATES.get(op, [DEFAULTS[op]]):
            try:
                t = measure(cfg)
            except Exception:
                continue  # candidate invalid for this shape/backend
            if t < best_t:
                best_cfg, best_t = cfg, t
        if best_cfg is None:
            best_cfg = dict(DEFAULTS[op])
        self.sweeps_run += 1
        self.store(op, shape, dtype, backend, best_cfg, kv_dtype)
        return dict(best_cfg)


# ---------------------------------------------------------------------------
# Synthetic-input measure functions (used by ops.py when sweeping is on).
# They import the kernel modules directly — never ops.py — so there is no
# import cycle, and they time the compiled kernel exactly as dispatched.
# ---------------------------------------------------------------------------


def _time_best(thunk: Callable[[], Any], repeats: int = 3) -> float:
    import jax

    jax.block_until_ready(thunk())  # compile + warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        best = min(best, time.perf_counter() - t0)
    return best


def measure_matmul(shape, dtype, backend) -> Callable[[Config], float]:
    import jax.numpy as jnp

    from repro.kernels import matmul as _k

    m, k, n = bucket_shape(shape)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    interp = backend == "interpret"

    def run(cfg: Config) -> float:
        return _time_best(
            lambda: _k.matmul(
                a, b, block_m=cfg["block_m"], block_n=cfg["block_n"],
                block_k=cfg["block_k"], interpret=interp,
            )
        )

    return run


def measure_flash_attention(shape, dtype, backend) -> Callable[[Config], float]:
    import jax.numpy as jnp

    from repro.kernels import flash_attention as _k

    bh, s, d = bucket_shape(shape)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((bh, s, d)), dtype)
    kv = jnp.asarray(rng.standard_normal((bh, s, d)), dtype)
    interp = backend == "interpret"

    def run(cfg: Config) -> float:
        return _time_best(
            lambda: _k.flash_attention(
                q, kv, kv, causal=True, block_q=cfg["block_q"],
                block_k=cfg["block_k"], interpret=interp,
            )
        )

    return run


MEASURES: dict[str, Callable[..., Callable[[Config], float]]] = {
    "matmul": measure_matmul,
    "flash_attention": measure_flash_attention,
}


def measure_for(op: str, shape, dtype, backend):
    """Measure-closure factory, or None when the op has no sweep runner."""
    fn = MEASURES.get(op)
    if fn is None:
        return None
    return fn(shape, dtype, backend)


# ---------------------------------------------------------------------------
# Process-global tuner (what ops.py consults). ``generation`` feeds the
# plan memoizer in ops.py so swapping tuners invalidates memoized plans.
# ---------------------------------------------------------------------------

_tuner: Optional[Autotuner] = None
_generation = 0


def get_tuner() -> Autotuner:
    global _tuner
    if _tuner is None:
        _tuner = Autotuner()
    return _tuner


def set_tuner(tuner: Optional[Autotuner]) -> None:
    """Install a tuner (tests point this at a tmp cache); None resets to the
    env-configured default on next use."""
    global _tuner, _generation
    _tuner = tuner
    _generation += 1


def generation() -> int:
    return _generation

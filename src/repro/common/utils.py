"""Small shared utilities: timing, humanized units, pytree accounting, and
the persistent compilation cache of the entry points."""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any

import jax
import numpy as np

# the checkout root (``src/repro/common/utils.py`` -> three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this sets nothing. Otherwise the cache lives at ``<repo>/.jax_cache``:
    a fixed path, because the path is part of the cache key — a directory
    that moves never hits. Entry points call this (``launch/serve.py``,
    ``chip_smoke.py``); importing the package never does, so tests and
    library users keep JAX's own setting."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Timer:
    """Wall-clock timer usable as a context manager.

    >>> with Timer() as t:
    ...     work()
    >>> t.elapsed  # seconds
    """

    def __init__(self) -> None:
        self.elapsed = 0.0
        self._t0 = 0.0

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.elapsed = time.perf_counter() - self._t0


def _humanize(x: float, units: list[str], base: float = 1000.0) -> str:
    for unit in units:
        if abs(x) < base:
            return f"{x:.3g}{unit}"
        x /= base
    return f"{x:.3g}{units[-1]}"


def human_num(x: float) -> str:
    return _humanize(float(x), ["", "K", "M", "B", "T", "P"])


def human_bytes(x: float) -> str:
    return _humanize(float(x), ["B", "KiB", "MiB", "GiB", "TiB", "PiB"], base=1024.0)


def human_flops(x: float) -> str:
    return _humanize(float(x), ["F", "KF", "MF", "GF", "TF", "PF", "EF"])


def pytree_num_params(tree: Any) -> int:
    """Total number of elements across all leaves (works on ShapeDtypeStruct too)."""
    leaves = jax.tree.leaves(tree)
    return sum(int(np.prod(leaf.shape)) for leaf in leaves)


def pytree_bytes(tree: Any) -> int:
    """Total bytes across all leaves (works on ShapeDtypeStruct too)."""
    leaves = jax.tree.leaves(tree)
    return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in leaves)


def tree_struct_str(tree: Any, max_leaves: int = 40) -> str:
    """Debug rendering of a pytree's leaf shapes/dtypes."""
    lines = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat[:max_leaves]:
        name = jax.tree_util.keystr(path)
        lines.append(f"  {name}: {tuple(leaf.shape)} {leaf.dtype}")
    if len(flat) > max_leaves:
        lines.append(f"  ... ({len(flat) - max_leaves} more leaves)")
    return "\n".join(lines)


def now_ms() -> float:
    return time.perf_counter() * 1e3

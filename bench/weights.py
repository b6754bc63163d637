"""Seeded weights for a benchmark run, made on the device from ``--seed``.

Every leaf of the served parameter tree is cut into blocks along its
leading axis (one layer of a stacked ``[L, ...]`` leaf, or 1024 rows of a
plain one) and each block is drawn from its own key,
``fold_in(fold_in(seed_key, crc32(path)), block)``. The draw is exact
integer arithmetic up to one scaling multiply, so the plain reference can
regenerate any single layer (``layer_leaf``) bit for bit without holding
the whole model and without taking anything the program made.

A leaf is stacked where its top-level subtree's name ends in ``blocks``
(``blocks``, and an expert model's ``dense_blocks`` and ``moe_blocks``);
its block ``l`` is the subtree's layer ``l``.

Values are uniform with the standard deviation of a ``1/sqrt(fan_in)``
initialisation, the fan-in of an expert stack ``[E, fan_in, ...]`` (a
``moe`` subtree's ``w_in``, ``w_gate``, ``w_out``) taken after its expert
axis; the token embedding has std 0.02 (the usual initializer
range), so that a tied output head does not make every position predict
its own input token. Norm scales are drawn around 1 so that a path which
ignores them cannot agree with the reference.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

ROWS_PER_BLOCK = 1024
STACKED_SUFFIX = "blocks"  # top-level subtrees whose leaves carry a layer axis
EXPERT_LEAVES = ("w_in", "w_gate", "w_out")  # under ``moe``: [E, fan_in, ...]


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (the driver's exceed 32 bits)."""
    k = jax.random.key(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _uniform(key, shape) -> jax.Array:
    """Uniform on (-1, 1) with 2^-23 resolution, in exact f32 arithmetic."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    u = (bits >> 9).astype(jnp.float32)
    return (u + 0.5) * (2.0**-22) - 1.0


def _scale(path: str, block_shape: tuple[int, ...]) -> tuple[float, float]:
    """(offset, half-width) of the uniform draw for the leaf at ``path``."""
    parts = path.split("/")
    name = parts[-1]
    if parts[-2:-1] == ["moe"] and name in EXPERT_LEAVES:
        block_shape = block_shape[1:]  # one expert's matrix
    if "norm" in name:
        return 1.0, 0.25
    if name == "tok":
        std = 0.02
    elif name == "wo":  # [H, hd, d]: contracts heads and head dim
        std = (block_shape[0] * block_shape[1]) ** -0.5
    else:  # [fan_in, ...]
        std = block_shape[0] ** -0.5
    return 0.0, std * math.sqrt(3.0)


def _block(key, path, block_shape, dtype, scale_shape=None) -> jax.Array:
    """One block of the leaf at ``path``; ``scale_shape`` is the shape whose
    fan-in sets the scale (the block's own for a layer, the whole leaf for
    rows)."""
    off, half = _scale(path, scale_shape or block_shape)
    x = _uniform(key, block_shape) * half
    if off:
        x = x + off
    return x.astype(dtype)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _leaf_key(skey, path: str) -> jax.Array:
    return jax.random.fold_in(skey, zlib.crc32(path.encode()))


def stacked(path: str) -> bool:
    """Whether the leaf at ``path`` carries a leading layer axis."""
    return path.split("/", 1)[0].endswith(STACKED_SUFFIX)


def _make_leaf(skey, path: str, shape, dtype) -> jax.Array:
    key = _leaf_key(skey, path)
    if stacked(path):
        block_shape = tuple(shape[1:])
        return jax.lax.map(
            lambda i: _block(jax.random.fold_in(key, i), path, block_shape, dtype),
            jnp.arange(shape[0]),
        )
    if len(shape) < 2:
        return _block(jax.random.fold_in(key, 0), path, tuple(shape), dtype)
    n = -(-shape[0] // ROWS_PER_BLOCK)
    block_shape = (ROWS_PER_BLOCK, *shape[1:])
    rows = jax.lax.map(
        lambda i: _block(jax.random.fold_in(key, i), path, block_shape, dtype,
                         scale_shape=tuple(shape)),
        jnp.arange(n),
    )
    return rows.reshape(n * ROWS_PER_BLOCK, *shape[1:])[: shape[0]]


def build(param_specs, seed: int, out_shardings=None):
    """The whole parameter tree of ``param_specs`` (ShapeDtypeStructs, the
    program's layout) in its served dtype, made in one jitted call."""

    def make(skey):  # the seed is an argument: one program serves every seed
        return jax.tree_util.tree_map_with_path(
            lambda p, s: _make_leaf(skey, _path_str(p), s.shape, s.dtype),
            param_specs,
        )

    return jax.jit(make, out_shardings=out_shardings)(seed_key(seed))


@functools.partial(jax.jit, static_argnames=("path", "shape", "dtype"))
def _layer(skey, i, *, path, shape, dtype):
    key = jax.random.fold_in(_leaf_key(skey, path), i)
    return _block(key, path, shape, dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("path", "shape", "dtype"))
def _plain(skey, *, path, shape, dtype):
    return _make_leaf(skey, path, shape, dtype).astype(jnp.float32)


def layer_leaf(seed: int, path: str, layer: int, shape, dtype="bfloat16"):
    """Layer ``layer`` of the stacked leaf at ``path`` (e.g.
    ``"blocks/attn/wq"``, ``"moe_blocks/moe/router"``) as served in the
    leaf's own ``dtype``, upcast to f32; ``shape`` is one layer's shape.
    Bit-identical to ``build(...)[path][layer]``."""
    return _layer(seed_key(seed), jnp.int32(layer), path=path,
                  shape=tuple(shape), dtype=jnp.dtype(dtype))


def plain_leaf(seed: int, path: str, shape, dtype="bfloat16"):
    """An unstacked leaf (embedding, head, final norm) as served, in f32."""
    return _plain(seed_key(seed), path=path, shape=tuple(shape),
                  dtype=jnp.dtype(dtype))

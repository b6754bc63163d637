"""Rule-based sharding: axis roles over a mesh + path/shape partition rules.

Two layers:

* :class:`MeshInfo` — a mesh plus axis ROLES. The same physical fabric is
  viewed differently per Spatzformer mode: MERGE folds the ``pod`` axis into
  the batch axes (one fused data-parallel fabric), SPLIT hands each pod its
  own standalone ``(data, model)`` view. ``tp_enabled=False`` additionally
  demotes the ``model`` axis to a batch axis (the DP+ZeRO strategies in
  ``launch/dryrun.py``).
* ``spec_for_param`` and friends — pure partition rules keyed on a leaf's
  pytree path and shape, shared by params, optimizer state and batches so a
  reshard between any two :class:`MeshInfo` views is always well-defined.

Hard-won rules pinned by ``tests/test_sharding_rules.py``:

* a stacked-layer leading dim (ndim ≥ 3) is NEVER sharded — the scan over
  layers would otherwise all-gather the full stack every step (the 6×7 GB
  regression caught in the dry-run artifact);
* MoE expert stacks ``[L, E, d, f]`` shard the EXPERT dim (expert
  parallelism feeds the ``shard_map`` in :mod:`repro.models.moe`);
* GQA attention ``[L, d, kv_heads, head_dim]`` prefers the heads dim and
  falls back to head_dim when ``kv_heads`` isn't divisible (kv=8 on TP-16);
* embeddings prefer the vocab dim, falling back to d_model for odd vocabs
  (minicpm3's 73448);
* ``model_size == 1`` replicates everything.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

MODEL_AXIS = "model"

# Leaves below this element count replicate (norm gains, biases, scalars):
# sharding them saves nothing and invites involuntary gathers in scanned
# stacks. Kept well under any weight matrix of the assigned archs.
MIN_SHARD_ELEMS = 2**16

# FSDP second-dim sharding kicks in above this leaf element count by default
# (callers tune it down for optimizer state, e.g. dryrun's 2**22).
DEFAULT_FSDP_THRESHOLD = 2**24


# =============================================================================
# MeshInfo: a mesh plus axis roles
# =============================================================================


@dataclass(frozen=True)
class MeshInfo:
    """A device mesh annotated with which axes carry the batch and whether
    tensor parallelism over the ``model`` axis is active.

    ``batch_axes`` may include ``"model"`` (with ``tp_enabled=False``) for the
    DP+ZeRO strategies: the model axis then counts toward ``data_size`` and
    ``model_size`` reports 1.
    """

    mesh: Mesh
    batch_axes: tuple[str, ...] = ("data",)
    tp_enabled: bool = True

    # ------------------------------------------------------------------ sizes

    @property
    def n_devices(self) -> int:
        return int(self.mesh.size)

    @property
    def data_size(self) -> int:
        """Total data-parallel degree: product of the batch axes' sizes."""
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes], dtype=np.int64))

    @property
    def model_axis(self) -> Optional[str]:
        """The tensor-parallel axis name, or None when TP is off."""
        if not self.tp_enabled:
            return None
        if MODEL_AXIS not in self.mesh.axis_names or MODEL_AXIS in self.batch_axes:
            return None
        return MODEL_AXIS

    @property
    def model_size(self) -> int:
        ax = self.model_axis
        return int(self.mesh.shape[ax]) if ax is not None else 1

    # ------------------------------------------------------------------ specs

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constraint(self, x: jax.Array, spec: P) -> jax.Array:
        return jax.lax.with_sharding_constraint(x, self.named(spec))

    def batch_spec(self, ndim: int) -> P:
        """P with the batch axes on dim 0 and the rest replicated."""
        return P(self.batch_axes, *([None] * (ndim - 1)))


def single_device_mesh_info() -> MeshInfo:
    """Degenerate 1-device ``(data, model)`` view — the fallback fabric when
    ``len(jax.devices()) == 1`` (laptops, the fast CI lane)."""
    grid = np.array(jax.devices()[:1]).reshape(1, 1)
    return MeshInfo(Mesh(grid, ("data", MODEL_AXIS)), batch_axes=("data",))


def serving_mesh_info(devices: Optional[Any] = None) -> MeshInfo:
    """Merged serving fabric: ONE ``(data=1, model=N)`` view over the given
    devices — the whole cluster becomes a single tensor-parallel engine
    (the serving analogue of Spatzformer's merge mode: one controller, all
    lanes fused). Degenerates gracefully to the single-device view."""
    devs = list(devices) if devices is not None else list(jax.devices())
    grid = np.array(devs).reshape(1, len(devs))
    return MeshInfo(Mesh(grid, ("data", MODEL_AXIS)), batch_axes=("data",))


# =============================================================================
# partition rules
# =============================================================================


def _divisible(dim: int, by: int) -> bool:
    return dim >= by and dim % by == 0


def spec_for_param(path: str, ndim: int, shape: tuple[int, ...], model_size: int) -> P:
    """Tensor-parallel PartitionSpec for one parameter leaf.

    ``path`` is the ``jax.tree_util.keystr`` rendering of the leaf's pytree
    path (e.g. ``"['blocks']['attn']['wk']"``); rules key on substrings so the
    same rules apply when the tree is nested under optimizer-state prefixes.
    """
    if model_size <= 1 or ndim == 0:
        return P()
    parts: list[Any] = [None] * ndim
    # stacked-layer stacks [L, ...]: dim 0 is scanned over, never sharded
    first = 1 if ndim >= 3 else 0

    # MoE expert stacks [L, E, d, f]: expert parallelism on the expert dim.
    # Matched on the exact `['moe']` segment — attention params under
    # `moe_blocks` must NOT take this branch (their dim 1 is d_model, which
    # always divides TP and would defeat the heads/head_dim rule below).
    # The shared expert nested under the moe subtree is a plain MLP and
    # falls through to the generic rule.
    if "['moe']" in path and "shared" not in path and ndim == 4:
        if _divisible(shape[1], model_size):
            parts[1] = MODEL_AXIS
            return P(*parts)

    # Attention projections [L, d, (kv_)heads, head_dim]: heads first (clean
    # head parallelism), head_dim as the GQA fallback (kv_heads < TP degree).
    if "attn" in path and ndim == 4:
        for dim in (2, 3):
            if _divisible(shape[dim], model_size):
                parts[dim] = MODEL_AXIS
                return P(*parts)

    # Generic rule: the largest shardable dim wins. Vocab→d_model fallback
    # for embeddings falls out of this (prefer the bigger vocab dim when it
    # divides, else d_model).
    for dim in sorted(range(first, ndim), key=lambda d: shape[d], reverse=True):
        if _divisible(shape[dim], model_size):
            parts[dim] = MODEL_AXIS
            return P(*parts)
    return P()


def _add_fsdp_dim(
    spec: P,
    shape: tuple[int, ...],
    info: MeshInfo,
    data_size: int,
    threshold: int = DEFAULT_FSDP_THRESHOLD,
) -> P:
    """ZeRO/FSDP second-dim sharding: put the batch axes on the largest free
    dim of a big leaf. The stacked-layer dim 0 (ndim ≥ 3) is never eligible —
    same regression guard as :func:`spec_for_param`."""
    ndim = len(shape)
    if ndim == 0 or math.prod(shape) < threshold:
        return spec
    parts: list[Any] = list(spec) + [None] * (ndim - len(spec))
    first = 1 if ndim >= 3 else 0
    candidates = [
        d
        for d in range(first, ndim)
        if parts[d] is None and _divisible(shape[d], max(data_size, 1))
    ]
    if not candidates:
        return spec
    best = max(candidates, key=lambda d: shape[d])
    parts[best] = info.batch_axes
    return P(*parts)


def spec_for_batch(shape: tuple[int, ...], data_size: int, batch_axes: tuple[str, ...]) -> P:
    """Batch-leaf spec: shard dim 0 over the batch axes when divisible,
    replicate otherwise (odd global batches, scalars)."""
    if not shape or data_size <= 0 or not _divisible(shape[0], max(data_size, 1)):
        return P()
    return P(batch_axes, *([None] * (len(shape) - 1)))


# =============================================================================
# pytree builders
# =============================================================================


def param_shardings(
    tree: Any,
    info: MeshInfo,
    *,
    fsdp: bool = False,
    fsdp_threshold: int = DEFAULT_FSDP_THRESHOLD,
) -> Any:
    """NamedSharding pytree for params (or anything param-shaped: grads,
    optimizer moments, EF residuals). Pass ``fsdp=True`` to additionally
    shard big leaves over the batch axes (ZeRO-style)."""
    model_size = info.model_size

    def one(path, leaf):
        shape = tuple(leaf.shape)
        if len(shape) == 0 or math.prod(shape) < MIN_SHARD_ELEMS:
            spec = P()
        else:
            spec = spec_for_param(
                jax.tree_util.keystr(path), len(shape), shape, model_size
            )
        if fsdp:
            spec = _add_fsdp_dim(spec, shape, info, info.data_size, fsdp_threshold)
        return info.named(spec)

    return jax.tree_util.tree_map_with_path(one, tree)


def opt_shardings(opt_tree: Any, info: MeshInfo, **kwargs: Any) -> Any:
    """Optimizer-state shardings: moments mirror their parameter's spec (the
    rules key on path substrings, so the param subtree nested inside the
    AdamW state resolves identically); scalar ``step`` replicates."""
    return param_shardings(opt_tree, info, **kwargs)


def batch_shardings(tree: Any, info: MeshInfo) -> Any:
    """NamedSharding pytree for a data batch: leading dim over the batch
    axes, replicated fallback when the batch doesn't divide ``data_size``."""
    data_size = info.data_size
    return jax.tree.map(
        lambda leaf: info.named(
            spec_for_batch(tuple(leaf.shape), data_size, info.batch_axes)
        ),
        tree,
    )


def replicated(info: MeshInfo) -> NamedSharding:
    """Fully-replicated sharding on this view (scalars, metrics)."""
    return info.named(P())


# =============================================================================
# serving shardings (merge-mode tensor-parallel engine)
# =============================================================================


# positional cache leaves (see LM.init_cache): the head-major attention K/V
# ``[L, B, KV, S, hd]`` (and the paged pool ``[L, NB, KV, bs, hd]``), the
# hybrid shared-block K/V, and the quantized cache's per-row scales
# ``[L, B, KV, S]`` — their KV dim shards with the payload's KV heads (a
# replicated scale fallback still broadcasts cleanly against a
# head_dim-sharded payload); and the MLA latent/rope caches
# ``[L, B, S, rank]``. Everything else is recurrent state with no
# positional axis.
HEAD_MAJOR_CACHE_KEYS = frozenset(
    {"k", "v", "k_scale", "v_scale", "attn_k", "attn_v"}
)
_LATENT_CACHE_KEYS = frozenset({"ckv", "krope"})


def serve_cache_shardings(cache_shape: Any, info: MeshInfo) -> Any:
    """KV-cache placement for the SERVING slot pool — head-major
    ``[L, B_slots, KV, S_max, hd]`` / MLA ``[L, B_slots, S_max, rank]``
    leaves plus SSM state ``[L, B_slots, ...]``.

    Differs from training-time ``LM.cache_shardings`` on purpose: the
    serving engine scatters single rows at arbitrary ``(slot, pos)`` every
    tick, so the slot (B) and sequence (S) dims are NEVER sharded — a
    model-axis split of either would turn every O(1) cache write into a
    cross-shard exchange. Head-major K/V leaves partition their KV heads
    (dim 2: clean head parallelism, matching ``spec_for_param``'s attention
    rule and the per-head ``shard_map`` the attention kernels run under),
    head_dim as the fallback; MLA leaves their latent rank. Recurrent SSM
    leaves take their widest trailing dim ≥ dim 2. The layer stack dim 0 is
    never sharded.
    """
    ms = info.model_size

    def leaf_spec(path, leaf):
        parts: list[Any] = [None] * leaf.ndim
        if ms > 1 and leaf.ndim >= 2:
            name = getattr(path[-1], "key", None) if path else None
            if name in HEAD_MAJOR_CACHE_KEYS:
                # [L, B, KV, S(, hd)]: heads first, head_dim on 5-D payloads
                order = [2] + ([4] if leaf.ndim == 5 else [])
            elif name in _LATENT_CACHE_KEYS:
                order = [leaf.ndim - 1]  # [L, B, S, rank]: the rank
            else:
                # SSM conv/recurrent state [L, B, ...]: widest trailing dim
                order = sorted(
                    range(2, leaf.ndim), key=lambda d: leaf.shape[d], reverse=True
                )
            for d in order:
                if _divisible(leaf.shape[d], ms):
                    parts[d] = MODEL_AXIS
                    break
        return info.named(P(*parts))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_shape)


def serve_state_shardings(tree: Any, info: MeshInfo) -> Any:
    """Per-slot engine state (last tokens, cur_len, override lanes, PRNG
    key): pure control state, replicated on every shard — the merged
    fabric runs under one controller, so every device sees the identical
    slot bookkeeping."""
    return jax.tree.map(lambda _: replicated(info), tree)

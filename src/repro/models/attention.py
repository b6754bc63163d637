"""Attention: GQA/MHA with dense and memory-efficient (chunked online-softmax)
implementations, qk-norm, RoPE, sliding windows, and a KV-cache decode path.

All three implementations are GQA-native: K/V stay at ``n_kv_heads`` and the
query heads are grouped ``[B, S, KV, G, hd]`` inside the einsums, so the
``H//KV``-fold K/V expansion (`_repeat_kv`) is never materialized. The
chunked implementation is the CPU/XLA analogue of the Pallas flash-attention
kernel (``repro.kernels.ops.gqa_flash_attention``, which takes over on TPU):
it never materializes the full S×S score matrix — it scans KV blocks
carrying the online (max, sum, acc) triple. Decode dispatches through
``repro.kernels.ops.decode_attention`` (grouped oracle on CPU, the batched
Pallas decode kernel on TPU).

KV caches are head-major: ``[B, KV, S_max, hd]`` per layer (and
``[num_blocks, KV, block_size, hd]`` for the paged pool), so every kernel
tile is a 2-D ``(positions, head_dim)`` slab in the TPU's tiled minor dims.
Under merge-mode tensor parallelism the attention kernels run per KV-head
shard inside a ``shard_map`` (:func:`_per_head`), never on gathered K/V.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.compression import quantize_rows
from repro.kernels import ops
from repro.models.layers import Params, apply_rope, dense_init, rms_norm
from repro.models.quant import qweight

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attention_init(key, cfg: ArchConfig, dtype) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(k1, (d, H, hd), dtype),
        "wk": dense_init(k2, (d, KV, hd), dtype),
        "wv": dense_init(k3, (d, KV, hd), dtype),
        "wo": dense_init(k4, (H, hd, d), dtype, fan_in=H * hd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


# ---------------------------------------------------------------------------
# Score-level primitives
# ---------------------------------------------------------------------------


def _repeat_kv(x: jax.Array, groups: int) -> jax.Array:
    """[B, S, KV, hd] -> [B, S, KV*groups, hd] by head repetition.

    Kept as a reference utility (and for external callers); the attention
    paths below are GQA-native and never call it.
    """
    if groups == 1:
        return x
    b, s, kv, hd = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, groups, hd))
    return x.reshape(b, s, kv * groups, hd)


def _head_constraint(x: jax.Array, mesh_info, head_axis: int) -> jax.Array:
    """Pin ``x``'s head dim onto the tensor-parallel ``model`` axis.

    The serving cluster's merge mode shards attention head-parallel: q/k/v
    projections and the KV cache split on their (kv_)head dim, with
    head_dim as the GQA fallback when the head count doesn't divide the TP
    degree — the same preference order as ``spec_for_param`` /
    ``serve_cache_shardings``, so constraining here never fights the
    placement the params and cache arrived with. No-op off-mesh.
    """
    if mesh_info is None or mesh_info.model_size <= 1:
        return x
    from jax.sharding import PartitionSpec as P

    ms = mesh_info.model_size
    for ax in (head_axis, x.ndim - 1):
        if x.shape[ax] % ms == 0 and x.shape[ax] >= ms:
            parts: list = [None] * x.ndim
            parts[ax] = "model"
            return mesh_info.constraint(x, P(*parts))
    return x


def _group_q(q: jax.Array, kv_heads: int) -> jax.Array:
    """[B, Sq, H, hd] -> [B, Sq, KV, G, hd] (head-major grouping: query head
    h belongs to KV head h // G)."""
    b, sq, h, hd = q.shape
    assert h % kv_heads == 0, (h, kv_heads)
    return q.reshape(b, sq, kv_heads, h // kv_heads, hd)


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    window: int = 0,
) -> jax.Array:
    """Reference attention materializing the full score matrix.

    q: [B, Sq, H, hd], k/v: [B, Sk, KV, hd] with H % KV == 0 (KV == H is
    plain MHA). The group dim lives inside the einsum — no K/V repetition.
    q_offset: absolute position of q[0] (for causal masking vs a longer k).
    """
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    scale = hd**-0.5
    if kvh == h:  # MHA: flat 4-D einsums (cheaper to compile/lower than 5-D)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    else:
        qg = _group_q(q, kvh)  # [B,Sq,KV,G,hd]
        scores = (
            jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
        )  # [B,KV,G,Sq,Sk]
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    mask = mask[None, None] if kvh == h else mask[None, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if kvh == h:
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, hd)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    chunk: int = 512,
    q_offset: int | jax.Array = 0,
    window: int = 0,
) -> jax.Array:
    """Flash-style online-softmax attention scanning KV chunks.

    q: [B, Sq, H, hd], k/v: [B, Sk, KV, hd] with H % KV == 0. Never
    materializes [Sq, Sk]; per-step footprint is [B, KV, G, Sq, chunk].
    Matches :func:`dense_attention` to fp tolerance.
    """
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    grouped = kvh != h  # GQA: group dim inside the einsums, no K/V repeat
    if sk % chunk:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pad_mask = True
    else:
        pad_mask = False
    skp = k.shape[1]
    n_chunks = skp // chunk
    scale = hd**-0.5

    kc = k.reshape(b, n_chunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)

    qpos = jnp.arange(sq) + q_offset  # [Sq]
    if grouped:
        qf = _group_q(q, kvh).astype(jnp.float32)  # [B,Sq,KV,G,hd]
        qk, pv = "bqkgd,bckd->bkgqc", "bkgqc,bckd->bkgqd"
        head_shape = (b, kvh, g)
    else:  # MHA: flat 4-D einsums (cheaper to compile/lower than 5-D)
        qf = q.astype(jnp.float32)
        qk, pv = "bqhd,bchd->bhqc", "bhqc,bchd->bhqd"
        head_shape = (b, h)
    n_mask_dims = len(head_shape)  # leading broadcast dims for the [Sq,c] mask

    @jax.checkpoint
    def body(carry, xs):
        # rematted: the [B,heads...,Sq,chunk] probability block is recomputed
        # in backward rather than saved per KV chunk
        m, l, acc = carry  # [*head,Sq], [*head,Sq], [*head,Sq,hd]
        kci, vci, ci = xs  # [B,chunk,KV,hd] x2, scalar chunk index
        scores = jnp.einsum(qk, qf, kci.astype(jnp.float32)) * scale
        kpos = ci * chunk + jnp.arange(chunk)  # [chunk]
        mask = jnp.ones((sq, chunk), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        if pad_mask:
            mask &= (kpos < sk)[None, :]
        scores = jnp.where(
            mask.reshape((1,) * (n_mask_dims - 1) + (1, sq, chunk)), scores, NEG_INF
        )
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            pv, p, vci.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((*head_shape, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((*head_shape, sq), jnp.float32)
    acc0 = jnp.zeros((*head_shape, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kc, vc, jnp.arange(n_chunks))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [*head,Sq,hd]
    if grouped:
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    else:
        out = out.transpose(0, 2, 1, 3)
    return out.astype(q.dtype)  # [B,Sq,H,hd]


# ---------------------------------------------------------------------------
# Full block-level forward (projections + rope + attention)
# ---------------------------------------------------------------------------


def attention_apply(
    params: Params,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    impl: Optional[str] = None,
    return_kv: bool = False,
    mesh_info=None,
):
    """Self-attention over a full sequence (train / prefill).

    x: [B, S, d]; positions: [S] or [B, S]. With ``return_kv`` also returns
    the post-rope (k, v) head-major [B,KV,S,hd] — exactly the decode cache
    layout, enabling prefill-into-cache. On TPU the full-attention
    window-free case routes to the GQA-native Pallas flash kernel — per
    KV-head shard under merge-mode ``mesh_info`` (:func:`_per_head`).
    """
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    impl = impl or cfg.attn_impl

    q = jnp.einsum("bsd,dhk->bshk", x, qweight(params["wq"], x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, qweight(params["wk"], x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, qweight(params["wv"], x.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if positions.ndim == 1:
        positions = positions[None]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # head-major [B,KV,S,hd]: the cache layout and the flash kernel's input
    kv_cache = (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)) if return_kv else None

    # Pallas GQA flash kernel on TPU, but only on the prefill path
    # (return_kv=True): pallas_call has no VJP, so the training forward
    # (which jax.grad traverses) must stay on the XLA implementations.
    if (
        return_kv
        and jax.default_backend() == "tpu"
        and not cfg.sliding_window
    ):
        # [B,S,H,hd] -> [B,H,S,d] for the Pallas GQA kernel
        o = _per_head(
            mesh_info,
            functools.partial(ops.gqa_flash_attention, causal=True),
            dict(q=q.transpose(0, 2, 1, 3), k=kv_cache[0], v=kv_cache[1]),
            {},
        ).transpose(0, 2, 1, 3)
    elif impl == "dense":
        o = dense_attention(q, k, v, causal=True, window=cfg.sliding_window)
    else:
        o = chunked_attention(
            q, k, v, causal=True, chunk=cfg.attn_chunk, window=cfg.sliding_window
        )
    out = jnp.einsum("bshk,hkd->bsd", o, qweight(params["wo"], o.dtype))
    if return_kv:
        return out, kv_cache
    return out


# ---------------------------------------------------------------------------
# Decode path with KV cache
# ---------------------------------------------------------------------------


def attention_decode(
    params: Params,
    cfg: ArchConfig,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cur_len: jax.Array,
    mesh_info=None,
    block_tables: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
):
    """One decode step.

    x: [B, 1, d]; cache_k/v: [B, KV, S_max, hd]; cur_len: [] or [B] tokens
    already in the cache. Returns (out [B,1,d], new_k, new_v). With
    ``layer`` (int32 scalar) the cache arguments are instead the whole
    stacked ``[L, B, KV, S_max, hd]`` cache (scales ``[L, B, KV, S_max]``)
    that the decode layer loop carries: the new row lands at
    ``(layer, slot, :, cur_len)`` in place and the attention kernel reads
    that layer straight out of the stack, so no per-layer slice of the
    cache is ever cut or written back. With
    ``mesh_info`` the step runs head-sharded over the ``model`` axis
    (merge-mode serving): q and the KV cache split on their head dims, the
    per-shard partial outputs of the ``wo`` contraction all-reduce.

    With ``block_tables`` ([B, max_blocks] int32) the cache arguments are
    instead a block-paged pool ``[num_blocks, KV, block_size, hd]``
    (:mod:`repro.serve.kv_pool`): the new K/V scatter lands at the
    sequence's ``(block, offset)`` for position ``cur_len`` (an
    unallocated-sentinel table entry drops the write — inert slots never
    touch another request's blocks), and attention dispatches through
    ``ops.paged_decode_attention``, whose CPU path is bit-identical to the
    dense gather.

    With ``k_scale``/``v_scale`` ([B, KV, S_max] — or [num_blocks, KV,
    block_size] paged — f32) the cache stores quantized rows: the new
    token's K/V quantize per-(position, head) row at insert (O(written
    rows), never a cache-sized requant), the scales scatter alongside the
    payloads, and attention dequantizes inside the kernel. Returns a
    5-tuple (out, new_k, new_v, new_k_scale, new_v_scale) in that case.
    When the cache dtype is f32 the rows store verbatim with scale 1.0 —
    bit-identical outputs to the unscaled path.
    """
    b, _, d = x.shape
    quant = k_scale is not None
    kv_axis = 1 if layer is None else 2  # the cache's KV-head axis

    with jax.named_scope("attn_in"):
        q = jnp.einsum("bsd,dhk->bshk", x, qweight(params["wq"], x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, qweight(params["wk"], x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, qweight(params["wv"], x.dtype))
        q = _head_constraint(q, mesh_info, 2)
        k = _head_constraint(k, mesh_info, 2)
        v = _head_constraint(v, mesh_info, 2)
        cache_k = _head_constraint(cache_k, mesh_info, kv_axis)
        cache_v = _head_constraint(cache_v, mesh_info, kv_axis)
        if cfg.qk_norm:
            q = rms_norm(q, params["q_norm"], cfg.norm_eps)
            k = rms_norm(k, params["k_norm"], cfg.norm_eps)
        pos = jnp.broadcast_to(jnp.asarray(cur_len), (b,))[:, None]  # [B,1]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)

        if quant:
            # per-(position, head)-row quantization of the freshly projected
            # K/V — identity (payload, ones) when the cache stores f32
            k, ks = quantize_rows(k, cache_k.dtype)
            v, vs = quantize_rows(v, cache_v.dtype)

    if block_tables is None:
        with jax.named_scope("kv_write"):
            # scatter the new k/v at cur_len on the head-major position axis
            kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            cache_k = _scatter_step(cache_k, kt, cur_len, 2, layer)
            cache_v = _scatter_step(cache_v, vt, cur_len, 2, layer)
            if quant:
                kst, vst = ks.transpose(0, 2, 1), vs.transpose(0, 2, 1)
                k_scale = _scatter_step(k_scale, kst, cur_len, 2, layer)
                v_scale = _scatter_step(v_scale, vst, cur_len, 2, layer)

        # grouped decode attention: never expands the cache to H heads
        # (materializing [B,S,H,hd] per layer is a groups× transient blowup
        # at 32k context); cache may be int8/f8 storage — the kernel widens
        # per-tile in-register, so no dequantized cache copy exists
        with jax.named_scope("attention"):
            o = _per_head(
                mesh_info,
                functools.partial(ops.decode_attention, window=cfg.sliding_window),
                dict(q=q[:, 0], k=cache_k, v=cache_v, k_scale=k_scale, v_scale=v_scale),
                dict(cur_len=cur_len, layer=layer),
                kv_axis=kv_axis,
            )[:, None]  # [B,1,H,hd]
    else:
        # paged: (slot, cur_len) -> (block, offset) through the sequence's
        # table row; an unallocated sentinel entry is out of pool range and
        # the write drops (inert/finished slots never corrupt a block that
        # was reassigned to another request)
        with jax.named_scope("kv_write"):
            bs = cache_k.shape[2]
            p = pos[:, 0]
            blk = block_tables[
                jnp.arange(b), jnp.minimum(p // bs, block_tables.shape[1] - 1)
            ]
            cache_k = cache_k.at[blk, :, p % bs].set(
                k[:, 0].astype(cache_k.dtype), mode="drop"
            )
            cache_v = cache_v.at[blk, :, p % bs].set(
                v[:, 0].astype(cache_v.dtype), mode="drop"
            )
            if quant:
                k_scale = k_scale.at[blk, :, p % bs].set(ks[:, 0], mode="drop")
                v_scale = v_scale.at[blk, :, p % bs].set(vs[:, 0], mode="drop")
        with jax.named_scope("attention"):
            o = _per_head(
                mesh_info,
                functools.partial(
                    ops.paged_decode_attention, window=cfg.sliding_window
                ),
                dict(q=q[:, 0], k=cache_k, v=cache_v, k_scale=k_scale, v_scale=v_scale),
                dict(cur_len=cur_len, block_tables=block_tables),
            )[:, None]
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bshk,hkd->bsd", o, qweight(params["wo"], o.dtype))
    if quant:
        return out, cache_k, cache_v, k_scale, v_scale
    return out, cache_k, cache_v


def attention_packed(
    params: Params,
    cfg: ArchConfig,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    tok_slot: jax.Array,
    tok_pos: jax.Array,
    valid: Optional[jax.Array] = None,
    pack_slots: Optional[jax.Array] = None,
    mesh_info=None,
    block_tables: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
):
    """Packed variable-length step: any mix of decode singletons and prefill
    chunks as ONE flat token batch (the unified serving dispatch).

    x: [T, d] packed hidden states; cache_k/v: [B, KV, S_max, hd];
    tok_slot/tok_pos: [T] int32 — token t belongs to cache slot
    ``tok_slot[t]`` at absolute position ``tok_pos[t]``; ``valid``
    optionally passes the precomputed per-pack attention mask (shared by
    every layer). The new K/V are scattered at (slot, pos) in one fused
    scatter (out-of-bounds positions — the pack's bucket padding — are
    dropped), then every token attends with its own causal bound
    ``p <= tok_pos[t]``: a prefill chunk is causally exact against both the
    already-cached prefix and its own earlier tokens written by the same
    scatter. Returns (out [T, d], new_k, new_v).

    With ``pack_slots`` ([P] int32, P ≪ B), ``tok_slot`` holds indices INTO
    ``pack_slots`` and attention runs against only those P gathered cache
    rows — the oracle's masked full-cross score plane then scales with the
    slots actually packed (a handful of admitting sequences), not the whole
    slot pool. Scatters still land in the full cache.

    With ``block_tables`` ([B, max_blocks] int32) the cache arguments are
    a block-paged pool ``[num_blocks, KV, block_size, hd]`` and the
    ``(slot, pos)`` indirection generalizes to ``(block, offset)``: the
    fused scatter routes through the token's table row (bucket-padding
    positions ≥ max_blocks*block_size map to the out-of-range sentinel
    and drop, exactly like the dense out-of-bounds drop), and attention
    dispatches through ``ops.paged_ragged_attention`` against the pack's
    table rows. Prefix-shared blocks are never written here — the engine
    only feeds tokens past the matched prefix, so every scattered
    position lands in a private block (block-aligned copy-on-write).

    With ``k_scale``/``v_scale`` the cache stores quantized rows (see
    :func:`attention_decode`): the pack's T fresh K/V rows quantize at
    insert and their scales scatter through the same (slot, pos) /
    (block, offset) routing as the payloads — O(T) scale rows written per
    step. Returns (out, new_k, new_v, new_k_scale, new_v_scale).
    """
    quant = k_scale is not None
    with jax.named_scope("attn_in"):
        q = jnp.einsum("td,dhk->thk", x, qweight(params["wq"], x.dtype))
        k = jnp.einsum("td,dhk->thk", x, qweight(params["wk"], x.dtype))
        v = jnp.einsum("td,dhk->thk", x, qweight(params["wv"], x.dtype))
        q = _head_constraint(q, mesh_info, 1)
        k = _head_constraint(k, mesh_info, 1)
        v = _head_constraint(v, mesh_info, 1)
        cache_k = _head_constraint(cache_k, mesh_info, 1)
        cache_v = _head_constraint(cache_v, mesh_info, 1)
        if cfg.qk_norm:
            q = rms_norm(q, params["q_norm"], cfg.norm_eps)
            k = rms_norm(k, params["k_norm"], cfg.norm_eps)
        pos = jnp.asarray(tok_pos, jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)

        glob_slot = tok_slot if pack_slots is None else pack_slots[tok_slot]
        if quant:
            k, ks = quantize_rows(k, cache_k.dtype)  # [T,KV,hd] -> scale [T,KV]
            v, vs = quantize_rows(v, cache_v.dtype)
    if block_tables is None:
        # one fused scatter for the whole pack replaces the per-admission
        # full-cache insert: O(T) rows written, never a cache-sized copy
        with jax.named_scope("kv_write"):
            cache_k = cache_k.at[glob_slot, :, pos].set(k.astype(cache_k.dtype), mode="drop")
            cache_v = cache_v.at[glob_slot, :, pos].set(v.astype(cache_v.dtype), mode="drop")
            if quant:
                k_scale = k_scale.at[glob_slot, :, pos].set(ks, mode="drop")
                v_scale = v_scale.at[glob_slot, :, pos].set(vs, mode="drop")

        with jax.named_scope("attention"):
            if pack_slots is None:
                att_k, att_v = cache_k, cache_v
                att_ks, att_vs = k_scale, v_scale
            else:  # P-row sub-cache view: attention work scales with the pack
                att_k, att_v = cache_k[pack_slots], cache_v[pack_slots]
                att_ks = None if k_scale is None else k_scale[pack_slots]
                att_vs = None if v_scale is None else v_scale[pack_slots]
            o = _per_head(
                mesh_info,
                functools.partial(ops.ragged_attention, window=cfg.sliding_window),
                dict(q=q, k=att_k, v=att_v, k_scale=att_ks, v_scale=att_vs),
                dict(tok_slot=tok_slot, tok_pos=pos, valid=valid),
            )  # [T, H, hd]
    else:
        # paged pool: same fused scatter through the (block, offset)
        # indirection. Positions past the table (bucket padding) pick the
        # out-of-range sentinel explicitly — clamping the table index and
        # letting a real block id through would corrupt offset 0 of a live
        # block; mode="drop" needs the OOB id to survive to the scatter
        with jax.named_scope("kv_write"):
            bs = cache_k.shape[2]
            maxb = block_tables.shape[1]
            nb = cache_k.shape[0]
            bidx = jnp.minimum(pos // bs, maxb - 1)
            blk = jnp.where(pos < maxb * bs, block_tables[glob_slot, bidx], nb)
            off = pos % bs
            cache_k = cache_k.at[blk, :, off].set(k.astype(cache_k.dtype), mode="drop")
            cache_v = cache_v.at[blk, :, off].set(v.astype(cache_v.dtype), mode="drop")
            if quant:
                k_scale = k_scale.at[blk, :, off].set(ks, mode="drop")
                v_scale = v_scale.at[blk, :, off].set(vs, mode="drop")

        with jax.named_scope("attention"):
            att_btab = (
                block_tables if pack_slots is None else block_tables[pack_slots]
            )
            o = _per_head(
                mesh_info,
                functools.partial(
                    ops.paged_ragged_attention, window=cfg.sliding_window
                ),
                dict(q=q, k=cache_k, v=cache_v, k_scale=k_scale, v_scale=v_scale),
                dict(tok_seq=tok_slot, tok_pos=pos, block_tables=att_btab, valid=valid),
            )  # [T, H, hd]
    with jax.named_scope("attn_out"):
        out = jnp.einsum("thk,hkd->td", o, qweight(params["wo"], o.dtype))
    if quant:
        return out, cache_k, cache_v, k_scale, v_scale
    return out, cache_k, cache_v


def _scatter_step(
    cache: jax.Array, new: jax.Array, cur_len: jax.Array, axis: int = 1,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Write ``new`` (size 1 on ``axis``) into ``cache`` at position
    cur_len (per-batch) of its position axis ``axis`` — 1 for a
    position-major ``[B,S,...]`` latent cache, 2 for a head-major
    ``[B,KV,S,hd]`` K/V cache or its ``[B,KV,S]`` scales.

    Scalar ``cur_len`` (all sequences aligned — the dry-run decode cells)
    uses one dynamic_update_slice; per-batch lengths use a vmapped
    dynamic_update_slice — an O(1)-per-row write instead of the old O(S)
    one-hot blend that read+wrote the entire cache every decode step.

    With ``layer`` (int32 scalar) ``cache`` is the stacked ``[L, ...]``
    cache (``axis`` still counts from the per-layer ``B`` axis) and each
    slot's row is written at ``(layer, slot, ..., cur_len)`` in place,
    O(B) rows a call. A lone row at an unknown offset of the position
    axis is narrower than a memory tile of the TPU, and to write one the
    compiler relayouts the whole stacked cache around every attention
    call; so each slot's write reads the tile-aligned window of ``w``
    positions that holds its row, patches the row and writes the window
    back (``w`` a multiple of every tile height, 128 where positions are
    the minor axis). Positions clamp into range as a
    dynamic_update_slice's start does.
    """
    if layer is not None:
        b, s = cache.shape[1], cache.shape[axis + 1]
        w = math.gcd(s, 128 if axis + 2 == cache.ndim else 32)
        pos = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
        pos = jnp.clip(pos, 0, s - 1)
        win = (1, 1) + cache.shape[2:axis + 1] + (w,) + cache.shape[axis + 2:]
        rows = jax.lax.broadcasted_iota(jnp.int32, win, axis + 1)

        def write_slot(i, c):
            p = pos[i]
            start = [layer, i] + [0] * (c.ndim - 2)
            start[axis + 1] = jnp.bitwise_and(p, -w)  # known tile-aligned
            old = jax.lax.dynamic_slice(c, start, win)
            row = jax.lax.dynamic_index_in_dim(new, i, keepdims=False)
            upd = jnp.where(rows == p - start[axis + 1], row.astype(c.dtype), old)
            return jax.lax.dynamic_update_slice(c, upd, start)

        return jax.lax.fori_loop(0, b, write_slot, cache)
    cur_len = jnp.asarray(cur_len)
    if cur_len.ndim == 0:
        start = [0] * cache.ndim
        start[axis] = cur_len
        return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), start)
    b = cache.shape[0]
    pos = jnp.broadcast_to(cur_len, (b,))

    def write_row(c, n, p):  # c: cache row (no B axis), n: its new slice
        start = [0] * c.ndim
        start[axis - 1] = p
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), start)

    return jax.vmap(write_row)(cache, new, pos)


def _per_head(
    mesh_info, fn, sharded: dict, replicated: dict, kv_axis: int = 1
) -> jax.Array:
    """Call attention kernel ``fn(**sharded, **replicated)``, per KV-head
    shard under merge-mode tensor parallelism.

    A Pallas call cannot be partitioned by GSPMD: left alone it would be
    fed all-gathered K/V. When the KV heads divide the ``model`` axis — the
    placement ``serve_cache_shardings`` gives the cache — ``fn`` runs inside
    a ``shard_map`` where the queries ``q`` (``[., H, d]``) split on axis 1,
    every other ``sharded`` operand (head-major caches ``[., KV, S, d]``,
    scales ``[., KV, S]``) on its KV-head axis ``kv_axis`` (2 for the
    stacked ``[L, B, KV, ...]`` decode cache), and every ``replicated`` one
    (descriptors, masks, block tables, the layer index) is whole: each
    device attends its own heads against its own cache shard, and the
    output ``[., H, d]`` stays head-sharded. ``None`` operands are dropped.
    Off-mesh (or with indivisible heads) it is a plain call."""
    sharded = {k: a for k, a in sharded.items() if a is not None}
    replicated = {k: a for k, a in replicated.items() if a is not None}
    ms = 1 if mesh_info is None else mesh_info.model_size
    if ms <= 1 or sharded["k"].shape[kv_axis] % ms:
        return fn(**sharded, **replicated)
    from jax.sharding import PartitionSpec as P

    heads = P(None, "model")
    cache_heads = P(*[None] * kv_axis, "model")
    specs = {k: heads if k == "q" else cache_heads for k in sharded}
    return jax.shard_map(
        lambda sh, rep: fn(**sh, **rep),
        mesh=mesh_info.mesh,
        in_specs=(specs, P()),
        out_specs=heads,
        check_vma=False,
    )(sharded, replicated)

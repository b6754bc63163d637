"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3).

Train/prefill uses the expanded form (decompress KV, normal attention).
Decode uses the ABSORBED form: only the compressed latent c_kv (rank
``kv_lora_rank``) plus the shared rope key are cached — the whole point of
MLA — and W_uk / W_uv are absorbed into the query/output projections, making
decode an MQA over a (kv_lora + rope_dim)-wide shared "head".

Cache per token = kv_lora + rope_dim floats (e.g. 576 for DeepSeek-V2) vs
2·H·hd for GQA — a 10-100× KV-memory reduction at long context.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.attention import (
    NEG_INF,
    _scatter_step,
    chunked_attention,
    dense_attention,
)
from repro.models.layers import Params, apply_rope, dense_init, rms_norm


def mla_init(key, cfg: ArchConfig, dtype) -> Params:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk_dim = m.nope_head_dim + m.rope_head_dim
    keys = jax.random.split(key, 6)
    p: Params = {}
    if m.q_lora_rank:
        p["wq_a"] = dense_init(keys[0], (d, m.q_lora_rank), dtype)
        p["q_norm"] = jnp.ones((m.q_lora_rank,), dtype)
        p["wq_b"] = dense_init(keys[1], (m.q_lora_rank, H, qk_dim), dtype, fan_in=m.q_lora_rank)
    else:
        p["wq"] = dense_init(keys[0], (d, H, qk_dim), dtype)
    p["wkv_a"] = dense_init(keys[2], (d, m.kv_lora_rank + m.rope_head_dim), dtype)
    p["kv_norm"] = jnp.ones((m.kv_lora_rank,), dtype)
    p["wkv_b"] = dense_init(
        keys[3],
        (m.kv_lora_rank, H, m.nope_head_dim + m.v_head_dim),
        dtype,
        fan_in=m.kv_lora_rank,
    )
    p["wo"] = dense_init(keys[4], (H, m.v_head_dim, d), dtype, fan_in=H * m.v_head_dim)
    return p


def _project_q(params: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    m = cfg.mla
    if m.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, params["wq_a"])
        cq = rms_norm(cq, params["q_norm"], cfg.norm_eps)
        return jnp.einsum("bsr,rhk->bshk", cq, params["wq_b"])
    return jnp.einsum("bsd,dhk->bshk", x, params["wq"])


def mla_apply(
    params: Params,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    return_kv: bool = False,
):
    """Full-sequence MLA (train / prefill), expanded form. x: [B,S,d].

    With ``return_kv`` also returns (c_kv [B,S,r], k_rope [B,S,rope_dim]) —
    the compressed-latent decode cache layout."""
    m = cfg.mla
    H = cfg.n_heads
    if positions.ndim == 1:
        positions = positions[None]

    q = _project_q(params, cfg, x)  # [B,S,H,nope+rope]
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])  # [B,S,kv_lora+rope]
    c_kv, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # [B,S,1,rope]

    kv = jnp.einsum("bsr,rhk->bshk", c_kv, params["wkv_b"])
    k_nope, v = jnp.split(kv, [m.nope_head_dim], axis=-1)

    k_rope_b = jnp.broadcast_to(k_rope, (*k_rope.shape[:2], H, m.rope_head_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)

    # pad v up to qk width so we can reuse the shared attention primitives,
    # then slice back (cheap: concat of zeros, sliced after).
    qk_dim = m.nope_head_dim + m.rope_head_dim
    if m.v_head_dim < qk_dim:
        v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - m.v_head_dim)))
    else:
        v_p = v
    if cfg.attn_impl == "dense":
        o = dense_attention(q_full, k_full, v_p, causal=True)
    else:
        o = chunked_attention(q_full, k_full, v_p, causal=True, chunk=cfg.attn_chunk)
    o = o[..., : m.v_head_dim]
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    if return_kv:
        return out, (c_kv, k_rope[:, :, 0, :])
    return out


# ---------------------------------------------------------------------------
# Absorbed decode with compressed cache
# ---------------------------------------------------------------------------


def mla_packed(
    params: Params,
    cfg: ArchConfig,
    x: jax.Array,
    cache_ckv: jax.Array,
    cache_krope: jax.Array,
    tok_slot: jax.Array,
    tok_pos: jax.Array,
    valid: jax.Array | None = None,
    pack_slots: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Packed variable-length MLA step over the compressed latent cache —
    the latent-space twin of ``attention_packed`` (unified serving
    dispatch: decode singletons and prefill chunks as ONE flat batch).

    x: [T, d] packed hidden states; cache_ckv: [B, S_max, kv_lora];
    cache_krope: [B, S_max, rope_dim]; tok_slot/tok_pos: [T] int32. The
    pack's fresh latents are ONE fused O(T) scatter (bucket-padding
    positions drop), then every token attends in absorbed form against
    the compressed cache. With ``pack_slots`` ([P] int32) attention reads
    only those P gathered latent rows. Returns (out [T, d], new_ckv,
    new_krope)."""
    m = cfg.mla
    with jax.named_scope("attn_in"):
        pos = jnp.asarray(tok_pos, jnp.int32)
        q = _project_q(params, cfg, x[None])[0]  # [T,H,nope+rope]
        q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
        q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

        ckv = jnp.einsum("td,dr->tr", x, params["wkv_a"])  # [T,kv_lora+rope]
        c_kv, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
        c_kv = rms_norm(c_kv, params["kv_norm"], cfg.norm_eps)
        k_rope = apply_rope(k_rope[:, None, :], pos, cfg.rope_theta)[:, 0, :]
        # absorb W_uk into q (q_eff [T,H,kv_lora]) to attend in latent space
        w_uk = params["wkv_b"][..., : m.nope_head_dim]  # [r,H,nope]
        q_eff = jnp.einsum("thk,rhk->thr", q_nope, w_uk)

    with jax.named_scope("kv_write"):
        glob_slot = tok_slot if pack_slots is None else pack_slots[tok_slot]
        cache_ckv = cache_ckv.at[glob_slot, pos].set(
            c_kv.astype(cache_ckv.dtype), mode="drop"
        )
        cache_krope = cache_krope.at[glob_slot, pos].set(
            k_rope.astype(cache_krope.dtype), mode="drop"
        )
    with jax.named_scope("attention"):
        if pack_slots is None:
            att_ckv, att_krope = cache_ckv, cache_krope
        else:  # P-row sub-cache view: attention work scales with the pack
            att_ckv, att_krope = cache_ckv[pack_slots], cache_krope[pack_slots]
        lat = ops.mla_ragged_attention(
            q_eff, q_rope, att_ckv, att_krope, tok_slot, pos,
            scale=(m.nope_head_dim + m.rope_head_dim) ** -0.5, valid=valid,
        )  # [T,H,r]
    with jax.named_scope("attn_out"):
        w_uv = params["wkv_b"][..., m.nope_head_dim :]  # [r,H,v]
        o = jnp.einsum("thr,rhv->thv", lat.astype(x.dtype), w_uv)
        out = jnp.einsum("thv,hvd->td", o, params["wo"])
    return out, cache_ckv, cache_krope


def mla_decode(
    params: Params,
    cfg: ArchConfig,
    x: jax.Array,
    cache_ckv: jax.Array,
    cache_krope: jax.Array,
    cur_len: jax.Array,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step with the latent cache.

    x: [B,1,d]; cache_ckv: [B,S,kv_lora]; cache_krope: [B,S,rope_dim]. With
    ``layer`` (int32 scalar) the caches are the whole stacked ``[L,B,S,.]``
    latents the decode layer loop carries: the new rows land at
    ``(layer, slot, cur_len)`` in place, and attention reads that layer out
    of the updated stack (a read-only slice XLA fuses into its einsums).
    """
    m = cfg.mla
    b = x.shape[0]
    s_max = cache_ckv.shape[-2]
    with jax.named_scope("attn_in"):
        pos = jnp.broadcast_to(jnp.asarray(cur_len), (b,))[:, None]  # [B,1]
        q = _project_q(params, cfg, x)  # [B,1,H,nope+rope]
        q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
        q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

        ckv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
        c_kv, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
        c_kv = rms_norm(c_kv, params["kv_norm"], cfg.norm_eps)
        k_rope = apply_rope(k_rope[:, :, None, :], pos, cfg.rope_theta)[:, :, 0, :]
        # absorb W_uk into q: q_eff [B,1,H,kv_lora]
        w_uk = params["wkv_b"][..., : m.nope_head_dim]  # [r,H,nope]
        q_eff = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)

    with jax.named_scope("kv_write"):
        cache_ckv = _scatter_step(cache_ckv, c_kv, cur_len, 1, layer)
        cache_krope = _scatter_step(cache_krope, k_rope, cur_len, 1, layer)

    # the latent MQA core: q_eff·cache scores through the latent readout
    with jax.named_scope("attention"):
        ckv_l, krope_l = cache_ckv, cache_krope
        if layer is not None:
            ckv_l = jax.lax.dynamic_index_in_dim(cache_ckv, layer, keepdims=False)
            krope_l = jax.lax.dynamic_index_in_dim(cache_krope, layer, keepdims=False)
        scores = (
            jnp.einsum("bshr,btr->bhst", q_eff.astype(jnp.float32),
                       ckv_l.astype(jnp.float32))
            + jnp.einsum(
                "bshk,btk->bhst", q_rope.astype(jnp.float32),
                krope_l.astype(jnp.float32)
            )
        ) * ((m.nope_head_dim + m.rope_head_dim) ** -0.5)
        valid = (jnp.arange(s_max)[None, :]
                 <= jnp.broadcast_to(jnp.asarray(cur_len), (b,))[:, None])
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        lat = jnp.einsum(
            "bhst,btr->bshr", probs.astype(x.dtype), ckv_l.astype(x.dtype)
        )

    # decompress through W_uv (absorbed output)
    with jax.named_scope("attn_out"):
        w_uv = params["wkv_b"][..., m.nope_head_dim :]  # [r,H,v]
        o = jnp.einsum("bshr,rhv->bshv", lat, w_uv)
        out = jnp.einsum("bshv,hvd->bsd", o, params["wo"])
    return out, cache_ckv, cache_krope

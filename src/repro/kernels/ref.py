"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

These are also the CPU fallback implementations used by ``ops.py`` when the
backend is not TPU, so the whole framework runs (slowly) anywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """[M,K] @ [K,N] with f32 accumulation."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def matmul_q8(a: jax.Array, b_q8: jax.Array, b_scale: jax.Array) -> jax.Array:
    """[M,K] @ int8 [K,N] with per-output-channel f32 scales [N].

    Oracle for the fused kernel: scale the finished f32 accumulator by the
    output column's scale (algebraically identical to dequantizing the
    weight first, but matching the kernel's flush-time multiply exactly)."""
    acc = jnp.dot(
        a, b_q8.astype(a.dtype), preferred_element_type=jnp.float32
    )
    return (acc * b_scale.reshape(1, -1).astype(jnp.float32)).astype(a.dtype)


def axpy(alpha, x: jax.Array, y: jax.Array) -> jax.Array:
    return (alpha * x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype)


def dotp(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))


def softmax(x: jax.Array) -> jax.Array:
    """Row softmax over the last dim, numerically stable, f32 math."""
    xf = x.astype(jnp.float32)
    m = xf.max(axis=-1, keepdims=True)
    e = jnp.exp(xf - m)
    return (e / e.sum(axis=-1, keepdims=True)).astype(x.dtype)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def fft(re: jax.Array, im: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched complex FFT over the last dim, planar (re, im) f32 layout."""
    z = jnp.fft.fft(re.astype(jnp.complex64) + 1j * im.astype(jnp.complex64), axis=-1)
    return jnp.real(z).astype(re.dtype), jnp.imag(z).astype(im.dtype)


def conv2d(x: jax.Array, w: jax.Array) -> jax.Array:
    """NHWC x HWIO VALID conv, stride 1, f32 accumulation."""
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w.astype(jnp.float32),
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out.astype(x.dtype)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True
) -> jax.Array:
    """[B,H,S,hd] attention oracle (dense softmax)."""
    b, h, s, d = q.shape
    scale = d**-0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, k.shape[2]), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def gqa_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True
) -> jax.Array:
    """GQA oracle. q: [BKV, G, Sq, d]; k/v: [BKV, Sk, d] (no head repeat)."""
    bkv, g, sq, d = q.shape
    sk = k.shape[1]
    scale = d**-0.5
    scores = jnp.einsum("bgqd,bkd->bgqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgqk,bkd->bgqd", p.astype(v.dtype), v)


def dequant_kv(k: jax.Array, k_scale: jax.Array | None, dtype) -> jax.Array:
    """Widen a (possibly int8) KV tensor to ``dtype`` and apply per-row
    scales (one scale per ``[..., d]`` row, i.e. ``k.shape[:-1]``). With
    ``k_scale=None`` this is the plain dtype cast the unquantized oracles
    always did; with all-ones f32 scales it is bit-identical to that cast
    (``x * 1.0 == x``), which is what makes the quantized machinery testable
    at ``kv_dtype=f32``."""
    kf = k.astype(dtype)
    if k_scale is None:
        return kf
    return kf * k_scale[..., None].astype(dtype)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cur_len: jax.Array,
    layer: jax.Array | None = None,
    *,
    window: int = 0,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-token grouped decode attention oracle.

    q: [B, KV, G, d]; k/v: [B, KV, S_max, d] (head-major), or with ``layer``
    the stacked [L, B, KV, S_max, d] cache, of which layer ``layer`` is
    read; cur_len: [] or [B] tokens already cached (the new token was
    scattered at index cur_len, so key position t is valid iff t <=
    cur_len). Optional ``k_scale``/``v_scale`` ([B, KV, S_max] f32, stacked
    like K/V) dequantize int8 K/V rows in-math — garbage scales at invalid
    positions are as harmless as garbage K/V (masked lanes).
    Returns [B, KV, G, d] in f32 softmax math, cast back to q.dtype.
    """
    if layer is not None:
        k, v = k[layer], v[layer]
        if k_scale is not None:
            k_scale, v_scale = k_scale[layer], v_scale[layer]
    k = dequant_kv(k, k_scale, q.dtype)
    v = dequant_kv(v, v_scale, q.dtype)
    b, kvh, g, d = q.shape
    s_max = k.shape[2]
    scale = d**-0.5
    scores = (
        jnp.einsum("bkgd,bktd->bkgt", q, k.astype(q.dtype)).astype(jnp.float32)
        * scale
    )  # [B,KV,G,S]
    kpos = jnp.arange(s_max)[None, :]
    cur = jnp.broadcast_to(jnp.asarray(cur_len), (b,))[:, None]
    valid = kpos <= cur
    if window:
        valid &= kpos > cur - window
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgt,bktd->bkgd", probs, v.astype(q.dtype))


def ragged_valid_mask(
    tok_slot: jax.Array,
    tok_pos: jax.Array,
    b: int,
    s_max: int,
    window: int = 0,
) -> jax.Array:
    """[T, B, S_max] bool: which cache entries each packed token may attend.

    Key position p of slot ``tok_slot[t]`` is valid iff p <= tok_pos[t]
    (windowed by p > tok_pos[t] - window) — the per-token generalization of
    the ``decode_attention`` convention. Descriptor-only, so the serving
    path computes it ONCE per pack and reuses it across every layer."""
    kpos = jnp.arange(s_max)[None, :]
    pos = jnp.asarray(tok_pos)[:, None]
    valid_s = kpos <= pos  # [T, S]
    if window:
        valid_s &= kpos > pos - window
    slot_hit = jnp.asarray(tok_slot)[:, None] == jnp.arange(b)[None, :]  # [T, B]
    return slot_hit[:, :, None] & valid_s[:, None, :]


def ragged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    tok_slot: jax.Array,
    tok_pos: jax.Array,
    *,
    window: int = 0,
    valid: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Packed variable-length attention oracle (the unified-dispatch path).

    q: [T, KV, G, d] packed query tokens (decode singletons and prefill
    chunks mixed); k/v: [B, KV, S_max, d] head-major batched cache with
    the packed tokens' K/V already scattered at (tok_slot, tok_pos);
    tok_slot/tok_pos: [T] int32; ``valid`` optionally passes a precomputed
    :func:`ragged_valid_mask`; ``k_scale``/``v_scale`` ([B, KV, S_max] f32)
    dequantize int8 caches per row. Returns [T, KV, G, d] in f32 softmax
    math, cast back to q.dtype.

    Full-cross formulation: every packed token scores against EVERY slot's
    cache in one batched matmul per KV head, and the B-1 wrong slots are
    masked away before a softmax over the joint (slot, position) axes —
    only the token's own slot survives, so this IS the per-slot softmax.
    B is small in serving (a handful of cache slots), so the B× extra MACs
    are far cheaper on CPU than a per-token cache gather followed by T tiny
    batched dots, and the whole oracle is two dot_generals + one where.
    """
    k = dequant_kv(k, k_scale, q.dtype)
    v = dequant_kv(v, v_scale, q.dtype)
    t, kvh, g, d = q.shape
    b, s_max = k.shape[0], k.shape[2]
    scale = d**-0.5
    if valid is None:
        valid = ragged_valid_mask(tok_slot, tok_pos, b, s_max, window)
    # explicit [KV]-batched [T·G, d] @ [d, B·S] matmuls: XLA CPU lowers this
    # shape well at every pack size (the equivalent 5-D einsum does not)
    qf = q.transpose(1, 0, 2, 3).reshape(kvh, t * g, d).astype(jnp.float32)
    kf = k.transpose(1, 0, 2, 3).reshape(kvh, b * s_max, d).astype(jnp.float32)
    scores = jnp.einsum("hqd,hsd->hqs", qf, kf) * scale  # [KV, T*G, B*S]
    valid_tg = jnp.broadcast_to(
        valid.reshape(t, 1, b * s_max), (t, g, b * s_max)
    ).reshape(t * g, b * s_max)
    scores = jnp.where(valid_tg[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    vf = v.transpose(1, 0, 2, 3).reshape(kvh, b * s_max, d).astype(jnp.float32)
    out = jnp.einsum("hqs,hsd->hqd", probs, vf)  # [KV, T*G, d]
    return out.reshape(kvh, t, g, d).transpose(1, 0, 2, 3).astype(q.dtype)


def mla_ragged_attention(
    q_eff: jax.Array,
    q_rope: jax.Array,
    ckv: jax.Array,
    krope: jax.Array,
    tok_slot: jax.Array,
    tok_pos: jax.Array,
    *,
    scale: float,
    valid: jax.Array | None = None,
) -> jax.Array:
    """Packed ragged attention in MLA latent space (absorbed decode form).

    q_eff: [T, H, r] queries pre-absorbed through W_uk (r = kv_lora_rank);
    q_rope: [T, H, rope] decoupled-RoPE queries; ckv: [B, S_max, r]
    compressed latent cache (doubles as K and V); krope: [B, S_max, rope]
    shared rope keys; tok_slot/tok_pos: [T] int32 pack descriptors;
    ``scale`` is the softmax scale — (nope + rope)**-0.5, NOT derived from
    the latent width (the latent dot replaces an H-head nope-dim dot, so
    the head-dim scale survives absorption). Returns [T, H, r] latent
    outputs; the caller decompresses through W_uv.

    Same full-cross formulation as :func:`ragged_attention`, specialized to
    MLA's MQA structure: ONE shared latent "head" serves every query head,
    scores are the sum of the latent and rope dots, and the value readout
    re-reads the latent cache itself.
    """
    t, h, r = q_eff.shape
    b, s_max = ckv.shape[0], ckv.shape[1]
    if valid is None:
        valid = ragged_valid_mask(tok_slot, tok_pos, b, s_max)
    qe = q_eff.transpose(1, 0, 2).astype(jnp.float32)  # [H, T, r]
    qr = q_rope.transpose(1, 0, 2).astype(jnp.float32)  # [H, T, rope]
    kl = ckv.reshape(b * s_max, r).astype(jnp.float32)  # [B·S, r]
    kr = krope.reshape(b * s_max, krope.shape[-1]).astype(jnp.float32)
    scores = (
        jnp.einsum("htr,sr->hts", qe, kl) + jnp.einsum("htk,sk->hts", qr, kr)
    ) * scale  # [H, T, B·S]
    valid_ts = valid.reshape(t, b * s_max)
    scores = jnp.where(valid_ts[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,sr->htr", probs, kl)  # latent-space readout
    return out.transpose(1, 0, 2).astype(q_eff.dtype)


def paged_gather(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize dense cache rows from a block-paged pool.

    pool: [num_blocks, KV, block_size, ...] (K/V payloads carry a trailing
    head_dim, scale pools none); block_tables: [R, max_blocks] int32
    (out-of-range sentinel = unallocated). Returns [R, KV, max_blocks *
    block_size, ...] — exactly the dense head-major ``[B, KV, S_max, ...]``
    cache layout, where position ``p`` of row ``r`` is
    ``pool[block_tables[r, p // block_size], :, p % block_size]``.

    Unallocated table entries clamp to the last real block, so their
    positions hold arbitrary (finite) pool contents — every consumer below
    masks by the same position bounds as the dense path, under which an
    identity-mapped pool reproduces the dense cache BIT-EXACTLY: masked
    score lanes contribute exp(NEG_INF - max) == 0 regardless of what the
    garbage positions hold. This gather is the oracle/CPU formulation; the
    Pallas path (``ragged_attention.paged_ragged_attention``) consumes the
    pool directly through its BlockSpec index map and never builds it.
    """
    nb, kvh, bs = pool.shape[:3]
    r, maxb = block_tables.shape
    view = pool[jnp.minimum(block_tables, nb - 1)]  # [R, maxb, KV, bs, ...]
    view = jnp.moveaxis(view, 1, 2)  # [R, KV, maxb, bs, ...]
    return view.reshape(r, kvh, maxb * bs, *pool.shape[3:])


def paged_decode_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    cur_len: jax.Array,
    block_tables: jax.Array,
    *,
    window: int = 0,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Paged single-token decode oracle: gather the per-sequence dense view
    from the pool, then the EXACT dense decode oracle — the paged engine's
    greedy streams stay bit-identical to the slot-cache engine on CPU.
    Scale pools ([num_blocks, KV, block_size] f32) ride the SAME gather
    (``paged_gather`` is trailing-dim agnostic), so scales travel with their
    blocks through tables, COW sharing and re-homing by construction."""
    return decode_attention(
        q,
        paged_gather(pool_k, block_tables),
        paged_gather(pool_v, block_tables),
        cur_len,
        window=window,
        k_scale=None if k_scale is None else paged_gather(k_scale, block_tables),
        v_scale=None if v_scale is None else paged_gather(v_scale, block_tables),
    )


def paged_ragged_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    tok_seq: jax.Array,
    tok_pos: jax.Array,
    block_tables: jax.Array,
    *,
    window: int = 0,
    valid: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Paged packed ragged oracle: the dense :func:`ragged_attention` over
    the block tables' gathered view (same masks, same math, bit-identical
    to the dense path wherever positions are valid). Scale pools gather
    through the same tables as their payload blocks."""
    return ragged_attention(
        q,
        paged_gather(pool_k, block_tables),
        paged_gather(pool_v, block_tables),
        tok_seq,
        tok_pos,
        window=window,
        valid=valid,
        k_scale=None if k_scale is None else paged_gather(k_scale, block_tables),
        v_scale=None if v_scale is None else paged_gather(v_scale, block_tables),
    )


def fft_twiddles(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-stage Stockham radix-2 twiddle table [log2(n), n//2] (re, im).

    Stage s (s=0 the first) multiplies odd halves by W_{2L}^{j mod L} where
    L = 2**s; entries are tiled so every stage reads row s directly.
    """
    stages = int(np.log2(n))
    assert 2**stages == n, f"n={n} must be a power of 2"
    tw_re = np.ones((stages, n // 2), np.float32)
    tw_im = np.zeros((stages, n // 2), np.float32)
    for s in range(stages):
        l = 2**s
        j = np.arange(n // 2) % l
        ang = -2.0 * np.pi * j / (2 * l)
        tw_re[s] = np.cos(ang).astype(np.float32)
        tw_im[s] = np.sin(ang).astype(np.float32)
    return tw_re, tw_im


def fft_stockham(re: jax.Array, im: jax.Array) -> tuple[jax.Array, jax.Array]:
    """jnp Stockham radix-2 (the exact algorithm the Pallas kernel runs).

    Kept separate from :func:`fft` (which defers to jnp.fft) so kernel bugs
    can be localized: kernel ↔ fft_stockham ↔ jnp.fft.
    """
    b, n = re.shape
    stages = int(np.log2(n))
    tw_re, tw_im = fft_twiddles(n)
    xr = re.astype(jnp.float32)
    xi = im.astype(jnp.float32)
    for s in range(stages):
        l = 2**s
        g = n // (2 * l)
        # Stockham split: even = first half, odd = second half, viewed [g, l]
        er = xr[:, : n // 2].reshape(b, g, l)
        ei = xi[:, : n // 2].reshape(b, g, l)
        orr = xr[:, n // 2 :].reshape(b, g, l)
        oi = xi[:, n // 2 :].reshape(b, g, l)
        twr = tw_re[s].reshape(g, l)
        twi = tw_im[s].reshape(g, l)
        tr = orr * twr - oi * twi
        ti = orr * twi + oi * twr
        xr = jnp.concatenate([er + tr, er - tr], axis=-1).reshape(b, n)
        xi = jnp.concatenate([ei + ti, ei - ti], axis=-1).reshape(b, n)
    return xr.astype(re.dtype), xi.astype(im.dtype)

"""Dispatch layer for the kernels: Pallas on TPU, interpret-mode Pallas for
validation, jnp oracle fallback for fast CPU execution.

Hot-path contract: no ``jnp.pad`` device copies. Kernels ceil-divide their
grids and mask tail blocks in-kernel (iota compares against the true sizes),
so arbitrary shapes dispatch straight through. Block sizes come from the
autotuner (``repro.kernels.autotune``) unless the caller pins them; the
resolved (mode, blocks) plan is memoized per static shape so repeat calls
skip both the tuner consult and the block arithmetic. ``mode`` resolution:

* ``auto``      — compiled Pallas on TPU, oracle elsewhere (production)
* ``pallas``    — compiled Pallas (TPU only)
* ``interpret`` — Pallas kernel body interpreted on CPU (correctness runs)
* ``ref``       — the jnp oracle
"""

from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.kernels import (
    autotune,
    axpy as _axpy_k,
    conv2d as _conv2d_k,
    decode_attention as _decode_k,
    dotp as _dotp_k,
    fft as _fft_k,
    flash_attention as _flash_k,
    matmul as _matmul_k,
    ragged_attention as _ragged_k,
    rmsnorm as _rmsnorm_k,
    softmax as _softmax_k,
)
from repro.kernels import ref

Mode = Literal["auto", "pallas", "interpret", "ref"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: Mode) -> str:
    if mode == "auto":
        return "pallas" if _on_tpu() else "ref"
    return mode


@functools.lru_cache(maxsize=4096)
def _plan(
    op: str, shape: tuple[int, ...], dtype_name: str, backend: str, gen: int,
    kv_dtype_name: Optional[str] = None,
) -> dict[str, int]:
    """Memoized block plan for one static (op, shape, dtype, backend) cell.

    ``gen`` is the tuner generation — swapping tuners (tests) invalidates
    every memoized plan without touching this cache directly.
    ``kv_dtype_name`` keys quantized-cache attention separately (an int8
    cache moves half/quarter the HBM bytes per tile, so its block-size
    winner need not match the f32 cache's).
    """
    tuner = autotune.get_tuner()
    hit = tuner.lookup(op, shape, dtype_name, backend, kv_dtype_name)
    if hit is not None:
        return dict(hit)
    # only build the measure closure (it allocates bucketed synthetic
    # inputs) once we know the lookup missed and a sweep will actually run
    measure = (
        autotune.measure_for(op, shape, dtype_name, backend)
        if tuner.sweep
        else None
    )
    return tuner.get(
        op, shape, dtype_name, backend, measure=measure, kv_dtype=kv_dtype_name
    )


def _blocks(
    op: str, shape: tuple[int, ...], dtype, backend: str, kv_dtype=None
) -> dict[str, int]:
    return _plan(
        op, shape, jnp.dtype(dtype).name, backend, autotune.generation(),
        None if kv_dtype is None else jnp.dtype(kv_dtype).name,
    )


# ---------------------------------------------------------------------------


def matmul(
    a,
    b,
    *,
    mode: Mode = "auto",
    block: Optional[int] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
):
    m = _resolve(mode)
    if m == "ref":
        return ref.matmul(a, b)
    m0, k0 = a.shape
    n0 = b.shape[1]
    if block is not None:
        block_m = block_n = block_k = block
    if block_m is None or block_n is None or block_k is None:
        cfg = _blocks("matmul", (m0, k0, n0), a.dtype, m)  # fill the gaps
        block_m = cfg["block_m"] if block_m is None else block_m
        block_n = cfg["block_n"] if block_n is None else block_n
        block_k = cfg["block_k"] if block_k is None else block_k
    return _matmul_k.matmul(
        a, b,
        block_m=min(block_m, max(m0, 1)),
        block_n=min(block_n, max(n0, 1)),
        block_k=min(block_k, max(k0, 1)),
        interpret=(m == "interpret"),
    )


def matmul_q8(
    a,
    b_q8,
    b_scale,
    *,
    mode: Mode = "auto",
    block: Optional[int] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """[M,K] @ int8 [K,N] + per-output-channel scales [N] — the quantized
    weight-serving matmul. Block plans key the tuner cache with the int8
    RHS dtype (``|kvint8`` suffix) so f32 winners aren't reused blindly."""
    m = _resolve(mode)
    if m == "ref":
        return ref.matmul_q8(a, b_q8, b_scale)
    m0, k0 = a.shape
    n0 = b_q8.shape[1]
    if block is not None:
        block_m = block_n = block_k = block
    if block_m is None or block_n is None or block_k is None:
        cfg = _blocks("matmul", (m0, k0, n0), a.dtype, m, kv_dtype=b_q8.dtype)
        block_m = cfg["block_m"] if block_m is None else block_m
        block_n = cfg["block_n"] if block_n is None else block_n
        block_k = cfg["block_k"] if block_k is None else block_k
    return _matmul_k.matmul_q8(
        a, b_q8, b_scale,
        block_m=min(block_m, max(m0, 1)),
        block_n=min(block_n, max(n0, 1)),
        block_k=min(block_k, max(k0, 1)),
        interpret=(m == "interpret"),
    )


def axpy(alpha, x, y, *, mode: Mode = "auto", block: Optional[int] = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.axpy(alpha, x, y)
    orig_shape = x.shape
    x2 = x.reshape(1, -1) if x.ndim == 1 else x
    y2 = y.reshape(1, -1) if y.ndim == 1 else y
    if block is None:
        block = _blocks("axpy", x2.shape, x.dtype, m)["block"]
    blk = min(block, x2.shape[-1])
    out = _axpy_k.axpy(alpha, x2, y2, block=blk, interpret=(m == "interpret"))
    return out.reshape(orig_shape)


def dotp(x, y, *, mode: Mode = "auto", block: Optional[int] = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.dotp(x, y)
    x2 = x.reshape(1, -1)
    y2 = y.reshape(1, -1)
    if block is None:
        block = _blocks("dotp", x2.shape, x.dtype, m)["block"]
    blk = min(block, x2.shape[-1])
    return _dotp_k.dotp(x2, y2, block=blk, interpret=(m == "interpret"))


def softmax(x, *, mode: Mode = "auto", block_rows: Optional[int] = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.softmax(x)
    orig = x.shape
    x2 = x.reshape(-1, orig[-1])
    if block_rows is None:
        block_rows = _blocks("softmax", x2.shape, x.dtype, m)["block_rows"]
    br = min(block_rows, x2.shape[0])
    out = _softmax_k.softmax(x2, block_rows=br, interpret=(m == "interpret"))
    return out.reshape(orig)


def rmsnorm(
    x, w, *, eps: float = 1e-6, mode: Mode = "auto",
    block_rows: Optional[int] = None,
):
    m = _resolve(mode)
    if m == "ref":
        return ref.rmsnorm(x, w, eps)
    orig = x.shape
    x2 = x.reshape(-1, orig[-1])
    if block_rows is None:
        block_rows = _blocks("rmsnorm", x2.shape, x.dtype, m)["block_rows"]
    br = min(block_rows, x2.shape[0])
    out = _rmsnorm_k.rmsnorm(x2, w, eps=eps, block_rows=br, interpret=(m == "interpret"))
    return out.reshape(orig)


def fft(re, im, *, mode: Mode = "auto", block_rows: Optional[int] = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.fft(re, im)
    if block_rows is None:
        block_rows = _blocks("fft", re.shape, re.dtype, m)["block_rows"]
    br = min(block_rows, re.shape[0])
    return _fft_k.fft(re, im, block_rows=br, interpret=(m == "interpret"))


def conv2d(x, w, *, mode: Mode = "auto", block_h: Optional[int] = None):
    m = _resolve(mode)
    if m == "ref":
        return ref.conv2d(x, w)
    kh = w.shape[0]
    h_out = x.shape[1] - kh + 1
    if block_h is None:
        block_h = _blocks("conv2d", x.shape, x.dtype, m)["block_h"]
    bh = min(block_h, h_out)
    # pad-free: the grid ceil-divides and the kernel anchors the tail tile's
    # halo slice at the image edge (shifted-tile recompute), so ragged H
    # dispatches straight through like every other kernel
    return _conv2d_k.conv2d(x, w, block_h=bh, interpret=(m == "interpret"))


def flash_attention(
    q, k, v, *, causal: bool = True, mode: Mode = "auto",
    block: Optional[int] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
):
    """q/k/v: [B, H, S, d] or [BH, S, d]. Arbitrary S — the kernel's
    key-validity mask covers the K overhang (causal and non-causal alike)."""
    m = _resolve(mode)
    squeeze = False
    if q.ndim == 3:
        q, k, v = q[None], k[None], v[None]
        squeeze = True
    b, h, s, d = q.shape
    if m == "ref":
        out = ref.flash_attention(q, k, v, causal=causal)
        return out[0] if squeeze else out
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, k.shape[2], d)
    vf = v.reshape(b * h, v.shape[2], d)
    if block is not None:
        block_q = block_k = block
    if block_q is None or block_k is None:
        cfg = _blocks("flash_attention", qf.shape, q.dtype, m)  # fill the gaps
        block_q = cfg["block_q"] if block_q is None else block_q
        block_k = cfg["block_k"] if block_k is None else block_k
    out = _flash_k.flash_attention(
        qf, kf, vf, causal=causal,
        block_q=min(block_q, s), block_k=min(block_k, kf.shape[1]),
        interpret=(m == "interpret"),
    )
    out = out.reshape(b, h, s, d)
    return out[0] if squeeze else out


def gqa_flash_attention(
    q, k, v, *, causal: bool = True, mode: Mode = "auto",
    block_q: Optional[int] = None, block_k: Optional[int] = None,
):
    """GQA-native attention: q [B, H, S, d], k/v [B, KV, S, d], H % KV == 0.

    K/V are never expanded to H heads — the kernel broadcasts each KV tile
    across the query-head group via the grid, the oracle via einsum."""
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    qg = q.reshape(b, kvh, g, sq, d).reshape(b * kvh, g, sq, d)
    kf = k.reshape(b * kvh, k.shape[2], d)
    vf = v.reshape(b * kvh, v.shape[2], d)
    m = _resolve(mode)
    if m == "ref":
        out = ref.gqa_flash_attention(qg, kf, vf, causal=causal)
    else:
        if block_q is None or block_k is None:
            cfg = _blocks("gqa_flash_attention", qg.shape, q.dtype, m)
            block_q = cfg["block_q"] if block_q is None else block_q
            block_k = cfg["block_k"] if block_k is None else block_k
        out = _flash_k.gqa_flash_attention(
            qg, kf, vf, causal=causal,
            block_q=min(block_q, sq), block_k=min(block_k, kf.shape[1]),
            interpret=(m == "interpret"),
        )
    return out.reshape(b, h, sq, d)


def decode_attention(
    q, k, v, cur_len, *, window: int = 0, mode: Mode = "auto",
    block_s: Optional[int] = None, k_scale=None, v_scale=None,
):
    """Batched single-token decode attention against the KV cache.

    q: [B, H, d] (the new token's query heads); k/v: [B, KV, S_max, d]
    (head-major decode-cache layout, possibly lower-precision storage);
    cur_len: [] or [B] tokens already cached per slot. ``k_scale``/``v_scale``
    ([B, KV, S_max] f32, the cache-resident scale leaves) mark K/V as
    int8 rows — dequant happens inside the kernel / oracle, never as an
    f32 cache copy. Returns [B, H, d]."""
    b, h, d = q.shape
    kvh, s_max = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    m = _resolve(mode)
    if m == "ref":
        out = ref.decode_attention(
            qg, k, v, cur_len, window=window,
            k_scale=k_scale, v_scale=v_scale,
        )
    else:
        if block_s is None:
            block_s = _blocks(
                "decode_attention", k.shape, q.dtype, m,
                kv_dtype=None if k_scale is None else k.dtype,
            )["block_s"]
        # no pre-cast of the cache: the kernel upcasts per-tile (int8/bf16
        # storage reads stay at storage width in HBM) and reads the cache
        # and its scales in place
        out = _decode_k.decode_attention(
            qg, k, v, cur_len,
            window=window, block_s=min(block_s, s_max),
            interpret=(m == "interpret"), k_scale=k_scale, v_scale=v_scale,
        )
    return out.reshape(b, h, d)


def ragged_attention(
    q, k, v, tok_slot, tok_pos, *, window: int = 0, mode: Mode = "auto",
    block_s: Optional[int] = None, valid=None, k_scale=None, v_scale=None,
):
    """Packed variable-length attention: a flat token batch (decode
    singletons + prefill chunks from any mix of sequences) against the
    batched cache. The unified serving dispatch routes every tick through
    this one op instead of choosing between prefill and decode programs.

    q: [T, H, d] packed query tokens; k/v: [B, KV, S_max, d] (head-major
    decode-cache layout, possibly lower-precision storage) with the packed tokens' K/V
    already scattered at (tok_slot, tok_pos); tok_slot/tok_pos: [T] int32.
    ``valid`` optionally passes a precomputed ``ref.ragged_valid_mask``
    (descriptor-only, so one mask serves every layer of a packed step); the
    Pallas kernel derives its masks in-kernel and ignores it.
    Returns [T, H, d]."""
    t, h, d = q.shape
    kvh, s_max = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    qg = q.reshape(t, kvh, g, d)
    m = _resolve(mode)
    if m == "ref":
        out = ref.ragged_attention(
            qg, k, v, tok_slot, tok_pos, window=window, valid=valid,
            k_scale=k_scale, v_scale=v_scale,
        )
    else:
        if block_s is None:
            block_s = _blocks(
                "ragged_attention", k.shape, q.dtype, m,
                kv_dtype=None if k_scale is None else k.dtype,
            )["block_s"]
        # no pre-cast of the cache: the kernel upcasts per-tile (int8/bf16
        # storage reads stay at storage width in HBM)
        out = _ragged_k.ragged_attention(
            qg, k, v, tok_slot, tok_pos,
            window=window, block_s=min(block_s, s_max),
            interpret=(m == "interpret"), k_scale=k_scale, v_scale=v_scale,
        )
    return out.reshape(t, h, d)


def mla_ragged_attention(
    q_eff, q_rope, ckv, krope, tok_slot, tok_pos, *, scale: float,
    mode: Mode = "auto", block_s: Optional[int] = None, valid=None,
):
    """Packed ragged attention over the MLA compressed latent cache.

    q_eff: [T, H, r] absorbed queries; q_rope: [T, H, rope]; ckv:
    [B, S_max, r] latent cache (keys AND values); krope: [B, S_max, rope];
    ``scale`` the absorbed softmax scale ((nope+rope)**-0.5). Returns
    [T, H, r] latent outputs.

    The non-ref modes reuse the existing ragged kernel as a latent-space
    MQA: keys = concat(ckv, krope) under ONE shared KV head, values = ckv
    zero-padded to key width, and the query pre-scaled by
    ``scale * (r+rope)**0.5`` to cancel the kernel's internal
    ``d**-0.5`` — the padded value lanes read back as zeros and are
    sliced off. No MLA-specific kernel needs to exist for the packed
    path to ride the tuned dispatch."""
    t, h, r = q_eff.shape
    m = _resolve(mode)
    if m == "ref":
        return ref.mla_ragged_attention(
            q_eff, q_rope, ckv, krope, tok_slot, tok_pos,
            scale=scale, valid=valid,
        )
    rope = q_rope.shape[-1]
    d_tot = r + rope
    gain = scale * d_tot**0.5  # kernel divides by sqrt(d_tot); we undo it
    q_cat = jnp.concatenate([q_eff * gain, q_rope * gain], axis=-1)
    qg = q_cat.reshape(t, 1, h, d_tot)  # ONE shared latent KV head
    k_cat = jnp.concatenate([ckv, krope], axis=-1)[:, None]  # [B, 1, S, d]
    v_pad = jnp.concatenate([ckv, jnp.zeros_like(krope)], axis=-1)[:, None]
    s_max = k_cat.shape[2]
    if block_s is None:
        block_s = _blocks("ragged_attention", k_cat.shape, q_eff.dtype, m)[
            "block_s"
        ]
    out = _ragged_k.ragged_attention(
        qg, k_cat, v_pad, tok_slot, tok_pos,
        window=0, block_s=min(block_s, s_max),
        interpret=(m == "interpret"),
    )  # [T, 1, H, d_tot]
    return out.reshape(t, h, d_tot)[..., :r]


def paged_ragged_attention(
    q, k, v, tok_seq, tok_pos, block_tables, *, window: int = 0,
    mode: Mode = "auto", valid=None, k_scale=None, v_scale=None,
):
    """Packed variable-length attention against a block-paged KV pool: the
    ``(slot, pos)`` descriptor indirection of :func:`ragged_attention`
    generalized to ``(block, offset)`` through per-sequence block tables.

    q: [T, H, d] packed query tokens; k/v: [num_blocks, KV, block_size, d]
    pool with the packed tokens' K/V already scattered at their (block,
    offset); tok_seq/tok_pos: [T] int32 — token t belongs to block-table
    row ``tok_seq[t]`` at absolute position ``tok_pos[t]``; block_tables:
    [R, max_blocks] int32. The oracle/CPU path gathers the tables' dense
    view and reuses the dense oracle (bit-identical to unpaged serving);
    the Pallas kernel streams pool blocks straight through its index map
    — no gathered view ever exists on TPU. Returns [T, H, d]."""
    t, h, d = q.shape
    kvh = k.shape[1]
    assert h % kvh == 0, (h, kvh)
    qg = q.reshape(t, kvh, h // kvh, d)
    m = _resolve(mode)
    if m == "ref":
        out = ref.paged_ragged_attention(
            qg, k, v, tok_seq, tok_pos, block_tables,
            window=window, valid=valid,
            k_scale=k_scale, v_scale=v_scale,
        )
    else:
        out = _ragged_k.paged_ragged_attention(
            qg, k, v, tok_seq, tok_pos, block_tables,
            window=window, interpret=(m == "interpret"),
            k_scale=k_scale, v_scale=v_scale,
        )
    return out.reshape(t, h, d)


def paged_decode_attention(
    q, k, v, cur_len, block_tables, *, window: int = 0, mode: Mode = "auto",
    k_scale=None, v_scale=None,
):
    """Batched single-token decode attention against a block-paged pool.

    q: [B, H, d]; k/v: [num_blocks, KV, block_size, d]; cur_len: [] or [B];
    block_tables: [B, max_blocks] int32 (row b maps sequence b's S tiles
    to pool blocks). CPU gathers the dense per-sequence view and runs the
    dense decode oracle — bit-identical to the unpaged path; TPU routes
    through the paged ragged kernel with one descriptor per sequence (the
    same ONE kernel carries prefill packs and decode chunks)."""
    b, h, d = q.shape
    kvh = k.shape[1]
    assert h % kvh == 0, (h, kvh)
    m = _resolve(mode)
    if m == "ref":
        qg = q.reshape(b, kvh, h // kvh, d)
        out = ref.paged_decode_attention(
            qg, k, v, cur_len, block_tables, window=window,
            k_scale=k_scale, v_scale=v_scale,
        )
        return out.reshape(b, h, d)
    cur = jnp.broadcast_to(jnp.asarray(cur_len), (b,))
    return paged_ragged_attention(
        q, k, v, jnp.arange(b, dtype=jnp.int32), cur, block_tables,
        window=window, mode=mode, k_scale=k_scale, v_scale=v_scale,
    )

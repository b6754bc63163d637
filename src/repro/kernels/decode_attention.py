"""Batched GQA decode-attention kernel: one query token per sequence against
one layer of the stacked head-major ``[L, B, KV, S_max, hd]`` cache, per-slot
valid lengths.

This is the serving-engine hot path: every engine tick runs one of these per
layer over all batch slots. The grid is (batch, KV head, S tiles); the
query-head group rides inside the block (a [G, hd] tile — G = H//KV query
heads share one KV head), so the cache is never expanded ``G``-fold. The
per-slot length arrives as a scalar-prefetch-style SMEM operand and gates
whole tiles: tiles entirely past ``cur_len`` are skipped (``pl.when``), so
short slots in a long cache cost proportionally less. The layer index is a
second scalar-prefetch operand that only the K/V index maps read: the kernel
fetches its tiles straight out of the stacked cache, so the layer loop never
cuts a per-layer copy of the cache for it.

Layout: the cache is head-major so every K/V tile is a 2-D ``(block_s, hd)``
slab — the block ``(1, 1, block_s, hd)`` keeps the TPU's tiled minor dims
(sublane, lane) at (positions, head_dim). A position-major ``[B, S, KV, hd]``
cache would put the KV-head count on the sublane dim, which a one-head block
cannot tile. Per-position scales of a quantized cache are ``[L, B, KV, S_max]``
rows fetched as whole-KV ``(1, KV, block_s)`` blocks (legal for any KV), and
the kernel picks its head's row.

Semantics match ``ref.decode_attention``: key position ``t`` is valid iff
``t <= cur_len`` (the new token was just scattered at index ``cur_len``),
windowed by ``t > cur_len - window`` when ``window > 0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import _online_softmax_update

NEG_INF = -1e30


def attend_tile(
    pos, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
    block_s: int, s_steps: int, window: int
):
    """One (query row, KV head, S tile) grid step shared by the decode,
    ragged and paged kernels: keys at absolute positions ``p <= pos`` (and
    inside the window) are valid; the online-softmax state lives in VMEM
    scratch across the S axis (grid dim 2).

    ``ks_ref``/``vs_ref`` (``None`` for an unquantized cache) hold the
    whole-KV ``[KV, block_s]`` scale tile; the K scale multiplies the score
    lanes and the V scale the probability lanes, so the narrow K/V tiles
    widen in-register and no dequantized cache copy ever exists in HBM."""
    hi, si = pl.program_id(1), pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bs, d]
        v = v_ref[0, 0].astype(jnp.float32)  # [bs, d]
        d = q.shape[-1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (d**-0.5)
        kpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos <= pos
        if window:
            valid &= kpos > pos - window
        v_scale = None
        if ks_ref is not None:
            s = s * ks_ref[0, pl.ds(hi, 1), :]  # [1, bs] per-position K scale
            # overhang lanes of a tail tile are undefined: zero their scale
            rpos = si * block_s + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_s), 1
            )
            v_scale = jnp.where(rpos <= pos, vs_ref[0, pl.ds(hi, 1), :], 0.0)
        s = jnp.where(valid, s, NEG_INF)
        # zero rows of v that can't contribute (overhang reads are undefined)
        vpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v_ok = vpos <= pos
        if window:
            v_ok &= vpos > pos - window
        v = jnp.where(v_ok, v, 0.0)
        _online_softmax_update(s, v, m_ref, l_ref, acc_ref, v_scale)

    # skip tiles entirely past the valid prefix (and before the window)
    live = si * block_s <= pos
    if window:
        live &= (si + 1) * block_s > pos - window
    pl.when(live)(_compute)

    @pl.when(si == s_steps - 1)
    def _flush():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def split_refs(refs, quant: bool):
    """Unpack a kernel's ``(q, k, [ks], v, [vs], o, m, l, acc)`` refs into
    attend_tile's order, with ``None`` scales for an unquantized cache."""
    if quant:
        q, k, ks, v, vs, *rest = refs
    else:
        (q, k, v, *rest), ks, vs = refs, None, None
    return (q, k, v, ks, vs, *rest)


def _decode_kernel(lens_ref, layer_ref, *refs, quant: bool, **kw):
    # this slot's cached-token count; the new token sits at index cur
    attend_tile(lens_ref[pl.program_id(0)], *split_refs(refs, quant), **kw)


@functools.partial(
    jax.jit, static_argnames=("window", "block_s", "interpret")
)
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cur_len: jax.Array,
    layer: jax.Array | None = None,
    *,
    window: int = 0,
    block_s: int = 256,
    interpret: bool = False,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """q: [B, KV, G, d]; k/v: the stacked [L, B, KV, S_max, d] cache, read
    at layer ``layer`` (int32 scalar); cur_len: [B] int32. Without
    ``layer``, k/v are one layer's [B, KV, S_max, d].

    With ``k_scale``/``v_scale`` ([L, B, KV, S_max] f32, or [B, KV, S_max]
    without ``layer``) K/V may be int8: tiles dequantize in-register inside
    the kernel. Returns [B, KV, G, d] attention outputs for the new token."""
    if layer is None:  # one layer's cache is a stack of one (a free reshape)
        k, v = k[None], v[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    b, kvh, g, d = q.shape
    s_max = k.shape[3]
    s_steps = pl.cdiv(s_max, block_s)
    grid = (b, kvh, s_steps)
    quant = k_scale is not None
    kv_spec = pl.BlockSpec(
        (None, 1, 1, block_s, d),
        lambda bi, hi, si, lens, lyr: (lyr[0], bi, hi, si, 0),
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, g, d), lambda bi, hi, si, lens, lyr: (bi, hi, 0, 0)
        ),
        kv_spec,
    ]
    if quant:
        scale_spec = pl.BlockSpec(
            (None, 1, kvh, block_s),
            lambda bi, hi, si, lens, lyr: (lyr[0], bi, 0, si),
        )
        in_specs += [scale_spec, kv_spec, scale_spec]
    else:
        in_specs.append(kv_spec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bi, hi, si, lens, lyr: (bi, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )

    cur_len = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    operands = (
        (cur_len, layer, q, k, k_scale, v, v_scale)
        if quant
        else (cur_len, layer, q, k, v)
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, quant=quant, block_s=block_s, s_steps=s_steps,
            window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)

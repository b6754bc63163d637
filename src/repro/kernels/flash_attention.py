"""Flash attention forward kernels (LM hot-spot; the framework's biggest
compute consumer at prefill).

Blockwise online-softmax attention: Q tiles stay VMEM-resident while K/V
tiles stream HBM→VMEM along the innermost (sequential) grid dim; the
(m, l, acc) online-softmax state lives in f32 VMEM scratch. Causal masking
skips fully-masked K tiles via ``pl.when`` (upper-triangle tiles cost zero
MXU work). This is the Pallas twin of
``repro.models.attention.chunked_attention`` (the XLA fallback), and the
oracle is ``ref.flash_attention``.

Ragged shapes: grids ceil-divide and a key-validity iota mask inside the
kernel drops the K overhang (valid length = the true S), so non-divisible
and non-causal shapes run in-kernel instead of falling back to the oracle.

``gqa_flash_attention`` is the GQA-native variant: the grid iterates KV
heads with the Q-head group as its own (parallel) grid dim, so one K/V tile
serves the whole group and K/V are never physically repeated ``H//KV``-fold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_update(s, v, m_ref, l_ref, acc_ref, v_row_scale=None):
    """One K-tile's online (max, sum, acc) update. s: [bq, bk] f32 scores.

    ``v_row_scale`` ([1, bk] f32) dequantizes int8 value rows inside the
    readout: ``p @ (v * vs[:, None]) == (p * vs) @ v``, so the per-position
    scale multiplies the probability lanes instead of the [bk, d] tile."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    pv = p if v_row_scale is None else p * v_row_scale
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        pv, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new


def _flash_tile_body(
    q_tile, k_tile, v_tile, o_ref, m_ref, l_ref, acc_ref, write_out, *,
    qi, ki, causal: bool, k_steps: int, block_q: int, block_k: int, kv_len: int
):
    """Shared per-tile body of the flash kernels: init at the first K step,
    masked score compute + online-softmax update (with the causal tile
    skip), flush at the last. ``q_tile``/``k_tile``/``v_tile`` are thunks
    reading this kernel's block layout; ``write_out`` stores the final
    tile. ``qi``/``ki`` are the Q/K grid positions (axes differ between the
    flat and GQA grids)."""

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_tile().astype(jnp.float32)  # [bq, d]
        k = k_tile().astype(jnp.float32)  # [bk, d]
        v = v_tile().astype(jnp.float32)  # [bk, d]
        d = q.shape[-1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (d**-0.5)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < kv_len  # key-validity mask: drops the K overhang
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            valid &= kpos <= qpos
        s = jnp.where(valid, s, NEG_INF)
        if kv_len % block_k:  # overhang rows of v are undefined; p there is 0
            vpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(vpos < kv_len, v, 0.0)
        _online_softmax_update(s, v, m_ref, l_ref, acc_ref)

    if causal:
        # skip K tiles strictly above the diagonal
        pl.when((ki * block_k) <= (qi * block_q + block_q - 1))(_compute)
    else:
        _compute()

    @pl.when(ki == k_steps - 1)
    def _flush():
        write_out(
            (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30))
        )


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, causal: bool,
    k_steps: int, block_q: int, block_k: int, kv_len: int
):
    def write_out(tile):
        o_ref[0] = tile.astype(o_ref.dtype)

    _flash_tile_body(
        lambda: q_ref[0], lambda: k_ref[0], lambda: v_ref[0],
        o_ref, m_ref, l_ref, acc_ref, write_out,
        qi=pl.program_id(1), ki=pl.program_id(2), causal=causal,
        k_steps=k_steps, block_q=block_q, block_k=block_k, kv_len=kv_len,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q/k/v: [BH, S, d] (batch·heads flattened). Arbitrary S; tails masked."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    k_steps = pl.cdiv(sk, block_k)
    grid = (bh, pl.cdiv(sq, block_q), k_steps)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel,
            causal=causal,
            k_steps=k_steps,
            block_q=block_q,
            block_k=block_k,
            kv_len=sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# GQA-native variant
# ---------------------------------------------------------------------------


def _gqa_flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, causal: bool,
    k_steps: int, block_q: int, block_k: int, kv_len: int
):
    def write_out(tile):
        o_ref[0, 0] = tile.astype(o_ref.dtype)

    # K/V tiles are shared across the group grid dim (axis 1)
    _flash_tile_body(
        lambda: q_ref[0, 0], lambda: k_ref[0], lambda: v_ref[0],
        o_ref, m_ref, l_ref, acc_ref, write_out,
        qi=pl.program_id(2), ki=pl.program_id(3), causal=causal,
        k_steps=k_steps, block_q=block_q, block_k=block_k, kv_len=kv_len,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def gqa_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q: [BKV, G, Sq, d]; k/v: [BKV, Sk, d] (batch·KV-heads flattened, G =
    H//KV query heads per KV head). The group is a parallel grid dim whose
    K/V BlockSpec ignores it — each K/V tile is fetched once per group, not
    repeated in HBM."""
    bkv, g, sq, d = q.shape
    sk = k.shape[1]
    k_steps = pl.cdiv(sk, block_k)
    grid = (bkv, g, pl.cdiv(sq, block_q), k_steps)
    return pl.pallas_call(
        functools.partial(
            _gqa_flash_kernel,
            causal=causal,
            k_steps=k_steps,
            block_q=block_q,
            block_k=block_k,
            kv_len=sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, gi, i, j: (b, gi, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, gi, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, gi, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b, gi, i, j: (b, gi, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((bkv, g, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)

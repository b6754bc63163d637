"""Packed variable-length (ragged) attention kernel: a flat ``[T]`` token
batch — decode singletons and prefill chunks from different sequences mixed
freely — against the batched head-major ``[B, KV, S_max, hd]`` decode
cache (see :mod:`repro.kernels.decode_attention` for why head-major).

This is the unified-dispatch serving hot path: ONE kernel serves every mix
of admission prefill chunks and decode steps, so the engine never has to
choose between stalling decode for a B=1 prefill and starving admissions.
Each packed token ``t`` carries a descriptor pair read via scalar prefetch:

* ``tok_slot[t]`` — which cache slot (batch row) the token belongs to;
* ``tok_pos[t]``  — its absolute sequence position. The token's K/V have
  already been scattered into the cache at ``(tok_slot, tok_pos)`` (the
  dispatch layer fuses that scatter), so key position ``p`` of the slot is
  valid iff ``p <= tok_pos`` — exactly the ``decode_attention`` convention,
  generalized from "one token per slot" to "any tokens, any slots". A
  prefill chunk is just consecutive tokens of one slot with increasing
  ``tok_pos``: the per-token bound makes the chunk causally exact, and
  chunk-vs-chunk boundaries need no special cases.

The grid is (token, KV head, S tiles); the query-head group rides inside
the block as a ``[G, hd]`` tile, and ``tok_slot`` indexes the cache fetch in
the BlockSpec index map — the packed batch never materializes a gathered
``[T, KV, S_max, hd]`` cache view. Tiles entirely past ``tok_pos`` (or
before the sliding window) are skipped via ``pl.when``, so decode tokens of
short sequences stay cheap inside a long-cache pack.

Padding tokens (pack ragged-to-bucket tail) should point at slot 0 with
``tok_pos >= S_max``: every tile stays live but the output row is ignored
by the caller, and the out-of-bounds scatter was already dropped upstream.

:func:`paged_ragged_attention` generalizes the same kernel from a dense
``[B, KV, S_max, hd]`` cache to a block-paged ``[num_blocks, KV,
block_size, hd]`` pool: the descriptor indirection ``(slot, pos)`` becomes
``(block, offset)`` by routing the BlockSpec's cache fetch through a
``[R, max_blocks]`` block table — S tile ``si`` of sequence row ``r``
streams from pool block ``block_tables[r, si]`` instead of cache row
``r``. Everything else (per-token causal bound, online softmax, tile
skipping) is IDENTICAL, which is the point: one kernel change carries
both packed prefill and the fused k-step decode chunks onto the paged
pool. Unallocated table entries hold the out-of-range sentinel
``num_blocks``; their tiles are provably dead (a request's table covers
every position ≤ its ``tok_pos``) and the index map clamps them in-range
so the prefetch never reads out of bounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import attend_tile, split_refs


def _ragged_kernel(slot_ref, pos_ref, *refs, quant: bool, **kw):
    # this token's absolute position; keys at p <= pos are valid (its own
    # K/V were scattered at pos before the kernel ran)
    attend_tile(pos_ref[pl.program_id(0)], *split_refs(refs, quant), **kw)


def _paged_kernel(seq_ref, pos_ref, btab_ref, *refs, quant: bool, **kw):
    # same compute as the dense-cache kernel: the paging lives entirely in
    # the BlockSpec index map (tile si already holds the positions
    # [si*block_s, (si+1)*block_s) of this token's sequence), and the block
    # table itself is only consumed there — the body never sees it. Scale
    # pools route through the SAME index map as their payload pools, so a
    # COW-shared block's scales are definitionally the ones fetched with it
    attend_tile(pos_ref[pl.program_id(0)], *split_refs(refs, quant), **kw)


@functools.partial(
    jax.jit, static_argnames=("window", "block_s", "interpret")
)
def ragged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    tok_slot: jax.Array,
    tok_pos: jax.Array,
    *,
    window: int = 0,
    block_s: int = 256,
    interpret: bool = False,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """q: [T, KV, G, d] packed queries; k/v: [B, KV, S_max, d] batched cache;
    tok_slot/tok_pos: [T] int32 per-token descriptors. With ``k_scale``/
    ``v_scale`` ([B, KV, S_max] f32) the cache may be int8 — tiles
    dequantize in-register inside the kernel.

    Returns [T, KV, G, d] attention outputs for every packed token."""
    t, kvh, g, d = q.shape
    s_max = k.shape[2]
    s_steps = pl.cdiv(s_max, block_s)
    grid = (t, kvh, s_steps)
    quant = k_scale is not None
    # the slot indirection lives in the index map: each token's K/V
    # tiles stream straight from its cache row, no [T, KV, S, d]
    # gather ever exists
    kv_spec = pl.BlockSpec(
        (1, 1, block_s, d),
        lambda ti, hi, si, slots, poss: (slots[ti], hi, si, 0),
    )
    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda ti, hi, si, slots, poss: (ti, hi, 0, 0)),
        kv_spec,
    ]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, kvh, block_s),
            lambda ti, hi, si, slots, poss: (slots[ti], 0, si),
        )
        in_specs += [scale_spec, kv_spec, scale_spec]
    else:
        in_specs.append(kv_spec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda ti, hi, si, slots, poss: (ti, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )

    tok_slot = jnp.asarray(tok_slot, jnp.int32)
    tok_pos = jnp.asarray(tok_pos, jnp.int32)
    operands = (
        (tok_slot, tok_pos, q, k, k_scale, v, v_scale)
        if quant
        else (tok_slot, tok_pos, q, k, v)
    )
    return pl.pallas_call(
        functools.partial(
            _ragged_kernel, quant=quant, block_s=block_s, s_steps=s_steps,
            window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, kvh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_ragged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    tok_seq: jax.Array,
    tok_pos: jax.Array,
    block_tables: jax.Array,
    *,
    window: int = 0,
    interpret: bool = False,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Packed ragged attention against a block-paged KV pool.

    q: [T, KV, G, d] packed queries; k/v: [num_blocks, KV, block_size, d]
    pool; tok_seq/tok_pos: [T] int32 — token t belongs to sequence row
    ``tok_seq[t]`` of ``block_tables`` at absolute position ``tok_pos[t]``;
    block_tables: [R, max_blocks] int32 mapping (sequence row, S tile) to a
    pool block (out-of-range sentinel = unallocated). The S tile size IS
    the pool's block_size — the pool layout already tiled the cache for
    the kernel, so no extra blocking choice exists on this path. With
    ``k_scale``/``v_scale`` ([num_blocks, KV, block_size] f32 scale
    pools) the payload pools may be int8: the scales ride the SAME
    block-table index map, so a COW-shared block always travels with the
    scales that describe it.

    Returns [T, KV, G, d] attention outputs for every packed token."""
    t, kvh, g, d = q.shape
    nb, block_s = k.shape[0], k.shape[2]
    s_steps = block_tables.shape[1]
    grid = (t, kvh, s_steps)
    quant = k_scale is not None

    def _block(ti, si, seqs, btab):
        # (slot, pos) -> (block, offset): the tile's pool block comes from
        # the sequence's table; clamp the unallocated sentinel in-range
        # (those tiles are masked dead by the position bound anyway)
        return jnp.minimum(btab[seqs[ti], si], nb - 1)

    kv_spec = pl.BlockSpec(
        (1, 1, block_s, d),
        lambda ti, hi, si, seqs, poss, btab: (_block(ti, si, seqs, btab), hi, 0, 0),
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, g, d), lambda ti, hi, si, seqs, poss, btab: (ti, hi, 0, 0)
        ),
        kv_spec,
    ]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, kvh, block_s),
            lambda ti, hi, si, seqs, poss, btab: (_block(ti, si, seqs, btab), 0, 0),
        )
        in_specs += [scale_spec, kv_spec, scale_spec]
    else:
        in_specs.append(kv_spec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda ti, hi, si, seqs, poss, btab: (ti, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )

    tok_seq = jnp.asarray(tok_seq, jnp.int32)
    tok_pos = jnp.asarray(tok_pos, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    operands = (
        (tok_seq, tok_pos, block_tables, q, k, k_scale, v, v_scale)
        if quant
        else (tok_seq, tok_pos, block_tables, q, k, v)
    )
    return pl.pallas_call(
        functools.partial(
            _paged_kernel, quant=quant, block_s=block_s, s_steps=s_steps,
            window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, kvh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)

"""Decode-attention parity: the batched GQA decode kernel (interpret mode)
and the grouped oracle vs per-slot dense_attention, including per-slot
cur_len, sliding window, and qk-norm through attention_decode."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.attention import (
    attention_decode,
    attention_init,
    dense_attention,
)
from repro.models.layers import apply_rope, rms_norm


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# ---------------------------------------------------------------------------
# Kernel-level parity: ops.decode_attention (interpret) vs dense_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("kv_heads", [2, 6])
def test_decode_kernel_vs_dense_oracle(rng, window, kv_heads):
    b, h, hd, s_max = 3, 6, 16, 40
    cur_len = np.array([0, 7, 33], np.int32)  # per-slot ragged lengths
    q = _rand(rng, (b, h, hd))
    k = _rand(rng, (b, kv_heads, s_max, hd))  # head-major cache
    v = _rand(rng, (b, kv_heads, s_max, hd))

    got = ops.decode_attention(
        q, k, v, jnp.asarray(cur_len), window=window, mode="interpret", block_s=16
    )
    got_ref = ops.decode_attention(
        q, k, v, jnp.asarray(cur_len), window=window, mode="ref"
    )

    # oracle: per slot, one query at absolute position cur_len against the
    # first cur_len+1 cache entries (dense_attention is GQA-native)
    for i in range(b):
        cur = int(cur_len[i])
        o = dense_attention(
            q[i][None, None],          # [1, 1, H, hd]
            k[i, :, : cur + 1].transpose(1, 0, 2)[None],  # [1, cur+1, KV, hd]
            v[i, :, : cur + 1].transpose(1, 0, 2)[None],
            causal=True,
            q_offset=cur,
            window=window,
        )[0, 0]
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(o), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(got_ref[i]), np.asarray(o), rtol=2e-4, atol=2e-4
        )


def test_decode_kernel_low_precision_cache(rng):
    """f8/bf16 cache storage: kernel upcasts to the query dtype."""
    b, h, kv, hd, s_max = 2, 4, 2, 16, 32
    cur = jnp.asarray([5, 17], jnp.int32)
    q = _rand(rng, (b, h, hd))
    k = _rand(rng, (b, kv, s_max, hd)).astype(jnp.bfloat16)
    v = _rand(rng, (b, kv, s_max, hd)).astype(jnp.bfloat16)
    got = ops.decode_attention(q, k, v, cur, mode="interpret", block_s=16)
    expect = ops.decode_attention(q, k, v, cur, mode="ref")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expect, np.float32),
        rtol=2e-2, atol=2e-2,
    )


@pytest.mark.parametrize("layer", [0, 2, 4])  # first, middle, last of L=5
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_decode_kernel_reads_stacked_cache_at_layer(rng, kv_dtype, layer):
    """The kernel on the stacked [L, B, KV, S, hd] cache at layer ``l``
    equals the oracle on that layer's own k[l], v[l] (and scales)."""
    n_layers, b, h, kv, hd, s_max = 5, 3, 6, 2, 16, 40
    cur = jnp.asarray([0, 9, 39], jnp.int32)
    q = _rand(rng, (b, h, hd))
    shape = (n_layers, b, kv, s_max, hd)
    if kv_dtype == "int8":
        k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02, shape[:-1]), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, shape[:-1]), jnp.float32)
        one = dict(k_scale=ks[layer], v_scale=vs[layer])
    else:
        k = _rand(rng, shape).astype(jnp.bfloat16)
        v = _rand(rng, shape).astype(jnp.bfloat16)
        ks = vs = None
        one = {}
    lyr = jnp.int32(layer)
    got = ops.decode_attention(
        q, k, v, cur, lyr, mode="interpret", block_s=16, k_scale=ks, v_scale=vs
    )
    expect = ops.decode_attention(q, k[layer], v[layer], cur, mode="ref", **one)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4
    )
    got_ref = ops.decode_attention(
        q, k, v, cur, lyr, mode="ref", k_scale=ks, v_scale=vs
    )
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(expect))


# ---------------------------------------------------------------------------
# Layer-level parity: attention_decode vs an independently-built oracle,
# with qk-norm and sliding window enabled
# ---------------------------------------------------------------------------


def _tiny_cfg(**kw):
    base = dict(
        name="tiny", family="dense", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab_size=64, dtype="float32",
        norm_eps=1e-5, rope_theta=10000.0,
    )
    base.update(kw)
    return ArchConfig(**base)


@pytest.mark.parametrize("qk_norm,window", [(False, 0), (True, 0), (True, 6)])
def test_attention_decode_vs_manual_oracle(rng, qk_norm, window):
    cfg = _tiny_cfg(qk_norm=qk_norm, sliding_window=window)
    params = attention_init(jax.random.key(0), cfg, jnp.float32)
    b, s_max = 2, 24
    cur_len = np.array([4, 15], np.int32)
    # pre-existing cache contents (as if prefilled)
    cache_k = _rand(rng, (b, cfg.n_kv_heads, s_max, cfg.head_dim))
    cache_v = _rand(rng, (b, cfg.n_kv_heads, s_max, cfg.head_dim))
    x = _rand(rng, (b, 1, cfg.d_model))

    out, new_k, new_v = attention_decode(
        params, cfg, x, cache_k, cache_v, jnp.asarray(cur_len)
    )

    # independent oracle: project, qk-norm, rope at the absolute position,
    # then per-slot dense attention over the updated cache prefix
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    if qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k_new = rms_norm(k_new, params["k_norm"], cfg.norm_eps)
    pos = jnp.asarray(cur_len)[:, None]
    q = apply_rope(q, pos, cfg.rope_theta)
    k_new = apply_rope(k_new, pos, cfg.rope_theta)

    for i in range(b):
        cur = int(cur_len[i])
        # the new token's k/v must have landed at index cur
        np.testing.assert_allclose(
            np.asarray(new_k[i, :, cur]), np.asarray(k_new[i, 0]), rtol=1e-5, atol=1e-6
        )
        ki = np.array(cache_k[i])  # writable copy, [KV, S, hd]
        ki[:, cur] = np.asarray(k_new[i, 0])
        o = dense_attention(
            q[i][None],
            jnp.asarray(ki[:, : cur + 1].transpose(1, 0, 2))[None],
            new_v[i, :, : cur + 1].transpose(1, 0, 2)[None],
            causal=True,
            q_offset=cur,
            window=window,
        )
        expect = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(expect[0]), rtol=2e-4, atol=2e-4
        )


def test_attention_decode_scalar_cur_len_matches_vector(rng):
    cfg = _tiny_cfg()
    params = attention_init(jax.random.key(1), cfg, jnp.float32)
    b, s_max = 2, 16
    cache_k = _rand(rng, (b, cfg.n_kv_heads, s_max, cfg.head_dim))
    cache_v = _rand(rng, (b, cfg.n_kv_heads, s_max, cfg.head_dim))
    x = _rand(rng, (b, 1, cfg.d_model))
    o1, k1, v1 = attention_decode(params, cfg, x, cache_k, cache_v, jnp.int32(5))
    o2, k2, v2 = attention_decode(
        params, cfg, x, cache_k, cache_v, jnp.asarray([5, 5], jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(k1), np.asarray(k2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5, atol=1e-6)


def test_scatter_step_writes_single_row(rng):
    from repro.models.attention import _scatter_step

    cache = jnp.zeros((3, 10, 2, 4), jnp.float32)
    new = _rand(rng, (3, 1, 2, 4))
    cur = jnp.asarray([0, 4, 9], jnp.int32)
    out = _scatter_step(cache, new, cur)
    for i, c in enumerate([0, 4, 9]):
        np.testing.assert_allclose(np.asarray(out[i, c]), np.asarray(new[i, 0]))
        rest = np.delete(np.asarray(out[i]), c, axis=0)
        assert np.all(rest == 0)


def test_dataclass_replace_configs_still_frozen():
    cfg = _tiny_cfg()
    cfg2 = dataclasses.replace(cfg, sliding_window=4)
    assert cfg2.sliding_window == 4 and cfg.sliding_window == 0

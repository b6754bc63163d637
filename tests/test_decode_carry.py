"""The dense-cache decode step carries the stacked ``[L, ...]`` cache
through its layer loop and writes one row per slot per layer in place.

Reference: a per-layer loop written here — a scan that hands each layer
its own slice of the cache and stacks the updated slices back, the way
the step moved the whole cache before — over a cache pre-filled with
random rows, so that every row the step must not write is visible. Logits
and every cache leaf must equal it bit for bit, for a few steps."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import LM

B, S_MAX, STEPS = 2, 12, 3

CASES = {
    "gqa-bf16": ("codeqwen1.5-7b", dict(dtype="bfloat16"), None),
    "gqa-int8": ("codeqwen1.5-7b", {}, "int8"),
    "mla": ("minicpm3-4b", {}, None),
    "moe-first-k-dense": ("deepseek-v2-lite-16b", dict(n_layers=3), None),
}


def _model(case):
    name, over, kv_dtype = CASES[case]
    cfg = dataclasses.replace(get_arch(name).reduced(), **over)
    m = LM(cfg)
    return m, m.init(jax.random.key(1)), kv_dtype


def _filled_cache(m, kv_dtype, seed=0):
    """A cache whose every row holds random data (scales too)."""
    cache = m.init_cache(B, S_MAX, kv_dtype=kv_dtype)
    keys = jax.random.split(jax.random.key(seed), len(cache))
    out = {}
    for k, (name, leaf) in zip(keys, sorted(cache.items())):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            out[name] = jax.random.randint(k, leaf.shape, -127, 128).astype(leaf.dtype)
        elif name.endswith("_scale"):
            out[name] = jax.random.uniform(k, leaf.shape, leaf.dtype, 0.01, 0.05)
        else:
            out[name] = jax.random.normal(k, leaf.shape, leaf.dtype)
    return out


def _reference_step(m, params, cache, tokens, cur_len):
    """decode_step as a per-layer loop over sliced-out layer caches."""
    from repro.models.layers import embed_tokens, rms_norm, unembed

    cfg = m.cfg

    def body(x, xs):
        blk, cl = xs
        return m._block_decode(blk, x, cl, cur_len)

    x = embed_tokens(params["embed"], tokens)
    if cfg.family == "moe" and cfg.first_k_dense:
        kd = cfg.first_k_dense
        x, nd = jax.lax.scan(
            body, x, (params["dense_blocks"], jax.tree.map(lambda c: c[:kd], cache))
        )
        x, nm = jax.lax.scan(
            body, x, (params["moe_blocks"], jax.tree.map(lambda c: c[kd:], cache))
        )
        cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), nd, nm)
    else:
        blocks = params["blocks"] if cfg.family == "dense" else params["moe_blocks"]
        x, cache = jax.lax.scan(body, x, (blocks, cache))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params["embed"], x), cache


@pytest.mark.parametrize("lens", ["scalar", "per-slot"])
@pytest.mark.parametrize("case", list(CASES))
def test_carried_decode_step_matches_per_layer_loop(case, lens):
    m, params, kv_dtype = _model(case)
    cache = ref_cache = _filled_cache(m, kv_dtype)
    # per-slot lengths reach the cache's end: the last write clamps into
    # range, as the per-layer dynamic_update_slice does
    cur = (
        jnp.int32(4) if lens == "scalar"
        else jnp.asarray([1, S_MAX - 2], jnp.int32)
    )
    toks = jax.random.randint(jax.random.key(2), (STEPS, B, 1), 0, m.cfg.vocab_size)
    step = jax.jit(m.decode_step)
    ref = jax.jit(lambda p, c, t, n: _reference_step(m, p, c, t, n))
    for t in range(STEPS):
        lg, cache = step(params, cache, {"tokens": toks[t]}, cur)
        rlg, ref_cache = ref(params, ref_cache, toks[t], cur)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(rlg))
        for name in ref_cache:
            np.testing.assert_array_equal(
                np.asarray(cache[name]), np.asarray(ref_cache[name]),
                err_msg=name,
            )
        cur = cur + 1


@pytest.mark.parametrize("case", list(CASES))
def test_carried_decode_step_writes_only_its_rows(case):
    """One step changes exactly the row at (layer, slot, cur_len[slot]) of
    every layer: every other row of every leaf is left bit for bit."""
    m, params, kv_dtype = _model(case)
    cache = _filled_cache(m, kv_dtype)
    cur = np.array([3, 7], np.int32)
    tok = jnp.zeros((B, 1), jnp.int32)
    _, new = jax.jit(m.decode_step)(params, cache, {"tokens": tok}, jnp.asarray(cur))
    for name, leaf in cache.items():
        old, got = np.asarray(leaf), np.asarray(new[name])
        pos_axis = 3 if name in ("k", "v", "k_scale", "v_scale") else 2
        written = np.zeros(old.shape, bool)
        for b, p in enumerate(cur):
            idx = [slice(None)] * old.ndim
            idx[1], idx[pos_axis] = b, p
            written[tuple(idx)] = True
        np.testing.assert_array_equal(got[~written], old[~written], err_msg=name)
        assert not np.array_equal(got[written], old[written]), name

"""Packed variable-length (ragged) attention: interpret-mode Pallas kernel
vs the jnp oracle for pure-decode, pure-prefill-chunk, and mixed packs
(GQA grouping, per-slot lengths, sliding windows, bucket padding), and the
packed model step vs the full prefill path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels import ops, ref
from repro.models import LM

F32 = jnp.float32


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _rand(rng, shape, dtype=F32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _cache(rng, b, s_max, kv, d):  # head-major [B, KV, S_max, d]
    return _rand(rng, (b, kv, s_max, d)), _rand(rng, (b, kv, s_max, d))


def _check(q, k, v, tok_slot, tok_pos, *, window=0, block_s=16, n_real=None):
    """interpret-mode kernel vs oracle on the [T, H, d] dispatch layout.
    ``n_real`` limits the comparison to the pack's real tokens — bucket
    padding rows (pos >= S_max) are contractually ignored by callers."""
    got = ops.ragged_attention(
        q, k, v, tok_slot, tok_pos, window=window,
        mode="interpret", block_s=block_s,
    )
    want = ops.ragged_attention(q, k, v, tok_slot, tok_pos, window=window, mode="ref")
    n = len(tok_slot) if n_real is None else n_real
    np.testing.assert_allclose(
        np.asarray(got)[:n], np.asarray(want)[:n], rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("h,kv", [(4, 4), (6, 2)])  # MHA and GQA grouping
def test_pure_decode_pack_matches_oracle(rng, h, kv):
    b, s_max, d = 3, 40, 16
    k, v = _cache(rng, b, s_max, kv, d)
    q = _rand(rng, (b, h, d))
    tok_slot = jnp.arange(b, dtype=jnp.int32)
    tok_pos = jnp.asarray([5, 17, 33], jnp.int32)  # ragged per-slot lengths
    _check(q, k, v, tok_slot, tok_pos)


def test_pure_decode_pack_matches_decode_attention(rng):
    """A pack of one token per slot at cur_len IS batched decode attention."""
    b, s_max, h, kv, d = 3, 40, 4, 2, 16
    k, v = _cache(rng, b, s_max, kv, d)
    q = _rand(rng, (b, h, d))
    cur = jnp.asarray([5, 17, 33], jnp.int32)
    got = ops.ragged_attention(
        q, k, v, jnp.arange(b, dtype=jnp.int32), cur, mode="ref"
    )
    want = ops.decode_attention(q, k, v, cur, mode="ref")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2)])
def test_prefill_chunk_pack_matches_oracle(rng, h, kv):
    """A prefill chunk: consecutive positions of one slot, mid-cache."""
    b, s_max, d = 2, 48, 16
    k, v = _cache(rng, b, s_max, kv, d)
    t = 9
    q = _rand(rng, (t, h, d))
    tok_slot = jnp.full((t,), 1, jnp.int32)
    tok_pos = jnp.arange(12, 12 + t, dtype=jnp.int32)
    _check(q, k, v, tok_slot, tok_pos)


@pytest.mark.parametrize("window", [0, 8])
def test_mixed_pack_matches_oracle(rng, window):
    """Decode singletons + a prefill chunk + bucket padding in one pack."""
    b, s_max, h, kv, d = 3, 40, 4, 2, 16
    k, v = _cache(rng, b, s_max, kv, d)
    # slots 0/2 decode at their cur_len; slot 1 prefills positions 4..9;
    # two padding tokens point at slot 0 past max_len
    tok_slot = jnp.asarray([0, 2, 1, 1, 1, 1, 1, 1, 0, 0], jnp.int32)
    tok_pos = jnp.asarray([7, 21, 4, 5, 6, 7, 8, 9, s_max, s_max], jnp.int32)
    q = _rand(rng, (len(tok_slot), h, d))
    _check(q, k, v, tok_slot, tok_pos, window=window, n_real=8)


def test_prefill_chunk_is_causally_exact(rng):
    """Chunked packed attention over a scattered cache equals one-shot full
    causal attention over the same sequence."""
    from repro.models.attention import dense_attention

    s, h, kv, d = 12, 4, 2, 16
    s_max = 32
    kseq = _rand(rng, (1, s, kv, d))
    vseq = _rand(rng, (1, s, kv, d))
    q = _rand(rng, (1, s, h, d))
    want = dense_attention(q, kseq, vseq, causal=True)  # [1, S, H, d]

    kc = jnp.zeros((2, kv, s_max, d), F32).at[1, :, :s].set(kseq[0].transpose(1, 0, 2))
    vc = jnp.zeros((2, kv, s_max, d), F32).at[1, :, :s].set(vseq[0].transpose(1, 0, 2))
    got = jnp.concatenate([
        ops.ragged_attention(
            q[0, st : st + 4], kc, vc,
            jnp.full((min(4, s - st),), 1, jnp.int32),
            jnp.arange(st, min(st + 4, s), dtype=jnp.int32),
            mode="ref",
        )
        for st in range(0, s, 4)
    ])  # three chunks of 4
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want[0]), rtol=2e-4, atol=2e-4
    )


def test_ref_oracle_padding_rows_are_finite(rng):
    """Bucket-padding tokens (pos >= S_max) must not poison the pack."""
    b, s_max, h, kv, d = 2, 16, 4, 2, 8
    k, v = _cache(rng, b, s_max, kv, d)
    q = _rand(rng, (3, h, d))
    out = ops.ragged_attention(
        q, k, v,
        jnp.asarray([0, 1, 0], jnp.int32),
        jnp.asarray([3, 5, s_max], jnp.int32),
        mode="ref",
    )
    assert bool(jnp.isfinite(out).all())


def test_packed_step_matches_prefill_and_decode():
    """LM.packed_step chunked over a prompt reproduces the full prefill's
    cache and last-token logits, then decodes like decode_step."""
    cfg = get_arch("codeqwen1.5-7b").reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=7).astype(np.int32)
    b, s_max = 2, 24

    logits_ref, one_cache = jax.jit(lambda pp, bb: m.prefill(pp, bb, s_max))(
        p, {"tokens": jnp.asarray(prompt)[None]}
    )
    cache = m.init_cache(b, s_max)
    step = jax.jit(m.packed_step)
    last = None
    for st in range(0, len(prompt), 3):
        chunk = prompt[st : st + 3]
        logits, cache = step(
            p, cache, jnp.asarray(chunk),
            jnp.full((len(chunk),), 1, jnp.int32),
            jnp.arange(st, st + len(chunk), dtype=jnp.int32),
        )
        last = logits[-1]
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(logits_ref[0, len(prompt) - 1]),
        rtol=1e-4, atol=1e-4,
    )
    # the scattered cache row equals the prefill cache
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache[key][:, 1, :, : len(prompt)]),
            np.asarray(one_cache[key][:, 0, :, : len(prompt)]),
            rtol=1e-5, atol=1e-5,
        )

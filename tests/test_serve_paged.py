"""Block-paged KV serving: the paged pool + block-table indirection must be
BIT-IDENTICAL to the dense per-slot cache for greedy streams — under
mid-stream admissions, cancellation, prefix reuse and pool pressure — and
the paged Pallas kernel must match the gather oracle. The dense engine is
the reference everywhere: paged mode is an opt-in memory layout, never a
numerics change."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.modes import Mode
from repro.kernels import ops
from repro.models import LM
from repro.serve import Request, SamplingParams, ServeCluster, ServeEngine

F32 = jnp.float32


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("codeqwen1.5-7b").reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    return cfg, m, p


# ------------------------------------------------------------------ kernel


def _rand(rng, shape, dtype=F32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2)])
def test_paged_kernel_matches_oracle(h, kv):
    """Interpret-mode paged Pallas kernel vs the gather oracle on a mixed
    pack (decode singletons + a prefill chunk) over a fragmented pool."""
    rng = np.random.default_rng(0)
    nb, bs, d, maxb = 7, 8, 16, 3  # rows address up to 24 positions
    pool_k = _rand(rng, (nb, kv, bs, d))  # head-major blocks
    pool_v = _rand(rng, (nb, kv, bs, d))
    # two requests with deliberately scrambled, partial tables (sentinel nb
    # marks unallocated tail entries)
    btab = jnp.asarray([[4, 1, 6], [0, 5, nb]], jnp.int32)
    tok_seq = jnp.asarray([0, 1, 1, 1, 1], jnp.int32)
    tok_pos = jnp.asarray([20, 9, 10, 11, 12], jnp.int32)
    q = _rand(rng, (5, h, d))
    got = ops.paged_ragged_attention(
        q, pool_k, pool_v, tok_seq, tok_pos, btab, mode="interpret"
    )
    want = ops.paged_ragged_attention(
        q, pool_k, pool_v, tok_seq, tok_pos, btab, mode="ref"
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_paged_oracle_matches_dense_with_identity_tables():
    """An identity block table makes the pool a re-blocking of the dense
    cache: the paged oracle must agree with the dense ragged oracle."""
    rng = np.random.default_rng(1)
    b, s_max, h, kv, d, bs = 2, 24, 4, 2, 16, 8
    k = _rand(rng, (b, kv, s_max, d))
    v = _rand(rng, (b, kv, s_max, d))
    maxb = s_max // bs

    def to_pool(x):  # [B, KV, maxb*bs, d] -> [B*maxb, KV, bs, d]
        x = x.reshape(b, kv, maxb, bs, d).transpose(0, 2, 1, 3, 4)
        return x.reshape(b * maxb, kv, bs, d)

    pool_k, pool_v = to_pool(k), to_pool(v)
    btab = jnp.arange(b * maxb, dtype=jnp.int32).reshape(b, maxb)
    tok_seq = jnp.asarray([0, 1, 1], jnp.int32)
    tok_pos = jnp.asarray([7, 13, 14], jnp.int32)
    q = _rand(rng, (3, h, d))
    got = ops.paged_ragged_attention(
        q, pool_k, pool_v, tok_seq, tok_pos, btab, mode="ref"
    )
    want = ops.ragged_attention(q, k, v, tok_seq, tok_pos, mode="ref")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


# ------------------------------------------------------------------ engine


def _prompts(cfg, sizes, seed=11):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, cfg.vocab_size, size=s).astype(np.int32) for s in sizes
    ]


def _serve(m, p, prompts, *, max_new=6, slots=2, max_len=64,
           prefill_budget=16, **kw):
    eng = ServeEngine(
        m, p, batch_slots=slots, max_len=max_len,
        prefill_budget=prefill_budget, **kw,
    )
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr, params=SamplingParams(max_new=max_new)))
    eng.run()
    return eng, {r.rid: r.generated for r in eng.finished}


def test_paged_engine_bit_identical_to_dense(small_model):
    """More requests than slots (mid-stream admissions as slots churn), a
    prompt longer than the prefill budget (chunked feeding): every greedy
    stream must match the dense engine token-for-token."""
    cfg, m, p = small_model
    prompts = _prompts(cfg, (6, 13, 40, 9, 21))
    _, dense = _serve(m, p, prompts)
    eng, paged = _serve(m, p, prompts, kv_block_size=8)
    assert paged == dense
    # every block went back to the free list when its request finished
    assert eng.pool.free == eng.num_blocks


def test_bf16_dense_and_paged_engines_serve():
    """The chip serves in bf16, the config's own dtype, which ``reduced()``
    replaces with f32: serve the reduced codeqwen config in bf16 through
    the unified dense and paged engines — fused admission, a chunked
    prompt longer than the prefill budget, decode to ``max_new`` — so a
    dtype fault surfaces here before it costs chip time."""
    import dataclasses

    cfg = dataclasses.replace(get_arch("codeqwen1.5-7b").reduced(), dtype="bfloat16")
    m = LM(cfg)
    p = m.init_params(0)
    assert {leaf.dtype for leaf in jax.tree.leaves(p)} == {jnp.dtype(jnp.bfloat16)}
    prompts = _prompts(cfg, (5, 40, 17))
    dense_eng, dense = _serve(m, p, prompts, max_new=4)
    paged_eng, paged = _serve(m, p, prompts, max_new=4, kv_block_size=8)
    for eng in (dense_eng, paged_eng):
        assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(eng.cache))
    assert sorted(dense) == [0, 1, 2]
    assert all(len(toks) == 4 for toks in dense.values())
    assert all(0 <= t < cfg.vocab_size for toks in dense.values() for t in toks)
    assert paged == dense  # the CPU paths gather into the dense oracle


@pytest.mark.parametrize("block", [None, 8])
def test_lower_dispatch_programs(small_model, block):
    """``lower_dispatch`` lowers the hot tick and packed programs against
    the live operands without running them (the engine state is
    untouched); off-TPU they carry the jnp oracles, not the Pallas call."""
    cfg, m, p = small_model
    eng = ServeEngine(m, p, batch_slots=2, max_len=64, kv_block_size=block)
    progs = eng.lower_dispatch()
    assert sorted(progs) == ["packed", "tick"]
    for lowered in progs.values():
        assert "tpu_custom_call" not in lowered.as_text()
    eng.submit(Request(rid=0, prompt=_prompts(cfg, (6,))[0],
                       params=SamplingParams(max_new=2)))
    eng.run()  # the lowered-only engine still serves
    assert [len(r.generated) for r in eng.finished] == [2]


def test_paged_engine_cancellation_bit_identity(small_model):
    """Mid-stream cancellation frees the cancelled request's blocks and
    must not perturb any other stream (ISSUE acceptance: bit-identical
    under mid-stream admissions + cancellation)."""
    cfg, m, p = small_model
    prompts = _prompts(cfg, (6, 9, 13, 7), seed=23)
    _, dense = _serve(m, p, prompts, max_new=8)

    eng = ServeEngine(m, p, batch_slots=2, max_len=64, prefill_budget=16,
                      kv_block_size=8)
    handles = [
        eng.submit(Request(rid=i, prompt=pr, params=SamplingParams(max_new=8)))
        for i, pr in enumerate(prompts)
    ]
    it = iter(handles[0])
    next(it)
    next(it)  # requests 0/1 are mid-stream on the two slots
    handles[1].cancel()
    eng.run()
    got = {r.rid: r.generated for r in eng.finished}
    cancelled = [r for r in eng.finished if r.finish_reason == "cancelled"]
    assert [r.rid for r in cancelled] == [1]
    # the cancelled stream got a PREFIX of its uncancelled tokens; every
    # surviving stream is bit-identical to dense
    assert got[1] == dense[1][: len(got[1])]
    for rid in (0, 2, 3):
        assert got[rid] == dense[rid]
    assert eng.pool.free == eng.num_blocks  # cancel leaked nothing


def test_paged_prefix_reuse_identity_and_hits(small_model):
    """A shared system prompt: the radix tree must skip its full blocks on
    later admissions (hits recorded) while every stream stays identical to
    prefix-off serving."""
    cfg, m, p = small_model
    rng = np.random.default_rng(3)
    sys_p = rng.integers(0, cfg.vocab_size, size=24).astype(np.int32)
    prompts = [
        np.concatenate([sys_p, rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)])
        for _ in range(4)
    ]
    _, off = _serve(m, p, prompts, kv_block_size=8)
    eng, on = _serve(m, p, prompts, kv_block_size=8, prefix_cache=True)
    assert on == off
    st = eng.prefix.stats()
    assert st.hits >= 1 and st.hit_tokens >= 24  # >= one full shared prefix
    # the tree retains its nodes (resident for future admissions), each
    # holding exactly the tree's own reference
    assert eng.pool.used == st.nodes
    assert all(c in (0, 1) for c in eng.pool.refcount.tolist())


def test_paged_cow_boundary_divergence(small_model):
    """Prompts diverging MID-block share only the blocks before the
    divergence (block-aligned COW: no mid-block copy, no cross-talk) and
    still match prefix-off serving bit-for-bit."""
    cfg, m, p = small_model
    rng = np.random.default_rng(5)
    head = rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)  # 2.5 blocks
    tails = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32) for _ in range(3)]
    prompts = [np.concatenate([head, t]) for t in tails]
    _, off = _serve(m, p, prompts, kv_block_size=8, slots=1)
    eng, on = _serve(m, p, prompts, kv_block_size=8, slots=1, prefix_cache=True)
    assert on == off
    st = eng.prefix.stats()
    # only the 2 FULL head blocks (16 tokens) are shareable; the half
    # block where streams diverge is recomputed privately per request
    assert st.hits == 2 and st.hit_tokens == 2 * 16


def test_paged_pool_exhaustion_admission_waits(small_model):
    """A pool too small for every request's worst case: admission makes
    the overflow requests WAIT (recorded as alloc pressure), everything
    still finishes, outputs identical, nothing leaks."""
    cfg, m, p = small_model
    prompts = _prompts(cfg, (24, 26, 25, 27), seed=9)
    _, dense = _serve(m, p, prompts, slots=4)
    # each request needs ceil((len+6)/8) = 4 blocks; 9 blocks admit at
    # most two residents despite 4 free slots
    eng, paged = _serve(m, p, prompts, slots=4, kv_block_size=8, num_blocks=9)
    assert paged == dense
    assert eng.pool.alloc_failures >= 1  # pressure was actually exercised
    assert eng.pool.free == eng.num_blocks


def test_paged_submit_infeasible_request_raises(small_model):
    cfg, m, p = small_model
    eng = ServeEngine(m, p, batch_slots=2, max_len=64, kv_block_size=8,
                      num_blocks=2)
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(Request(rid=0, prompt=_prompts(cfg, (20,))[0],
                           params=SamplingParams(max_new=30)))


def test_paged_requires_unified_and_divisibility(small_model):
    cfg, m, p = small_model
    with pytest.raises(ValueError, match="multiple"):
        ServeEngine(m, p, max_len=60, kv_block_size=8)
    with pytest.raises(ValueError, match="kv_block_size"):
        ServeEngine(m, p, max_len=64, prefix_cache=True)


def test_paged_prewarm_then_serve(small_model):
    """prewarm() on a paged engine (all-sentinel tables: warmup dispatches
    drop every write) must leave serving bit-identical."""
    cfg, m, p = small_model
    prompts = _prompts(cfg, (6, 9), seed=31)
    _, dense = _serve(m, p, prompts, max_len=32, max_chunk=2)
    eng = ServeEngine(m, p, batch_slots=2, max_len=32, prefill_budget=16,
                      max_chunk=2, kv_block_size=8)
    eng.prewarm()
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr, params=SamplingParams(max_new=6)))
    eng.run()
    assert {r.rid: r.generated for r in eng.finished} == dense


def test_paged_reset_roundtrip(small_model):
    """reset() returns a paged engine to full capacity (pool, prefix tree
    and block tables included) and reserving runs reproduce exactly."""
    cfg, m, p = small_model
    prompts = _prompts(cfg, (6, 9, 13), seed=17)
    eng = ServeEngine(m, p, batch_slots=2, max_len=64, prefill_budget=16,
                      kv_block_size=8, prefix_cache=True)
    def run():
        for i, pr in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=pr,
                               params=SamplingParams(max_new=6)))
        eng.run()
        out = {r.rid: r.generated for r in eng.finished}
        return out
    first = run()
    eng.reset()
    assert eng.pool.free == eng.num_blocks
    assert eng.prefix.stats().nodes == 0
    assert run() == first


def test_paged_cluster_mid_stream_reconfigure(small_model):
    """A paged cluster surviving a mid-stream reconfigure: outputs stay
    bit-identical to a dense engine, and every fabric's pool ends
    refcount-consistent (blocks held only by each engine's prefix tree)."""
    cfg, m, p = small_model
    sizes = (5, 23, 11, 8, 17, 7)
    reqs = lambda: [  # noqa: E731 — fresh Request objects per consumer
        Request(rid=i, prompt=pr, params=SamplingParams(max_new=4))
        for i, pr in enumerate(_prompts(cfg, sizes, seed=21))
    ]
    ref_eng = ServeEngine(m, p, batch_slots=2, max_len=48)
    for r in reqs():
        ref_eng.submit(r)
    ref_eng.run()
    ref = {r.rid: r.generated for r in ref_eng.finished}

    cl = ServeCluster(m, p, mode=Mode.SPLIT, batch_slots=2, max_len=48,
                      kv_block_size=8, prefix_cache=True)
    arrivals = [(i * 0.002, r) for i, r in enumerate(reqs())]
    stats = cl.run(arrivals=arrivals,
                   reconfigure_schedule=[(0.005, Mode.MERGE)])
    assert {r.rid: r.generated for r in cl.finished} == ref
    assert len(stats.reconfigures) == 1
    for engines in cl._fabrics.values():
        for e in engines:
            st = e.prefix.stats()
            assert e.pool.used == st.nodes  # only tree refs remain
            assert all(c in (0, 1) for c in e.pool.refcount.tolist())

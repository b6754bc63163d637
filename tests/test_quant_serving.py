"""Quantized serving: int8 KV cache (in-kernel dequant, per-(position, head)
scales resident in the cache pytree), int8 weight serving, and dtype-aware
byte accounting.

These are the ENFORCEABLE invariants behind the report-only ``_quant_``
bench rows (see benchmarks/check_regression.py): the f32 lane is bit-exact,
int8 quality stays inside the TV / greedy-agreement gates, capacity really
is byte-accounted, and the quantized cache composes with every serving
feature (paged pool, prefix COW, speculation, split/merge reconfigure).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_sampling import N_DRAWS, _draw, _tv, oracle_probs

from repro.configs import get_arch
from repro.core.modes import Mode
from repro.dist.compression import dequantize_rows, quantize_rows
from repro.kernels import ops
from repro.kernels.autotune import cache_key
from repro.models import LM
from repro.models.quant import is_quantized, quantize_params, qweight
from repro.serve import Request, SamplingParams, ServeCluster, ServeEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("codeqwen1.5-7b").reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    return cfg, m, p


def _reqs(cfg, sizes, *, max_new=4, seed=21, prefix=None, **pkw):
    """Fresh Request objects each call (requests are mutated in-flight), so
    the same (sizes, seed) always replays the identical stream."""
    rng = np.random.default_rng(seed)
    out = []
    for i, s in enumerate(sizes):
        prompt = rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
        if prefix is not None:
            prompt = np.concatenate([prefix, prompt]).astype(np.int32)
        out.append(
            Request(rid=i, prompt=prompt, params=SamplingParams(max_new=max_new, **pkw))
        )
    return out


def _serve(m, p, reqs, **kw):
    eng = ServeEngine(m, p, **kw)
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    return {r.rid: r.generated for r in eng.finished}, stats, eng


# --------------------------------------------------- row-quant primitive


def test_quantize_rows_error_bound_and_sign():
    rng = np.random.default_rng(0)
    x = jnp.asarray(3.0 * rng.standard_normal((5, 7, 16)), jnp.float32)
    q, s = quantize_rows(x, jnp.int8)
    assert q.dtype == jnp.int8 and s.shape == (5, 7) and s.dtype == jnp.float32
    deq = np.asarray(dequantize_rows(q, s))
    err = np.abs(deq - np.asarray(x))
    bound = np.asarray(s)[..., None] / 2 + 1e-6  # round-to-nearest half-ULP
    assert (err <= bound).all(), err.max()
    # symmetric codebook: sign survives wherever |x| clears one step
    big = np.abs(np.asarray(x)) > np.asarray(s)[..., None]
    assert (np.sign(deq)[big] == np.sign(np.asarray(x))[big]).all()


def test_quantize_rows_zero_row_safe():
    x = jnp.zeros((3, 4, 8), jnp.float32)
    q, s = quantize_rows(x, jnp.int8)
    assert (np.asarray(s) > 0).all()  # amax=0 rows fall back to scale=1/127*?
    assert (np.asarray(dequantize_rows(q, s)) == 0).all()


# ----------------------------------------------- kernels: in-kernel dequant


def _quant_kv(rng, b, s, kv, d):  # head-major [B, KV, S, d], scales [B, KV, S]
    k = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv, s, d)), jnp.float32)
    kq, ks = quantize_rows(k, jnp.int8)
    vq, vs = quantize_rows(v, jnp.int8)
    return (kq, ks, vq, vs)


def test_decode_attention_q8_matches_dequant_oracle():
    rng = np.random.default_rng(1)
    b, s, kv, g, d = 2, 32, 2, 2, 16
    q = jnp.asarray(rng.standard_normal((b, kv * g, d)), jnp.float32)
    kq, ks, vq, vs = _quant_kv(rng, b, s, kv, d)
    cur = jnp.asarray([7, 29], jnp.int32)
    ref_q8 = ops.decode_attention(q, kq, vq, cur, mode="ref", k_scale=ks, v_scale=vs)
    ref_deq = ops.decode_attention(
        q, dequantize_rows(kq, ks), dequantize_rows(vq, vs), cur, mode="ref"
    )
    np.testing.assert_allclose(ref_q8, ref_deq, rtol=1e-6, atol=1e-6)
    got = ops.decode_attention(
        q, kq, vq, cur, mode="interpret", block_s=16, k_scale=ks, v_scale=vs
    )
    np.testing.assert_allclose(got, ref_q8, rtol=2e-4, atol=2e-4)


def test_ragged_attention_q8_matches_dequant_oracle():
    rng = np.random.default_rng(2)
    b, s, kv, g, d, t = 3, 32, 2, 2, 16, 10
    q = jnp.asarray(rng.standard_normal((t, kv * g, d)), jnp.float32)
    kq, ks, vq, vs = _quant_kv(rng, b, s, kv, d)
    slots = jnp.asarray(rng.integers(0, b, size=t), jnp.int32)
    poss = jnp.asarray(rng.integers(0, s, size=t), jnp.int32)
    ref_q8 = ops.ragged_attention(
        q, kq, vq, slots, poss, mode="ref", k_scale=ks, v_scale=vs
    )
    ref_deq = ops.ragged_attention(
        q, dequantize_rows(kq, ks), dequantize_rows(vq, vs), slots, poss, mode="ref"
    )
    np.testing.assert_allclose(ref_q8, ref_deq, rtol=1e-6, atol=1e-6)
    got = ops.ragged_attention(
        q, kq, vq, slots, poss, mode="interpret", block_s=16, k_scale=ks, v_scale=vs
    )
    np.testing.assert_allclose(got, ref_q8, rtol=2e-4, atol=2e-4)


def test_paged_attention_q8_matches_dequant_oracle():
    rng = np.random.default_rng(3)
    nb, bs, kv, g, d, b = 8, 8, 2, 2, 16, 2
    q = jnp.asarray(rng.standard_normal((b, kv * g, d)), jnp.float32)
    kq, ks, vq, vs = _quant_kv(rng, nb, bs, kv, d)  # pool layout [NB, KV, bs, d]
    tables = jnp.arange(nb, dtype=jnp.int32).reshape(b, nb // b)
    cur = jnp.asarray([9, 27], jnp.int32)
    ref_q8 = ops.paged_decode_attention(
        q, kq, vq, cur, tables, mode="ref", k_scale=ks, v_scale=vs
    )
    ref_deq = ops.paged_decode_attention(
        q, dequantize_rows(kq, ks), dequantize_rows(vq, vs), cur, tables, mode="ref"
    )
    np.testing.assert_allclose(ref_q8, ref_deq, rtol=1e-6, atol=1e-6)
    got = ops.paged_decode_attention(
        q, kq, vq, cur, tables, mode="interpret", k_scale=ks, v_scale=vs
    )
    np.testing.assert_allclose(got, ref_q8, rtol=2e-4, atol=2e-4)


def test_matmul_q8_matches_ref():
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.standard_normal((48, 40)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((40, 24)), jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(amax > 0, amax, 1.0) / 127.0
    q8 = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    ref_out = ops.matmul_q8(a, q8, scale, mode="ref")
    np.testing.assert_allclose(
        ref_out, a @ (q8.astype(jnp.float32) * scale), rtol=1e-5, atol=1e-5
    )
    got = ops.matmul_q8(a, q8, scale, mode="interpret", block=16)
    np.testing.assert_allclose(got, ref_out, rtol=2e-4, atol=2e-4)


def test_autotune_cache_key_kv_dtype_component():
    base = cache_key("decode_attention", (2, 64, 2, 16), jnp.float32, "cpu")
    q8 = cache_key(
        "decode_attention", (2, 64, 2, 16), jnp.float32, "cpu", kv_dtype=jnp.int8
    )
    assert base != q8 and q8 == base + "|kvint8"  # old keys unchanged


# ------------------------------------------- engine: f32 identity lane


def test_engine_kv_f32_lane_bit_identical(small_model):
    """kv_dtype='f32' keeps the full scale machinery (scale leaves, chunked
    admission, quantize_rows identity lane) yet streams bit-identically to
    the plain scale-less engine."""
    cfg, m, p = small_model
    sizes = (5, 11, 8, 14)
    base, _, _ = _serve(m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32)
    ident, _, eng = _serve(
        m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32, kv_dtype="f32"
    )
    assert ident == base
    assert "k_scale" in eng.cache and eng.cache["k"].dtype == jnp.float32


def test_engine_kv_f32_lane_paged_prefix_bit_identical(small_model):
    cfg, m, p = small_model
    sizes = (5, 11, 8)
    base, _, _ = _serve(
        m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32, kv_block_size=8
    )
    ident, _, _ = _serve(
        m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32, kv_block_size=8,
        prefix_cache=True, kv_dtype="f32",
    )
    assert ident == base


def test_engine_rejects_kv_dtype_on_legacy_path(small_model):
    cfg, m, p = small_model
    with pytest.raises(ValueError, match="unified"):
        ServeEngine(m, p, batch_slots=2, max_len=32, unified=False, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeEngine(m, p, batch_slots=2, max_len=32, kv_dtype="bf16")


# --------------------------------------------- engine: int8 quality gates


def test_engine_int8_greedy_agreement(small_model):
    """The steady greedy scenario, teacher-forced: replay the fp32 engine's
    streams through fp32 and int8 caches and compare every argmax decision.
    The >= 99% acceptance gate applies to decisions whose fp32 top-2 margin
    clears the measured int8 noise floor — on this RANDOM-INIT reduced model
    ~15% of steps are sub-0.03 near-ties that no 8-bit cache (or bf16, or a
    different matmul order) can pin down; a trained model's margins put
    virtually every step above the floor. Overall agreement is bounded too,
    and the logit perturbation itself is pinned."""
    cfg, m, p = small_model
    sizes = (5, 8, 11, 13, 16, 19, 23, 27)
    base, _, eng = _serve(
        m, p, _reqs(cfg, sizes, max_new=12), batch_slots=4, max_len=48,
        kv_dtype="int8",
    )
    assert eng.cache["k"].dtype == jnp.int8
    assert all(len(t) == 12 for t in base.values())
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32) for s in sizes]
    total = agree = decided = decided_agree = 0
    max_err = 0.0
    for i, pr in enumerate(prompts):
        seq = jnp.asarray(np.concatenate([pr, np.asarray(base[i], np.int32)]))
        t = len(seq)
        slot = jnp.zeros((t,), jnp.int32)
        pos = jnp.arange(t, dtype=jnp.int32)
        rows = jnp.arange(len(pr) - 1, t - 1, dtype=jnp.int32)  # decision points
        lf, _ = m.packed_step(p, m.init_cache(1, 64), seq, slot, pos, out_rows=rows)
        lq, _ = m.packed_step(
            p, m.init_cache(1, 64, kv_dtype=jnp.int8), seq, slot, pos, out_rows=rows
        )
        lf, lq = np.asarray(lf), np.asarray(lq)
        max_err = max(max_err, float(np.abs(lf - lq).max()))
        srt = np.sort(lf, axis=-1)
        margin = srt[:, -1] - srt[:, -2]
        same = lf.argmax(-1) == lq.argmax(-1)
        total += len(same)
        agree += int(same.sum())
        clear = margin > 0.03  # ~2x the observed noise floor
        decided += int(clear.sum())
        decided_agree += int(same[clear].sum())
    assert max_err < 0.05, max_err  # int8 KV perturbs logits by ~1e-2 here
    assert decided >= total // 2  # the gate must actually cover the run
    assert decided_agree / decided >= 0.99, (
        f"greedy agreement {decided_agree}/{decided} above the noise floor"
    )
    assert agree / total >= 0.9, f"overall agreement {agree}/{total}"


def test_int8_kv_sampling_tv_under_gate(small_model):
    """Sampling quality gate: 20k draws from the next-token distribution
    computed over an int8 KV cache stay within TV < 0.05 of the fp32
    renormalized-softmax oracle (reusing test_sampling's oracle/draw
    helpers). top-k bounds the support so binomial noise at N_DRAWS is
    ~0.01 — the budget is almost entirely quantization error."""
    cfg, m, p = small_model
    rng = np.random.default_rng(7)
    t = 24
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, size=t), jnp.int32)
    slot = jnp.zeros((t,), jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32)
    last = jnp.asarray([t - 1], jnp.int32)

    cache_f = m.init_cache(1, 32)
    logits_f, _ = m.packed_step(p, cache_f, prompt, slot, pos, out_rows=last)
    cache_q = m.init_cache(1, 32, kv_dtype=jnp.int8)
    logits_q, _ = m.packed_step(p, cache_q, prompt, slot, pos, out_rows=last)
    # positive control: the f32 store lane reproduces the plain logits bitwise
    cache_i = m.init_cache(1, 32, kv_dtype=jnp.float32)
    logits_i, _ = m.packed_step(p, cache_i, prompt, slot, pos, out_rows=last)
    assert (np.asarray(logits_i) == np.asarray(logits_f)).all()

    sp = SamplingParams(max_new=1, temperature=0.8, top_k=16, top_p=0.95)
    draws = _draw(np.asarray(logits_q[0]), sp, n=N_DRAWS)
    counts = np.bincount(draws, minlength=cfg.vocab_size)
    probs = oracle_probs(np.asarray(logits_f[0]), sp)
    tv = _tv(counts, probs)
    assert tv < 0.05, f"TV(int8 draws, fp32 oracle) = {tv:.4f}"


# --------------------------------------------------- composition: features


def test_quant_paged_matches_quant_dense(small_model):
    """int8 through the paged pool == int8 through the dense cache: the
    pool's block-shaped scale leaves carry the same values the dense
    [B, S, KV] leaves do."""
    cfg, m, p = small_model
    sizes = (5, 11, 8, 14)
    dense, _, _ = _serve(
        m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32, kv_dtype="int8"
    )
    paged, _, eng = _serve(
        m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32, kv_block_size=8,
        kv_dtype="int8",
    )
    assert paged == dense
    assert eng.cache["k"].dtype == jnp.int8 and "k_scale" in eng.cache


def test_quant_prefix_cow_scales_travel(small_model):
    """Prefix-cache hits on an int8 pool reuse quantized blocks AND their
    scale rows: streams match a no-prefix int8 engine bit-for-bit while the
    radix tree actually serves hits (scales travel with the shared blocks
    through COW re-reference)."""
    cfg, m, p = small_model
    shared = np.arange(1, 17, dtype=np.int32)  # 16-token shared system prefix
    sizes = (5, 7, 9, 6)
    base, _, _ = _serve(
        m, p, _reqs(cfg, sizes, prefix=shared, max_new=6),
        batch_slots=2, max_len=64, kv_block_size=8, kv_dtype="int8",
    )
    got, _, eng = _serve(
        m, p, _reqs(cfg, sizes, prefix=shared, max_new=6),
        batch_slots=2, max_len=64, kv_block_size=8, kv_dtype="int8",
        prefix_cache=True,
    )
    assert got == base
    assert eng.prefix.stats().hit_tokens > 0


def test_quant_speculative_bit_identical(small_model):
    """ngram speculation over an int8 cache commits the same greedy streams
    as int8 without speculation (verify reads the same quantized rows)."""
    cfg, m, p = small_model
    sizes = (6, 10, 8)
    base, _, _ = _serve(
        m, p, _reqs(cfg, sizes, max_new=10), batch_slots=2, max_len=48,
        kv_dtype="int8",
    )
    spec, stats, _ = _serve(
        m, p, _reqs(cfg, sizes, max_new=10), batch_slots=2, max_len=48,
        kv_dtype="int8", speculate="ngram",
    )
    assert spec == base
    assert stats.spec_ticks > 0


def test_quant_cluster_mid_stream_reconfigure(small_model):
    """int8 KV + int8 weights survive a mid-stream SPLIT->MERGE drain/
    re-home/resume with streams bit-identical to an uninterrupted int8
    engine (both fabrics quantize identically, so a re-homed request's
    re-prefill lands in an equivalently-quantized cache)."""
    cfg, m, p = small_model
    sizes = (5, 11, 8, 14, 7)
    ref, _, _ = _serve(
        m, p, _reqs(cfg, sizes, max_new=6), batch_slots=2, max_len=48,
        kv_dtype="int8", weight_dtype="int8",
    )
    cl = ServeCluster(
        m, p, mode=Mode.SPLIT, batch_slots=2, max_len=48,
        kv_dtype="int8", weight_dtype="int8",
    )
    arrivals = [(i * 0.002, r) for i, r in enumerate(_reqs(cfg, sizes, max_new=6))]
    stats = cl.run(arrivals=arrivals, reconfigure_schedule=[(0.005, Mode.MERGE)])
    assert {r.rid: r.generated for r in cl.finished} == ref
    assert len(stats.reconfigures) == 1
    assert stats.kv_bytes_resident > 0


# ------------------------------------------------------- byte accounting


def test_paged_bytes_per_block_is_measured(small_model):
    """bytes_per_block comes from the actual pool leaves, never a
    slots*f32 assumption: f32 = L*2*bs*KV*hd*4; int8 adds the f32 scale
    rows but still lands ~3.2x lighter at hd=16."""
    cfg, m, p = small_model
    L, kv, hd, bs = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, 8
    _, _, e32 = _serve(
        m, p, _reqs(cfg, (5,)), batch_slots=2, max_len=32, kv_block_size=bs
    )
    assert e32.pool.bytes_per_block == L * 2 * bs * kv * hd * 4
    _, _, e8 = _serve(
        m, p, _reqs(cfg, (5,)), batch_slots=2, max_len=32, kv_block_size=bs,
        kv_dtype="int8",
    )
    assert e8.pool.bytes_per_block == L * 2 * (bs * kv * hd + bs * kv * 4)
    assert e8.pool.bytes_per_block * 3 < e32.pool.bytes_per_block


def test_kv_bytes_resident_reported(small_model):
    cfg, m, p = small_model
    sizes = (5, 11, 8)
    _, s32, e32 = _serve(m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32)
    _, s8, e8 = _serve(
        m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32, kv_dtype="int8"
    )
    # dense residency is the whole preallocated cache, dtype-aware
    assert s32.kv_bytes_resident == e32.kv_bytes_resident() > 0
    assert s8.kv_bytes_resident == e8.kv_bytes_resident() > 0
    assert s8.kv_bytes_resident * 3 < s32.kv_bytes_resident
    # paged residency peaks with pool occupancy and returns to 0 on drain
    _, sp, ep = _serve(
        m, p, _reqs(cfg, sizes), batch_slots=2, max_len=32, kv_block_size=8,
        kv_dtype="int8",
    )
    assert sp.kv_bytes_resident > 0
    assert sp.kv_bytes_resident % ep.pool.bytes_per_block == 0
    assert ep.pool.used == 0 and ep.kv_bytes_resident() == 0
    assert ep.pool.stats().kv_bytes_resident == 0


# ------------------------------------------------------- weight serving


def test_quantize_params_identity_and_structure(small_model):
    cfg, m, p = small_model
    assert quantize_params(p, None) is p
    assert quantize_params(p, "f32") is p
    qp = quantize_params(p, "int8")
    wq = qp["blocks"]["attn"]["wq"]
    assert is_quantized(wq) and wq["q8"].dtype == jnp.int8
    assert wq["scale"].dtype == jnp.float32
    # non-matmul leaves ride through untouched (same array objects; the
    # containers are rebuilt by the tree walk)
    for sub in ("embed", "final_norm"):
        assert all(
            a is b
            for a, b in zip(jax.tree.leaves(qp[sub]), jax.tree.leaves(p[sub]))
        ), sub
    # qweight read-through: dequant error bounded by half a step,
    # f32 leaves pass through unchanged
    w = np.asarray(p["blocks"]["attn"]["wq"])
    deq = np.asarray(qweight(wq))
    assert (np.abs(deq - w) <= np.asarray(wq["scale"]) / 2 + 1e-6).all()
    assert qweight(p["blocks"]["attn"]["wq"]) is p["blocks"]["attn"]["wq"]


def test_quantize_params_moe_router_stays_dense():
    cfg = get_arch("llama4-scout-17b-a16e").reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    qp = quantize_params(p, "int8")
    moe = qp["moe_blocks"]["moe"]
    assert is_quantized(moe["w_in"]) and is_quantized(moe["w_out"])
    assert not is_quantized(moe["router"])  # tiny, accuracy-critical
    assert moe["router"].dtype == jnp.float32


def test_weight_int8_serves_and_shrinks(small_model):
    """int8 weight serving runs the full engine path, the quantized block
    stack is ~4x lighter, and teacher-forced argmax decisions above the
    noise floor agree >= 99% with fp32 weights (same margin-aware gate as
    the KV test — random-init margins are full of near-ties)."""
    from repro.common.utils import pytree_bytes

    cfg, m, p = small_model
    qp = quantize_params(p, "int8")
    assert pytree_bytes(qp["blocks"]) * 3 < pytree_bytes(p["blocks"])
    sizes = (5, 8, 11, 13)
    q8, _, _ = _serve(
        m, p, _reqs(cfg, sizes, max_new=8), batch_slots=2, max_len=32,
        weight_dtype="int8",
    )
    assert all(len(t) == 8 for t in q8.values())
    rng = np.random.default_rng(9)
    seq = jnp.asarray(rng.integers(0, cfg.vocab_size, size=32), jnp.int32)
    slot = jnp.zeros((32,), jnp.int32)
    pos = jnp.arange(32, dtype=jnp.int32)
    lf, _ = m.packed_step(p, m.init_cache(1, 32), seq, slot, pos)
    lq, _ = m.packed_step(qp, m.init_cache(1, 32), seq, slot, pos)
    lf, lq = np.asarray(lf), np.asarray(lq)
    srt = np.sort(lf, axis=-1)
    clear = (srt[:, -1] - srt[:, -2]) > 0.05  # weight quant noise > KV's
    same = lf.argmax(-1) == lq.argmax(-1)
    assert clear.sum() >= 16, int(clear.sum())
    assert same[clear].mean() >= 0.99, (
        f"weight-int8 argmax agreement {int(same[clear].sum())}/{int(clear.sum())}"
    )


# ------------------------------------------------------- fp8 storage lane


def test_fp8_row_roundtrip_tighter_than_int8():
    """float8_e4m3fn rows round-trip through quantize_rows/dequantize_rows
    with bounded relative error; near-zero rows survive (scale floors at
    1/448 like the int8 lane floors at 1/127)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(6, 32)) * 3.0, jnp.float32)
    q, s = quantize_rows(x, jnp.float8_e4m3fn)
    assert q.dtype == jnp.float8_e4m3fn and s.shape == (6,)
    back = dequantize_rows(q, s)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    assert float(jnp.max(jnp.abs(back - x) / amax)) < 0.04  # e4m3: ~2^-3 rel
    z = jnp.zeros((2, 8), jnp.float32)
    qz, sz = quantize_rows(z, jnp.float8_e4m3fn)
    assert float(jnp.abs(dequantize_rows(qz, sz)).max()) == 0.0


def test_engine_kv_fp8_greedy_agreement(small_model):
    """kv_dtype='fp8' (float8_e4m3fn rows behind the same per-row scale
    machinery) serves full streams and its teacher-forced argmax decisions
    agree with fp32 above the same noise-floor gate as int8. The logit
    perturbation bound is LOOSER than int8's: e4m3's ~2^-4 relative step
    on large elements exceeds int8's uniform amax/254 step — fp8's win is
    dynamic range on small elements, not peak accuracy."""
    cfg, m, p = small_model
    sizes = (5, 8, 11, 13, 16, 19)
    base, _, eng = _serve(
        m, p, _reqs(cfg, sizes, max_new=10), batch_slots=4, max_len=48,
        kv_dtype="fp8",
    )
    assert eng.cache["k"].dtype == jnp.float8_e4m3fn
    assert "k_scale" in eng.cache
    assert all(len(t) == 10 for t in base.values())
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32) for s in sizes]
    total = agree = decided = decided_agree = 0
    max_err = 0.0
    for i, pr in enumerate(prompts):
        seq = jnp.asarray(np.concatenate([pr, np.asarray(base[i], np.int32)]))
        t = len(seq)
        slot = jnp.zeros((t,), jnp.int32)
        pos = jnp.arange(t, dtype=jnp.int32)
        rows = jnp.arange(len(pr) - 1, t - 1, dtype=jnp.int32)
        lf, _ = m.packed_step(p, m.init_cache(1, 64), seq, slot, pos, out_rows=rows)
        lq, _ = m.packed_step(
            p, m.init_cache(1, 64, kv_dtype=jnp.float8_e4m3fn), seq, slot,
            pos, out_rows=rows,
        )
        lf, lq = np.asarray(lf), np.asarray(lq)
        max_err = max(max_err, float(np.abs(lf - lq).max()))
        srt = np.sort(lf, axis=-1)
        margin = srt[:, -1] - srt[:, -2]
        same = lf.argmax(-1) == lq.argmax(-1)
        total += len(same)
        agree += int(same.sum())
        clear = margin > 0.03
        decided += int(clear.sum())
        decided_agree += int(same[clear].sum())
    assert max_err < 0.2, max_err  # e4m3 KV perturbs logits ~1e-1 here
    assert decided >= total // 2
    assert decided_agree / decided >= 0.99, (
        f"fp8 greedy agreement {decided_agree}/{decided} above the floor"
    )


def test_fp8_dtype_aliases_and_bytes(small_model):
    """Every fp8 alias normalizes to float8_e4m3fn, and the byte
    accounting sees 1-byte rows + f32 scales (same residency as int8)."""
    cfg, m, p = small_model
    aliases = ("f8", "fp8", "float8", "float8_e4m3", "float8_e4m3fn")
    engines = [
        ServeEngine(m, p, batch_slots=2, max_len=32, kv_dtype=a)
        for a in aliases
    ]
    assert all(e.cache["k"].dtype == jnp.float8_e4m3fn for e in engines)
    e8 = engines[0]
    ei = ServeEngine(m, p, batch_slots=2, max_len=32, kv_dtype="int8")
    ef = ServeEngine(m, p, batch_slots=2, max_len=32, kv_dtype="f32")
    assert e8.kv_bytes_resident() == ei.kv_bytes_resident()
    assert e8.kv_bytes_resident() < ef.kv_bytes_resident()

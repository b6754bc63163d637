"""Hot-path jaxpr inspection: the ops dispatch layer must not materialize
``jnp.pad`` copies (tail handling lives in the kernels), and the GQA
attention paths must not materialize the H//KV-fold K/V expansion."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import attention as attn_mod


def _top_level_primitives(fn, *args):
    """Primitive names of the traced fn's TOP-LEVEL jaxpr equations — the
    dispatch layer itself, not the Pallas kernel bodies."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]


@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (100, 70, 130)])
def test_matmul_dispatch_issues_no_pad(m, k, n):
    a = jnp.zeros((m, k), jnp.float32)
    b = jnp.zeros((k, n), jnp.float32)
    prims = _top_level_primitives(
        lambda x, y: ops.matmul(x, y, mode="interpret", block=32), a, b
    )
    assert "pad" not in prims, prims


@pytest.mark.parametrize("s", [64, 96, 100])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_dispatch_issues_no_pad(s, causal):
    q = jnp.zeros((2, 2, s, 16), jnp.float32)
    prims = _top_level_primitives(
        lambda x: ops.flash_attention(
            x, x, x, causal=causal, mode="interpret", block=32
        ),
        q,
    )
    assert "pad" not in prims, prims


def test_softmax_rmsnorm_axpy_dotp_dispatch_no_pad():
    x = jnp.zeros((37, 130), jnp.float32)  # ragged both dims
    w = jnp.zeros((130,), jnp.float32)
    v = jnp.zeros((5000,), jnp.float32)
    for fn, args in [
        (lambda a: ops.softmax(a, mode="interpret", block_rows=16), (x,)),
        (lambda a, b: ops.rmsnorm(a, b, mode="interpret", block_rows=16), (x, w)),
        (lambda a: ops.axpy(2.0, a, a, mode="interpret", block=256), (x,)),
        (lambda a: ops.dotp(a, a, mode="interpret", block=256), (v,)),
    ]:
        prims = _top_level_primitives(fn, *args)
        assert "pad" not in prims, prims


@pytest.mark.parametrize("h", [10, 13])  # divisible and ragged H_out
def test_conv2d_dispatch_issues_no_pad(h):
    x = jnp.zeros((2, h, 9, 4), jnp.float32)
    w = jnp.zeros((3, 3, 4, 8), jnp.float32)
    prims = _top_level_primitives(
        lambda a, b: ops.conv2d(a, b, mode="interpret", block_h=4), x, w
    )
    assert "pad" not in prims, prims


@pytest.mark.parametrize("h", [10, 13])
def test_conv2d_masked_grid_matches_ref(h):
    """The shifted-tail-tile grid must stay exact on ragged H (the bug the
    old padded wrapper worked around)."""
    import numpy as np

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, h, 9, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 8)), jnp.float32)
    got = ops.conv2d(x, w, mode="interpret", block_h=4)
    import numpy.testing as npt

    npt.assert_allclose(
        np.asarray(got), np.asarray(ops.conv2d(x, w, mode="ref")),
        rtol=2e-4, atol=2e-4,
    )


def test_ragged_attention_dispatch_no_pad():
    q = jnp.zeros((10, 6, 16), jnp.float32)  # ragged T
    k = jnp.zeros((3, 2, 40, 16), jnp.float32)  # head-major, ragged S_max
    slots = jnp.zeros((10,), jnp.int32)
    poss = jnp.zeros((10,), jnp.int32)
    prims = _top_level_primitives(
        lambda a, b, c, d: ops.ragged_attention(
            a, b, b, c, d, mode="interpret", block_s=16
        ),
        q, k, slots, poss,
    )
    # the kernel reads the cache in place: no pad, no relayout copy
    assert "pad" not in prims and "transpose" not in prims, prims


def test_decode_attention_dispatch_no_pad():
    q = jnp.zeros((3, 6, 16), jnp.float32)
    k = jnp.zeros((3, 2, 40, 16), jnp.float32)  # head-major cache
    cur = jnp.zeros((3,), jnp.int32)
    prims = _top_level_primitives(
        lambda a, b, c: ops.decode_attention(
            a, b, b, c, mode="interpret", block_s=16
        ),
        q, k, cur,
    )
    assert "pad" not in prims and "transpose" not in prims, prims


@pytest.mark.parametrize("n", [2048, 2500, 700])  # whole, ragged, tail-only
def test_compression_quantize_dequantize_no_pad(n):
    """The int8 compressor jits into serving ticks and the compressed-DP
    train step: the body + tail split must never materialize a jnp.pad
    copy of the gradient/cache tensor."""
    from repro.dist import compression as comp

    x = jnp.zeros((n,), jnp.float32)
    prims = _top_level_primitives(lambda a: comp.quantize(a)[0], x)
    assert "pad" not in prims, prims
    q, s = comp.quantize(x)
    prims = _top_level_primitives(lambda a: comp.dequantize(a, s), q)
    assert "pad" not in prims, prims


def test_quantize_rows_no_pad_and_identity_lane():
    """Insert-time KV row quantization: pad-free in both lanes, and the
    f32 store lane is the exact identity (values untouched, ones scales)."""
    from repro.dist import compression as comp

    x = jnp.zeros((7, 3, 16), jnp.float32)
    for dt in (jnp.int8, jnp.float32):
        prims = _top_level_primitives(
            lambda a: comp.quantize_rows(a, dt)[0], x
        )
        assert "pad" not in prims, prims
    v, s = comp.quantize_rows(x, jnp.float32)
    assert v is x and s.shape == (7, 3)


def _gqa_cfg():
    return ArchConfig(
        name="tiny", family="dense", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab_size=64, dtype="float32",
    )


def test_attention_apply_never_calls_repeat_kv(monkeypatch):
    cfg = _gqa_cfg()
    params = attn_mod.attention_init(jax.random.key(0), cfg, jnp.float32)

    def boom(x, groups):
        raise AssertionError("_repeat_kv materialized in attention_apply")

    monkeypatch.setattr(attn_mod, "_repeat_kv", boom)
    x = jnp.zeros((1, 8, cfg.d_model), jnp.float32)
    out = attn_mod.attention_apply(params, cfg, x, jnp.arange(8, dtype=jnp.int32))
    assert out.shape == (1, 8, cfg.d_model)


def test_attention_decode_never_calls_repeat_kv(monkeypatch):
    cfg = _gqa_cfg()
    params = attn_mod.attention_init(jax.random.key(0), cfg, jnp.float32)

    def boom(x, groups):
        raise AssertionError("_repeat_kv materialized in attention_decode")

    monkeypatch.setattr(attn_mod, "_repeat_kv", boom)
    x = jnp.zeros((2, 1, cfg.d_model), jnp.float32)
    ck = jnp.zeros((2, cfg.n_kv_heads, 16, cfg.head_dim), jnp.float32)
    out, _, _ = attn_mod.attention_decode(
        params, cfg, x, ck, ck, jnp.asarray([3, 5], jnp.int32)
    )
    assert out.shape == (2, 1, cfg.d_model)


def test_gqa_flash_no_head_expansion_in_jaxpr():
    """No top-level intermediate may carry an H-headed K/V: every broadcast
    to [*, H(=4)-headed, S, d] K/V layout would show up as a broadcast eqn
    whose output has 4 on the head axis with S=33 alongside."""
    b, h, kv, s, d = 1, 4, 2, 33, 16
    q = jnp.zeros((b, h, s, d), jnp.float32)
    k = jnp.zeros((b, kv, s, d), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a, c: ops.gqa_flash_attention(a, c, c, mode="interpret", block_q=16, block_k=16)
    )(q, k)
    expanded_kv_shape = (b * h, s, d)  # what a repeat would produce
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name in ("broadcast_in_dim", "concatenate"):
            for out in eqn.outvars:
                assert tuple(out.aval.shape) != expanded_kv_shape, eqn


def _all_primitives(jaxpr):
    """Primitive names of EVERY equation, recursing into sub-jaxprs
    (scan/cond/pjit bodies) — unlike _top_level_primitives, which stops at
    the dispatch layer."""
    names = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            names.add(eqn.primitive.name)
        stack.extend(sj for sj in jax.core.subjaxprs(j))
    return names


def test_greedy_spec_verify_program_is_threefry_and_sort_free():
    """The smode-0 (all-greedy) speculative verify program must be argmax
    prefix agreement only: no threefry PRNG, no sort anywhere in the
    traced program — greedy speculation costs exactly the packed model
    step. The sampled variant (smode 1) is the positive control."""
    from functools import partial

    import numpy as np

    from repro.configs import get_arch
    from repro.models import LM
    from repro.serve import ServeEngine

    cfg = get_arch("codeqwen1.5-7b").reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    eng = ServeEngine(m, p, batch_slots=2, max_len=32, speculate="ngram")
    pack = np.zeros((3, eng.B * 3 + eng.B), np.int32)  # desc cols + meta cols
    pack[2, : eng.B * 3] = eng.max_len
    spf, spi, btok, bval = eng._sp0
    args = (
        eng.params, eng.cache, eng._last_tok, eng._cur_len,
        jnp.asarray(pack), spf, spi, btok, bval,
    )
    def _prng(n):  # typed-key primitives trace as random_*; raw as threefry*
        return "threefry" in n or n.startswith("random_")

    greedy = _all_primitives(
        jax.make_jaxpr(partial(eng._spec_fn, depth_k=2, smode=0))(*args).jaxpr
    )
    assert not any(_prng(n) for n in greedy), sorted(greedy)
    assert "sort" not in greedy, sorted(greedy)
    sampled = _all_primitives(
        jax.make_jaxpr(partial(eng._spec_fn, depth_k=2, smode=1))(*args).jaxpr
    )
    assert any(_prng(n) for n in sampled), sorted(sampled)


def _scan_eqns(jaxpr):
    """Every ``scan`` equation of the program, in sub-jaxprs too."""
    stack, found = [jaxpr], []
    while stack:
        j = stack.pop()
        found += [e for e in j.eqns if e.primitive.name == "scan"]
        stack.extend(jax.core.subjaxprs(j))
    return found


@pytest.mark.parametrize(
    "name,kv_dtype",
    [
        ("codeqwen1.5-7b", None),  # GQA
        ("codeqwen1.5-7b", "int8"),  # GQA, quantized rows + scales
        ("minicpm3-4b", None),  # MLA latents
        ("deepseek-v2-lite-16b", None),  # MoE + MLA + first_k_dense
    ],
)
def test_decode_step_carries_the_stacked_cache(monkeypatch, name, kv_dtype):
    """No scan of a dense-cache decode step takes a cache leaf (or a layer
    slice of one) in its ``xs`` or returns one in its ``ys``: the layer
    loop carries the stacked cache and writes its rows in place. The GQA
    attention call receives the whole stacked leaf and a layer index."""
    from repro.configs import get_arch
    from repro.models import LM

    cfg = get_arch(name).reduced()
    m = LM(cfg)
    params = m.init(jax.random.key(0))
    cache = m.init_cache(2, 24, kv_dtype=kv_dtype)
    per_layer = {tuple(c.shape[1:]) for c in jax.tree.leaves(cache)}
    seen = []

    def spy(q, k, v, cur_len, layer=None, **kw):
        seen.append((k.shape, layer is not None))
        return real(q, k, v, cur_len, layer, **kw)

    real = ops.decode_attention
    monkeypatch.setattr(ops, "decode_attention", spy)
    jaxpr = jax.make_jaxpr(m.decode_step)(
        params, cache, {"tokens": jnp.zeros((2, 1), jnp.int32)},
        jnp.zeros((2,), jnp.int32),
    ).jaxpr
    scans = _scan_eqns(jaxpr)
    assert scans
    for eqn in scans:
        n_in = eqn.params["num_consts"] + eqn.params["num_carry"]
        xs, ys = eqn.invars[n_in:], eqn.outvars[eqn.params["num_carry"]:]
        for var in (*xs, *ys):
            assert tuple(var.aval.shape[1:]) not in per_layer, var.aval
    assert not any(
        e.primitive.name == "concatenate" for e in jaxpr.eqns
    ), "the stacked cache is split and joined again"
    if cfg.mla is None:
        assert seen and all(
            s == cache["k"].shape and has_layer for s, has_layer in seen
        ), seen

"""Model-level correctness: decode==forward, prefill+decode==forward,
per-family behaviours (MLA absorbed decode, MoE aux, hybrid tying)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models import LM

FAMS = [
    "codeqwen1.5-7b",  # GQA (4 KV heads; one when reduced)
    "qwen3-32b",  # GQA + qk_norm
    "minicpm3-4b",  # MLA (q_lora)
    "deepseek-v2-lite-16b",  # MoE + MLA + first_k_dense
    "falcon-mamba-7b",  # mamba1
    "zamba2-2.7b",  # hybrid mamba2 + shared attn
]


def _toks(cfg, b, s, key=2):
    return jax.random.randint(jax.random.key(key), (b, s), 0, cfg.vocab_size)


@pytest.mark.parametrize("name", FAMS)
def test_decode_matches_forward(name):
    cfg = get_arch(name).reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(1))
    B, S = 2, 8
    toks = _toks(cfg, B, S)
    full, _ = jax.jit(m.forward)(p, {"tokens": toks})
    cache = m.init_cache(B, S)
    step = jax.jit(m.decode_step)
    outs = []
    for t in range(S):
        lg, cache = step(p, cache, {"tokens": toks[:, t : t + 1]}, jnp.int32(t))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    scale = float(jnp.max(jnp.abs(full)))
    assert float(jnp.max(jnp.abs(dec - full))) / scale < 2e-2


@pytest.mark.parametrize("name", FAMS)
def test_prefill_then_decode_matches_forward(name):
    cfg = get_arch(name).reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(1))
    B, S, EXTRA = 2, 6, 3
    toks = _toks(cfg, B, S + EXTRA)
    full, _ = jax.jit(m.forward)(p, {"tokens": toks})
    lg, cache = jax.jit(lambda pp, bb: m.prefill(pp, bb, S + EXTRA))(
        p, {"tokens": toks[:, :S]}
    )
    scale = float(jnp.max(jnp.abs(full)))
    assert float(jnp.max(jnp.abs(lg - full[:, :S]))) / scale < 2e-2
    step = jax.jit(m.decode_step)
    for t in range(S, S + EXTRA):
        lg1, cache = step(p, cache, {"tokens": toks[:, t : t + 1]}, jnp.int32(t))
        err = float(jnp.max(jnp.abs(lg1[:, 0] - full[:, t]))) / scale
        assert err < 2e-2, (name, t, err)


def test_moe_aux_loss_positive():
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    _, aux = jax.jit(m.forward)(p, {"tokens": _toks(cfg, 2, 16)})
    # Switch-style balance loss is ≥ 1 per layer at perfect balance
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert float(aux) >= 0.9 * n_moe


def test_hybrid_shared_block_is_tied():
    cfg = get_arch("zamba2-2.7b").reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    # exactly ONE attention block's worth of shared params
    assert "shared" in p
    assert p["shared"]["attn"]["wq"].ndim == 3  # not L-stacked


def test_audio_stub_embeds_path():
    cfg = get_arch("musicgen-large").reduced()
    m = LM(cfg)
    p = m.init(jax.random.key(0))
    x = jnp.ones((2, 12, cfg.d_model), jnp.float32)
    logits, _ = jax.jit(m.forward)(p, {"embeds": x})
    assert logits.shape == (2, 12, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_f8_kv_cache_decode_close():
    """float8 KV storage (serving memory optimization) stays within a few %
    of the bf16-cache logits."""
    import dataclasses

    cfg = dataclasses.replace(
        get_arch("qwen3-32b").reduced(), kv_cache_dtype="float8_e4m3fn"
    )
    m = LM(cfg)
    p = m.init(jax.random.key(1))
    B, S = 2, 8
    toks = _toks(cfg, B, S)
    full, _ = jax.jit(m.forward)(p, {"tokens": toks})
    cache = m.init_cache(B, S)
    assert jax.tree.leaves(cache)[0].dtype == jnp.float8_e4m3fn
    step = jax.jit(m.decode_step)
    errs = []
    for t in range(S):
        lg, cache = step(p, cache, {"tokens": toks[:, t : t + 1]}, jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full[:, t]))))
    assert max(errs) / float(jnp.max(jnp.abs(full))) < 8e-2


def test_param_specs_match_init():
    cfg = get_arch("qwen3-32b").reduced()
    m = LM(cfg)
    specs = m.param_specs()
    params = m.init(jax.random.key(0))
    s_flat, s_def = jax.tree_util.tree_flatten(specs)
    p_flat, p_def = jax.tree_util.tree_flatten(params)
    assert s_def == p_def
    for s, pp in zip(s_flat, p_flat):
        assert s.shape == pp.shape and s.dtype == pp.dtype

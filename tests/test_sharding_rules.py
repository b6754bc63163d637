"""Sharding-rule unit tests, including the L-dim regression that once cost
6×7 GB of involuntary all-gathers (caught in the dry-run artifact)."""

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import _add_fsdp_dim, spec_for_param
from repro.roofline.analysis import RooflineTerms, parse_collectives

M = 16  # model-axis size


def test_stacked_dense_mlp_never_shards_layer_dim():
    # [L, d, f] — the regression: -3 must NOT hit dim 0
    spec = spec_for_param("['blocks']['mlp']['w_in']", 3, (32, 4096, 13440), M)
    assert spec == P(None, None, "model")
    spec = spec_for_param("['blocks']['mlp']['w_out']", 3, (32, 13440, 4096), M)
    assert spec == P(None, "model", None)


def test_moe_experts_shard_expert_dim():
    spec = spec_for_param("['moe_blocks']['moe']['w_in']", 4, (27, 64, 2048, 1408), M)
    assert spec == P(None, "model", None, None)
    # shared expert inside the moe subtree is a plain MLP
    spec = spec_for_param(
        "['moe_blocks']['moe']['shared']['w_in']", 3, (27, 2048, 2816), M
    )
    assert spec == P(None, None, "model")


def test_gqa_kv_divisibility_fallback():
    # kv=8 < 16 -> falls through to head_dim 128
    spec = spec_for_param("['blocks']['attn']['wk']", 4, (88, 12288, 8, 128), M)
    assert spec == P(None, None, None, "model")
    # kv=32 divisible -> heads dim
    spec = spec_for_param("['blocks']['attn']['wk']", 4, (32, 4096, 32, 128), M)
    assert spec == P(None, None, "model", None)


def test_model_size_one_replicates():
    spec = spec_for_param("['blocks']['mlp']['w_in']", 3, (32, 4096, 13440), 1)
    assert spec == P()


def test_vocab_fallback_to_dmodel():
    # minicpm3: 73448 % 16 != 0 -> shard d_model instead
    spec = spec_for_param("['embed']['tok']", 2, (73448, 2560), M)
    assert spec == P(None, "model")
    spec = spec_for_param("['embed']['tok']", 2, (32768, 4096), M)
    assert spec == P("model", None)


def test_fsdp_adds_data_dim_above_threshold():
    from repro.dist.sharding import MeshInfo
    from jax.sharding import Mesh
    import jax as _jax

    mesh = Mesh(np.array(_jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    info = MeshInfo(mesh)
    # big leaf (pretend data axis of size 1 divides everything): dim 0 (L)
    # must be skipped, another dim picked
    spec = _add_fsdp_dim(P(None, None, "model"), (88, 12288, 28672), info, 1, 2)
    assert spec[0] is None
    assert spec[1] in ("data", ("data",))  # P may normalize 1-tuples


# ---------------------------------------------------------------------------
# roofline unit tests
# ---------------------------------------------------------------------------


def test_parse_collectives_synthetic():
    hlo = """
  %ag = f32[128,256]{1,0} all-gather(f32[8,256]{1,0} %x), dimensions={0}
  %ar.1 = bf16[1024]{0} all-reduce(bf16[1024]{0} %y), to_apply=%add
  %done = f32[4]{0} all-reduce-done(f32[4]{0} %start)
  %unrelated = f32[2,2]{1,0} add(f32[2,2]{1,0} %a, f32[2,2]{1,0} %b)
"""
    out = parse_collectives(hlo)
    assert out["all-gather"] == 128 * 256 * 4
    assert out["all-reduce"] == 1024 * 2  # the -done half is not re-counted


def test_roofline_terms_math():
    t = RooflineTerms(
        name="x", chips=256, flops=256 * 197e12, hbm_bytes=0.0, coll_bytes=0.0,
        model_flops=128 * 197e12,
    )
    assert t.t_compute == pytest.approx(1.0)
    assert t.bottleneck == "compute"
    assert t.mfu == pytest.approx(0.5)
    assert t.usefulness == pytest.approx(0.5)
    t2 = RooflineTerms(
        name="y", chips=2, flops=0.0, hbm_bytes=2 * 819e9, coll_bytes=2 * 50e9 * 2,
    )
    assert t2.bottleneck == "collective"
    assert t2.step_time == pytest.approx(2.0)


def test_analytic_counts_sane():
    from repro.configs import get_arch, get_shape
    from repro.roofline.flops import count_cell

    cfg = get_arch("mistral-large-123b")
    c = count_cell(cfg, get_shape("train_4k"), dp=16, tp=16)
    # train flops must be 3-5x of 2*N*D (bwd + remat)
    base = 2 * cfg.num_params() * 4096 * 256
    assert 3 * base < c.flops < 5 * base
    assert c.model_flops == pytest.approx(3 * base)
    # decode flops per step ~ 2*N*B
    d = count_cell(cfg, get_shape("decode_32k"), dp=16, tp=16)
    assert d.flops > 2 * cfg.num_params() * 128  # plus attention context
    assert d.flops < 6 * cfg.num_params() * 128


def test_serve_cache_shardings_never_shard_slot_or_seq():
    """Serving cache placement: head-major attention K/V ``[L, B, KV, S,
    hd]`` shard KV heads first with a head_dim fallback, their scales
    ``[L, B, KV, S]`` the heads only; MLA latent/rope ``[L, B, S, r]``
    shard the rank. Slot (dim 1) and sequence stay whole (the engine
    scatters rows at arbitrary (slot, pos) every tick). Recurrent SSM
    leaves take their widest trailing dim."""
    import jax

    from repro.dist.sharding import serve_cache_shardings

    class FakeInfo:
        model_size = 2

        def named(self, spec):
            return spec

    f32 = np.float32
    cache = {
        "k": jax.ShapeDtypeStruct((2, 4, 2, 96, 16), f32),  # KV heads divide
        "v": jax.ShapeDtypeStruct((2, 4, 1, 96, 16), f32),  # GQA fallback: hd
        "k_scale": jax.ShapeDtypeStruct((2, 4, 2, 96), f32),  # with its heads
        "v_scale": jax.ShapeDtypeStruct((2, 4, 1, 96), f32),  # never the seq
        "ckv": jax.ShapeDtypeStruct((2, 4, 96, 32), f32),  # MLA latent: rank
        "krope": jax.ShapeDtypeStruct((2, 4, 96, 8), f32),
        "attn_k": jax.ShapeDtypeStruct((1, 4, 2, 96, 16), f32),  # hybrid pool
        "mamba": {"conv": jax.ShapeDtypeStruct((2, 4, 3, 64), f32)},  # widest
    }
    specs = serve_cache_shardings(cache, FakeInfo())
    assert specs["k"] == P(None, None, "model", None, None)
    assert specs["v"] == P(None, None, None, None, "model")
    assert specs["k_scale"] == P(None, None, "model", None)
    assert specs["v_scale"] == P(None, None, None, None)
    # the MLA regression: dim 2 is SEQUENCE — only the rank dim may shard
    assert specs["ckv"] == P(None, None, None, "model")
    assert specs["krope"] == P(None, None, None, "model")
    assert specs["attn_k"] == P(None, None, "model", None, None)
    assert specs["mamba"]["conv"] == P(None, None, None, "model")

"""Tiny configurations and cells for the CPU tests: the benchmark's own
code paths at widths a test run can hold."""

from __future__ import annotations

import copy
import dataclasses

from bench import harness

QWEN = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": False,
}
MLA = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 512,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": True, "scale_emb": 1,
    "scale_depth": 2 ** 0.5, "dim_model_base": 64,
}
# DeepSeek-V2-shaped: MLA with a dense query projection, one leading dense
# layer, then expert layers of 8 experts, top 2, one shared. ``config``
# holds the published keys; ``runs_as`` how the program runs them today:
# it renormalises the top-k weights and applies plain RoPE.
MOE = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 3,
    "vocab_size": 512, "first_k_dense_replace": 1, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0, "scoring_func": "softmax", "topk_method": "greedy",
    "moe_layer_freq": 1, "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False,
    "rope_scaling": {"type": "yarn", "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1},
}
MOE_RUNS_AS = {"norm_topk_prob": True, "rope_scaling": None}
FAMILIES = {  # family -> (published keys, program arch, reference module)
    "qwen": (QWEN, "bench-tiny-qwen", "qwen2"),
    "mla": (MLA, "bench-tiny-mla", "minicpm3"),
    "moe": (MOE, "bench-tiny-moe", "deepseek_v2"),
}
CHAT = {
    "generator": "serving",
    "arrivals": {"kind": "poisson", "rate_per_s": 6.0, "tail_share": 0.1},
    "warmup_s": 0.5,
    "prompt": {"median": 40, "sigma": 1.0, "min": 8, "max": 160},
    "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
}
REASONING = {
    "generator": "serving",
    "arrivals": {"kind": "backlog", "requests_per_slot": 3},
    "first_wave": "residual",
    "warmup_s": 0.0,
    "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 96},
    "output": {"median": 40, "sigma": 0.3, "min": 16, "max": 80},
}


def register():
    """Register the tiny program configurations (idempotent)."""
    from repro.configs import base, get_arch

    cq = get_arch("codeqwen1.5-7b")
    mc = get_arch("minicpm3-4b")
    ds = get_arch("deepseek-v2-lite-16b")
    arches = {
        "bench-tiny-qwen": dataclasses.replace(
            cq, name="bench-tiny-qwen", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            d_head=16, d_ff=128, vocab_size=512, rope_theta=10000.0),
        "bench-tiny-mla": dataclasses.replace(
            mc, name="bench-tiny-mla", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
            d_head=24, d_ff=128, vocab_size=512,
            mla=dataclasses.replace(mc.mla, kv_lora_rank=16, q_lora_rank=32,
                                    rope_head_dim=8, nope_head_dim=16, v_head_dim=16)),
        # capacity factor E / k: every expert can take every token of a
        # pack, so no token is dropped (the program's 1.25 drops tokens in
        # packs, which no reference reproduces)
        "bench-tiny-moe": dataclasses.replace(
            ds, name="bench-tiny-moe", n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
            d_head=24, d_ff=32, dense_ff=128, vocab_size=512, first_k_dense=1,
            rope_theta=10000.0,
            moe=dataclasses.replace(ds.moe, n_routed=8, top_k=2, n_shared=1, expert_ff=32,
                                    capacity_factor=4.0),
            mla=dataclasses.replace(ds.mla, kv_lora_rank=16, q_lora_rank=0,
                                    rope_head_dim=8, nope_head_dim=16, v_head_dim=16)),
    }
    for a in arches.values():
        if a.name not in base.ARCHS:
            base.register_arch(a)


def cell(family: str = "qwen", traffic: str = "chat", *, slots: int = 4,
         max_len: int = 256, chips: int = 1, max_gap: float = 1e9) -> harness.Cell:
    register()
    hf, arch, reference = FAMILIES[family]
    conf = {
        "name": f"tiny-{family}",
        "arch": arch,
        "reference": reference,
        "config": copy.deepcopy(hf),
        "deployment": {"chips": chips, "mode": "merge" if chips > 1 else "single",
                       "dtype": "float32", "slots": slots, "max_len": max_len},
    }
    if family == "moe":
        conf["runs_as"] = copy.deepcopy(MOE_RUNS_AS)
    mix = copy.deepcopy(CHAT if traffic == "chat" else REASONING)
    return harness.Cell(
        name=f"tiny-{family}.{traffic}", conf=conf, mix=mix,
        limits={"max_gap": max_gap, "min_tokens": 1}, chips=chips,
        end_to_end=[{"name": n, "unit": "x"} for n in
                    ("ttft_p90_ms", "tpot_p90_ms", "output_tok_s", "setup_s")],
        per_layer=[],
    )

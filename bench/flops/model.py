"""Model FLOPs per token from a configuration file's published sizes.

A token at position p (0-based) attends p + 1 keys. Weight matmuls cost
2 FLOPs per weight; attention scores and the value readout 2 FLOPs per
(query, key, head, dim); the output head is counted only for tokens whose
logits are needed (every decoded token and the last prompt token), since a
prompt's other rows need none. Latent attention is counted in its expanded
form (per-head keys of nope+rope dims, values of v dims), the model's own
arithmetic, whatever form a server computes it in.

An expert model (``n_routed_experts`` in the file) has
``first_k_dense_replace`` dense layers of width ``intermediate_size`` and
expert layers after them. An expert layer counts its attention, the router
over every published expert (``d x n_routed_experts_published``), the
shared experts (``n_shared_experts`` of width ``moe_intermediate_size``),
and a token's expected share of routed expert work on this chip under the
deployment: ``num_experts_per_tok x held / published`` experts of width
``moe_intermediate_size``, where the chip holds ``n_routed_experts`` of the
published experts. That is the expected count of a token's top k that land
on the held block when routing spreads tokens evenly over the experts; a
chip that holds all of them counts all k.
"""

from __future__ import annotations


def _is_mla(hf: dict) -> bool:
    return "kv_lora_rank" in hf


def _is_moe(hf: dict) -> bool:
    return "n_routed_experts" in hf


def _attn_weights(hf: dict) -> int:
    """Weights of one layer's attention projections."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    if _is_mla(hf):
        ql, kl = hf["q_lora_rank"], hf["kv_lora_rank"]
        nope, rp, v = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
        q = d * ql + ql * h * (nope + rp) if ql else d * h * (nope + rp)
        return q + d * (kl + rp) + kl * h * (nope + v) + h * v * d
    kv, hd = hf["num_key_value_heads"], d // h
    return d * (h + 2 * kv) * hd + h * hd * d


def weight_flops(hf: dict) -> float:
    """Per token, per dense layer: 2 x the layer's matmul weights."""
    d, ff = hf["hidden_size"], hf["intermediate_size"]
    return 2.0 * (_attn_weights(hf) + 3 * d * ff)


def expert_weight_flops(hf: dict) -> float:
    """Per token, per expert layer: 2 x the attention's weights, the
    router's, the shared experts' and the token's expected share of the
    routed experts held on this chip."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    held, published = hf["n_routed_experts"], hf["n_routed_experts_published"]
    routed = hf["num_experts_per_tok"] * held / published
    experts = 3 * d * f * (hf["n_shared_experts"] + routed)
    return 2.0 * (_attn_weights(hf) + d * published + experts)


def attn_flops_per_key(hf: dict) -> float:
    """Per (query token, attended key), per layer: scores + value readout."""
    h = hf["num_attention_heads"]
    if _is_mla(hf):
        return 2.0 * h * (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"])
    return 4.0 * h * (hf["hidden_size"] // h)


def head_flops(hf: dict) -> float:
    return 2.0 * hf["hidden_size"] * hf["vocab_size"]


def tokens_flops(hf: dict, start: int, stop: int, with_head: int) -> float:
    """FLOPs of the tokens at positions [start, stop) of one sequence, with
    the output head for ``with_head`` of them."""
    n = stop - start
    if n <= 0:
        return 0.0
    keys = (start + 1 + stop) * n / 2.0  # sum of p + 1 over the positions
    per_layer = n * weight_flops(hf) + keys * attn_flops_per_key(hf)
    if not _is_moe(hf):
        return hf["num_hidden_layers"] * per_layer + with_head * head_flops(hf)
    dense = hf.get("first_k_dense_replace", 0)
    per_expert_layer = n * expert_weight_flops(hf) + keys * attn_flops_per_key(hf)
    return (dense * per_layer + (hf["num_hidden_layers"] - dense) * per_expert_layer
            + with_head * head_flops(hf))

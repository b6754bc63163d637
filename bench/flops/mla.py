"""Least work of one MLA decode attention call (one layer) over the latent
cache, from its shapes, counted as ``attention.py`` counts the GQA calls.
The roofline's least time is ``attention.least_seconds``."""

from __future__ import annotations


def mla_decode_call(hf: dict, contexts: list[int], elem: int = 2) -> tuple[float, float]:
    """One MLA decode step's latent attention over the active slots, each
    attending ``c`` keys: scores of the absorbed query against the latent
    and rope rows, and the latent readout. Bytes count the absorbed query
    and rope query in, the latent readout out, and each slot's attended
    latent and rope rows once. Returns (flops, bytes)."""
    h, r, rope = hf["num_attention_heads"], hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    keys = float(sum(contexts))
    flops = 2.0 * h * (r + rope) * keys + 2.0 * h * r * keys
    bytes_ = elem * (len(contexts) * h * (r + rope) + len(contexts) * h * r + keys * (r + rope))
    return flops, bytes_

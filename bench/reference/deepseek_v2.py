"""DeepSeek-V2 forward pass, as published in the DeepSeek-V2 model
description (arXiv:2405.04434) and its released modelling code: pre-norm
decoder with multi-head latent attention (a low-rank query with its own
RMSNorm, or a dense query projection where ``q_lora_rank`` is null; a
compressed key/value latent with its own RMSNorm; a decoupled rotary key
shared by all heads), ``first_k_dense_replace`` leading SwiGLU layers of
width ``intermediate_size``, expert layers after them, and an untied head.

An expert layer: a float32 softmax router over the published experts,
greedy top-``num_experts_per_tok``; the top-k weights are renormalised to
sum to 1 where ``norm_topk_prob`` is set, and otherwise multiplied by
``routed_scaling_factor`` (the released DeepSeek-V2 gate); SwiGLU experts
of width ``moe_intermediate_size``; and ``n_shared_experts`` shared
experts, taken as one SwiGLU of width n_shared x moe_intermediate_size
(the sum of the shared experts equals it with their hidden units stacked).

The expert share: where a chip holds a block of the experts, the
configuration's ``n_routed_experts`` counts the experts held, and the
harness hands on the published count and the first held expert as
``n_routed_experts_published`` and ``first_routed_expert``. The router
ranks every published expert; the layer sums the outputs of the held
experts among each token's top k, and the shared experts' (``experts``
returns the two parts apart).

Rotary embeddings pair dimension i with i + d/2. The released checkpoint
stores the rope dimensions interleaved and its code de-interleaves them
before rotating: the same model with the rope columns of the query and of
``wkv_a`` permuted once, which is how the benchmark serves its weights.
No ``rope_scaling`` is implemented here: a configuration that runs one is
refused, never run with plain rotary embeddings.

Weights are named and laid out as the benchmark serves them. Layer ``l``
below ``first_k_dense_replace`` is layer ``l`` of ``dense_blocks``; an
expert layer is layer ``l - first_k_dense_replace`` of ``moe_blocks``.
Attention: ``wq [d, H, nope+rope]`` (or ``wq_a [d, q_lora]``, ``q_norm``,
``wq_b [q_lora, H, nope+rope]``), ``wkv_a [d, kv_lora+rope]``,
``kv_norm``, ``wkv_b [kv_lora, H, nope+v]``, ``wo [H, v, d]``. A dense
layer's ``mlp/{w_in, w_gate, w_out}``; an expert layer's ``moe/router [d,
published]`` in float32, ``moe/w_in`` and ``moe/w_gate [held, d, f]``,
``moe/w_out [held, f, d]``, and ``moe/shared/{w_in, w_gate, w_out}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import HIGHEST, causal_attention, mm, rms_norm, rope, swiglu

# how the released model routes; anything else is not implemented here
_ROUTING = {"scoring_func": "softmax", "topk_method": "greedy", "moe_layer_freq": 1}


def _supported(hf: dict) -> None:
    if hf.get("rope_scaling") is not None:
        raise NotImplementedError(f"rope_scaling {hf['rope_scaling']} is not implemented")
    for key, value in _ROUTING.items():
        if hf.get(key, value) != value:
            raise NotImplementedError(f"{key} {hf[key]!r} is not implemented")


def layer_leaves(hf: dict, l: int) -> dict:
    """{name: (served path, stack index, one layer's shape, dtype)}; a dtype
    of None is the deployment's."""
    _supported(hf)
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    ql, kl = hf["q_lora_rank"], hf["kv_lora_rank"]
    nope, rp, vd = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    leaves = {
        "norm1": ("norm1", (d,)),
        "norm2": ("norm2", (d,)),
        "wkv_a": ("attn/wkv_a", (d, kl + rp)),
        "kv_norm": ("attn/kv_norm", (kl,)),
        "wkv_b": ("attn/wkv_b", (kl, h, nope + vd)),
        "wo": ("attn/wo", (h, vd, d)),
    }
    if ql:
        leaves.update(wq_a=("attn/wq_a", (d, ql)), q_norm=("attn/q_norm", (ql,)),
                      wq_b=("attn/wq_b", (ql, h, nope + rp)))
    else:
        leaves["wq"] = ("attn/wq", (d, h, nope + rp))
    dense = hf.get("first_k_dense_replace", 0)
    dtypes = {}
    if l < dense:
        stack, index = "dense_blocks", l
        ff = hf["intermediate_size"]
        leaves.update(w_in=("mlp/w_in", (d, ff)), w_gate=("mlp/w_gate", (d, ff)),
                      w_out=("mlp/w_out", (ff, d)))
    else:
        stack, index = "moe_blocks", l - dense
        f, held = hf["moe_intermediate_size"], hf["n_routed_experts"]
        shared = hf["n_shared_experts"] * f
        leaves.update(
            router=("moe/router", (d, hf["n_routed_experts_published"])),
            e_in=("moe/w_in", (held, d, f)), e_gate=("moe/w_gate", (held, d, f)),
            e_out=("moe/w_out", (held, f, d)),
            s_in=("moe/shared/w_in", (d, shared)), s_gate=("moe/shared/w_gate", (d, shared)),
            s_out=("moe/shared/w_out", (shared, d)),
        )
        dtypes["router"] = "float32"  # the router is served in float32
    return {k: (f"{stack}/{path}", index, shape, dtypes.get(k))
            for k, (path, shape) in leaves.items()}


def plain_leaves(hf: dict) -> dict:
    d, v = hf["hidden_size"], hf["vocab_size"]
    out = {"tok": ("embed/tok", (v, d)), "final_norm": ("final_norm", (d,))}
    if not hf["tie_word_embeddings"]:
        out["head"] = ("embed/head", (d, v))
    return out


def embed(plain: dict, tokens, hf: dict):
    return plain["tok"][tokens]


def attention(a, w: dict, hf: dict, prec: str):
    """Latent attention of the normed rows ``a [N, d]``, expanded form."""
    d, h, kl = hf["hidden_size"], hf["num_attention_heads"], hf["kv_lora_rank"]
    nope, rp, vd = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    n, eps, theta = a.shape[0], hf["rms_norm_eps"], hf["rope_theta"]
    if "wq" in w:
        q = mm(a, w["wq"].reshape(d, h * (nope + rp)), prec)
    else:
        cq = rms_norm(mm(a, w["wq_a"], prec), w["q_norm"], eps)
        q = mm(cq, w["wq_b"].reshape(-1, h * (nope + rp)), prec)
    q = q.reshape(n, h, nope + rp)
    ckv = mm(a, w["wkv_a"], prec)
    c, k_rope = ckv[:, :kl], ckv[:, kl:]
    kvb = mm(rms_norm(c, w["kv_norm"], eps), w["wkv_b"].reshape(kl, h * (nope + vd)), prec)
    kvb = kvb.reshape(n, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k_rope = jnp.broadcast_to(rope(k_rope[:, None, :], theta), (n, h, rp))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    o = causal_attention(q, k, v, (nope + rp) ** -0.5).reshape(n, h * vd)
    return mm(o, w["wo"].reshape(h * vd, d), prec)


def gate(x, router, hf: dict):
    """[N, published] weight of each expert for each row of ``x``: the
    top-k softmax scores as the released gate weighs them, 0 elsewhere."""
    probs = jax.nn.softmax(jnp.matmul(x, router, precision=HIGHEST), axis=-1)
    top, idx = jax.lax.top_k(probs, hf["num_experts_per_tok"])
    if hf["num_experts_per_tok"] > 1 and hf.get("norm_topk_prob"):
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    else:
        top = top * hf.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


def experts(x, w: dict, hf: dict, prec: str):
    """(routed, shared) outputs of an expert layer's feed-forward for the
    normed rows ``x [N, d]``: the held experts' outputs weighted by the
    gate, and the shared experts'. Every held expert runs on every row; a
    row's weight for an expert outside its top k is 0."""
    held, first = w["e_in"].shape[0], hf["first_routed_expert"]
    g = gate(x, w["router"], hf)[:, first:first + held]

    def one(out, e):
        g_e, w_in, w_gate, w_out = e
        y = swiglu(x, {"w_in": w_in, "w_gate": w_gate, "w_out": w_out}, prec)
        return out + g_e[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), (g.T, w["e_in"], w["e_gate"], w["e_out"]))
    shared = swiglu(x, {"w_in": w["s_in"], "w_gate": w["s_gate"], "w_out": w["s_out"]}, prec)
    return routed, shared


def layer(x, w: dict, hf: dict, prec: str):
    """One layer; an expert layer where ``w`` holds a router."""
    eps = hf["rms_norm_eps"]
    x = x + attention(rms_norm(x, w["norm1"], eps), w, hf, prec)
    h = rms_norm(x, w["norm2"], eps)
    if "router" not in w:
        return x + swiglu(h, w, prec)
    routed, shared = experts(h, w, hf, prec)
    return x + routed + shared


def logits(x, plain: dict, hf: dict, prec: str):
    """Output logits of the final hidden rows ``x [n, d]``."""
    x = rms_norm(x, plain["final_norm"], hf["rms_norm_eps"])
    head = plain["head"] if "head" in plain else plain["tok"].T
    return mm(x, head, prec)

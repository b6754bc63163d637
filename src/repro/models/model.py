"""Unified LM over all assigned architecture families.

One class, four families:

* ``dense``  — pre-norm transformer (GQA/MHA, optional qk_norm, optional MLA)
* ``moe``    — same attention; FFN replaced by routed experts (+shared) after
               ``first_k_dense`` leading dense layers
* ``ssm``    — Mamba1 stack (attention-free)
* ``hybrid`` — Mamba2 stack with a single weight-tied attention+MLP block
               invoked every ``shared_attn_every`` layers (Zamba2)

Layer parameters are stacked on a leading ``L`` axis and the stack is
traversed with ``jax.lax.scan`` (compile-time/HLO-size control at 512
devices); ``cfg.remat == 'block'`` wraps the scanned body in
``jax.checkpoint``.

The same class serves training (``forward``), prefill (``forward``), and
decoding (``decode_step`` + ``init_cache``). Modality stubs: ``audio``/``vlm``
archs accept precomputed frame/patch embeddings via ``batch['embeds']``
(the encoders themselves are out of scope here).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.dist.sharding import HEAD_MAJOR_CACHE_KEYS, MeshInfo
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    Params,
    embed_tokens,
    embedding_init,
    mlp_apply,
    mlp_init,
    rms_norm,
    unembed,
)

# =============================================================================
# construction
# =============================================================================


class LM:
    def __init__(self, cfg: ArchConfig, mesh_info: Optional[MeshInfo] = None):
        self.cfg = cfg
        self.mesh_info = mesh_info
        self.dtype = jnp.dtype(cfg.dtype)
        if cfg.family == "hybrid":
            assert cfg.n_layers % cfg.shared_attn_every == 0, (
                cfg.n_layers,
                cfg.shared_attn_every,
            )

    # ------------------------------------------------------------------ init

    def _block_init(self, key) -> Params:
        """One transformer block's params (dense family or moe attention part)."""
        cfg, dt = self.cfg, self.dtype
        k_attn, k_mlp = jax.random.split(key)
        if cfg.mla is not None:
            attn = mla_mod.mla_init(k_attn, cfg, dt)
        else:
            attn = attn_mod.attention_init(k_attn, cfg, dt)
        return {
            "attn": attn,
            "norm1": jnp.ones((cfg.d_model,), dt),
            "norm2": jnp.ones((cfg.d_model,), dt),
            "mlp": mlp_init(k_mlp, cfg.d_model, cfg.d_ff, dt),
        }

    def _moe_block_init(self, key) -> Params:
        cfg, dt = self.cfg, self.dtype
        k_attn, k_moe = jax.random.split(key)
        if cfg.mla is not None:
            attn = mla_mod.mla_init(k_attn, cfg, dt)
        else:
            attn = attn_mod.attention_init(k_attn, cfg, dt)
        return {
            "attn": attn,
            "norm1": jnp.ones((cfg.d_model,), dt),
            "norm2": jnp.ones((cfg.d_model,), dt),
            "moe": moe_mod.moe_init(k_moe, cfg, dt),
        }

    def _dense_block_init_ff(self, key, d_ff: int) -> Params:
        """Dense block with an explicit d_ff (MoE stacks' leading dense layers)."""
        cfg, dt = self.cfg, self.dtype
        k_attn, k_mlp = jax.random.split(key)
        if cfg.mla is not None:
            attn = mla_mod.mla_init(k_attn, cfg, dt)
        else:
            attn = attn_mod.attention_init(k_attn, cfg, dt)
        return {
            "attn": attn,
            "norm1": jnp.ones((cfg.d_model,), dt),
            "norm2": jnp.ones((cfg.d_model,), dt),
            "mlp": mlp_init(k_mlp, cfg.d_model, d_ff, dt),
        }

    def _mamba_init(self, key) -> Params:
        cfg, dt = self.cfg, self.dtype
        init = ssm_mod.mamba1_init if cfg.ssm.variant == "mamba1" else ssm_mod.mamba2_init
        return {"mamba": init(key, cfg, dt), "norm": jnp.ones((cfg.d_model,), dt)}

    def init(self, key) -> Params:
        cfg, dt = self.cfg, self.dtype
        k_emb, k_blocks, k_shared = jax.random.split(key, 3)
        params: Params = {
            "embed": embedding_init(k_emb, cfg.vocab_size, cfg.d_model, dt, cfg.tie_embeddings),
            "final_norm": jnp.ones((cfg.d_model,), dt),
        }
        L = cfg.n_layers
        if cfg.family == "dense":
            keys = jax.random.split(k_blocks, L)
            params["blocks"] = jax.vmap(self._block_init)(keys)
        elif cfg.family == "moe":
            kd = cfg.first_k_dense
            if kd:
                dkeys = jax.random.split(jax.random.fold_in(k_blocks, 1), kd)
                dff = cfg.dense_ff or cfg.d_ff
                params["dense_blocks"] = jax.vmap(
                    functools.partial(self._dense_block_init_ff, d_ff=dff)
                )(dkeys)
            mkeys = jax.random.split(jax.random.fold_in(k_blocks, 2), L - kd)
            params["moe_blocks"] = jax.vmap(self._moe_block_init)(mkeys)
        elif cfg.family == "ssm":
            keys = jax.random.split(k_blocks, L)
            params["blocks"] = jax.vmap(self._mamba_init)(keys)
        elif cfg.family == "hybrid":
            keys = jax.random.split(k_blocks, L)
            params["blocks"] = jax.vmap(self._mamba_init)(keys)
            params["shared"] = self._block_init(k_shared)  # ONE tied attn+mlp block
        else:
            raise ValueError(cfg.family)
        return params

    def init_params(self, seed: int, out_shardings: Any = None) -> Params:
        """Seeded random params, built inside ONE jitted program: each
        leaf's f32 draw fuses with its cast to the model dtype, so no f32
        transient of a whole stacked leaf lives beside the weights (eager
        init holds ~2x a layer stack's bf16 bytes at full width). With
        ``out_shardings`` (a pytree of shardings, e.g.
        ``param_shardings``) every leaf is born on its devices — a sharded
        model never lands on one device first."""
        return jax.jit(self.init, out_shardings=out_shardings)(jax.random.key(seed))

    def param_specs(self, seed: int = 0) -> Any:
        """ShapeDtypeStruct pytree of the params (no allocation)."""
        return jax.eval_shape(lambda: self.init(jax.random.key(seed)))

    # ------------------------------------------------------------- block fns

    def _attn_apply(self, blk: Params, x: jax.Array, positions: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = rms_norm(x, blk["norm1"], cfg.norm_eps)
        if cfg.mla is not None:
            a = mla_mod.mla_apply(blk["attn"], cfg, h, positions)
        else:
            a = attn_mod.attention_apply(blk["attn"], cfg, h, positions)
        return x + a

    def _dense_block(self, blk: Params, x: jax.Array, positions: jax.Array) -> jax.Array:
        x = self._attn_apply(blk, x, positions)
        h = rms_norm(x, blk["norm2"], self.cfg.norm_eps)
        return x + mlp_apply(blk["mlp"], h)

    def _moe_block(
        self, blk: Params, x: jax.Array, positions: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        x = self._attn_apply(blk, x, positions)
        h = rms_norm(x, blk["norm2"], self.cfg.norm_eps)
        out, aux = moe_mod.moe_apply(blk["moe"], self.cfg, h, mesh_info=self.mesh_info)
        return x + out, aux

    def _mamba_block(self, blk: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = rms_norm(x, blk["norm"], cfg.norm_eps)
        fn = ssm_mod.mamba1_apply if cfg.ssm.variant == "mamba1" else ssm_mod.mamba2_apply
        return x + fn(blk["mamba"], cfg, h)

    def _maybe_remat(self, fn):
        if self.cfg.remat == "block":
            return jax.checkpoint(fn)
        return fn

    # Megatron-style sequence parallelism: between blocks the residual stream
    # is sharded over the MODEL axis on the sequence dim. XLA inserts the
    # all-gather before attention/FFN (which need full sequence / are head-
    # sharded) and the reduce-scatter after — and, critically, the remat
    # checkpoint saved per scanned layer is the SP-sharded tensor: boundary
    # activation memory drops by the TP degree (17 GB -> ~1 GB on
    # codeqwen/train_4k, measured via launch/dryrun.py).
    def _sp(self, x: jax.Array) -> jax.Array:
        mi = self.mesh_info
        if mi is None or mi.model_size <= 1:
            return x
        s = x.shape[1]
        if s < mi.model_size or s % mi.model_size:
            return x
        from jax.sharding import PartitionSpec as P

        return mi.constraint(x, P(mi.batch_axes, "model", None))

    def _logits_constraint(self, logits: jax.Array) -> jax.Array:
        """Keep [B,S,V] logits vocab-sharded: replicated f32 logits at
        vocab 92k-202k are 12-24 GB/device (measured in the dry-run
        artifact)."""
        mi = self.mesh_info
        if mi is None or mi.model_size <= 1:
            return logits
        if logits.shape[-1] % mi.model_size:
            return logits
        from jax.sharding import PartitionSpec as P

        parts = [None] * logits.ndim
        parts[0] = mi.batch_axes
        parts[-1] = "model"
        return mi.constraint(logits, P(*parts))

    # ---------------------------------------------------------------- forward

    def forward(self, params: Params, batch: dict) -> tuple[jax.Array, jax.Array]:
        """Full-sequence forward (train / prefill).

        batch: {'tokens': [B,S] int32} or {'embeds': [B,S,d]} for audio stubs.
        Returns (logits [B,S,V], aux scalar — MoE load-balance loss or 0).
        """
        cfg = self.cfg
        if "embeds" in batch:
            x = batch["embeds"].astype(self.dtype)
        else:
            x = embed_tokens(params["embed"], batch["tokens"])
        b, s = x.shape[:2]
        positions = jnp.arange(s, dtype=jnp.int32)
        if self.mesh_info is not None:
            x = self.mesh_info.constraint(x, self.mesh_info.batch_spec(3))
        aux_total = jnp.zeros((), jnp.float32)

        if cfg.family in ("dense",):
            body = self._maybe_remat(
                lambda xx, blk: (self._sp(self._dense_block(blk, self._sp(xx), positions)), None)
            )
            x, _ = jax.lax.scan(body, x, params["blocks"])
        elif cfg.family == "moe":
            if cfg.first_k_dense:
                body_d = self._maybe_remat(
                    lambda xx, blk: (self._sp(self._dense_block(blk, self._sp(xx), positions)), None)
                )
                x, _ = jax.lax.scan(body_d, x, params["dense_blocks"])

            def _moe_body(xx, blk):
                xx, aux = self._moe_block(blk, self._sp(xx), positions)
                return self._sp(xx), aux

            body_m = self._maybe_remat(_moe_body)
            x, auxs = jax.lax.scan(body_m, x, params["moe_blocks"])
            aux_total = aux_total + auxs.sum()
        elif cfg.family == "ssm":
            body = self._maybe_remat(
                lambda xx, blk: (self._sp(self._mamba_block(blk, self._sp(xx))), None)
            )
            x, _ = jax.lax.scan(body, x, params["blocks"])
        elif cfg.family == "hybrid":
            every = cfg.shared_attn_every
            n_groups = cfg.n_layers // every
            grouped = jax.tree.map(
                lambda p: p.reshape(n_groups, every, *p.shape[1:]), params["blocks"]
            )
            shared = params["shared"]

            def group_body(xx, gblk):
                def inner(xxx, blk):
                    return self._sp(self._mamba_block(blk, self._sp(xxx))), None

                xx, _ = jax.lax.scan(inner, xx, gblk)
                xx = self._dense_block(shared, xx, positions)
                return self._sp(xx), None

            x, _ = jax.lax.scan(self._maybe_remat(group_body), x, grouped)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params["embed"], x)
        logits = self._logits_constraint(logits)
        return logits, aux_total

    # ----------------------------------------------------------------- cache

    @property
    def cache_dtype(self):
        """KV/latent cache storage dtype (f8 option halves decode HBM)."""
        return jnp.dtype(self.cfg.kv_cache_dtype or self.cfg.dtype)

    def init_cache(
        self, batch: int, max_len: int, kv_dtype: Optional[Any] = None
    ) -> Params:
        """Decode cache pytree (zeros). Layout per family documented inline.

        ``kv_dtype`` opts the positional-KV cache into quantized-row
        storage: K/V leaves store that dtype (int8 for quantized serving;
        f32 keeps the scale machinery but stays bit-identical to the plain
        path) and per-(position, head) f32 ``k_scale``/``v_scale`` leaves
        ``[L, B, KV, S]`` live IN the cache pytree — they thread through
        scan/donation/COW exactly like the payloads they describe.

        Positional K/V leaves are head-major ``[L, B, KV, S, hd]`` (the
        layout the TPU attention kernels tile; see
        :mod:`repro.kernels.decode_attention`); MLA latents stay
        ``[L, B, S, r]``. Either way the position axis is ``ndim - 2`` of a
        payload leaf."""
        cfg, dt = self.cfg, self.cache_dtype
        L = cfg.n_layers
        if kv_dtype is not None and not self.has_positional_kv:
            raise ValueError(
                f"family {self.family_tag!r} has no positional KV to quantize"
            )
        if cfg.family in ("dense", "moe"):
            if cfg.mla is not None:
                m = cfg.mla
                return {
                    "ckv": jnp.zeros((L, batch, max_len, m.kv_lora_rank), dt),
                    "krope": jnp.zeros((L, batch, max_len, m.rope_head_dim), dt),
                }
            kv, hd = cfg.n_kv_heads, cfg.head_dim
            if kv_dtype is not None:
                qdt = jnp.dtype(kv_dtype)
                return {
                    "k": jnp.zeros((L, batch, kv, max_len, hd), qdt),
                    "v": jnp.zeros((L, batch, kv, max_len, hd), qdt),
                    "k_scale": jnp.ones((L, batch, kv, max_len), jnp.float32),
                    "v_scale": jnp.ones((L, batch, kv, max_len), jnp.float32),
                }
            return {
                "k": jnp.zeros((L, batch, kv, max_len, hd), dt),
                "v": jnp.zeros((L, batch, kv, max_len, hd), dt),
            }
        if cfg.family == "ssm":
            mk = (
                ssm_mod.mamba1_init_cache
                if cfg.ssm.variant == "mamba1"
                else ssm_mod.mamba2_init_cache
            )
            one = mk(cfg, batch, self.dtype)  # SSM states stay full precision
            return jax.tree.map(
                lambda leaf: jnp.zeros((L, *leaf.shape), leaf.dtype), one
            )
        if cfg.family == "hybrid":
            mk = ssm_mod.mamba2_init_cache
            one = mk(cfg, batch, self.dtype)
            n_inv = L // cfg.shared_attn_every
            kv, hd = cfg.n_kv_heads, cfg.head_dim
            return {
                "mamba": jax.tree.map(
                    lambda leaf: jnp.zeros((L, *leaf.shape), leaf.dtype), one
                ),
                "attn_k": jnp.zeros((n_inv, batch, kv, max_len, hd), dt),
                "attn_v": jnp.zeros((n_inv, batch, kv, max_len, hd), dt),
            }
        raise ValueError(cfg.family)

    def init_kv_pool(
        self, num_blocks: int, block_size: int, kv_dtype: Optional[Any] = None
    ) -> Params:
        """Block-paged KV pool (zeros): ``[L, num_blocks, KV, block_size,
        hd]`` per leaf — the dense cache's ``[B, S_max]`` plane refactored
        into shared, individually-ownable blocks (paged serving,
        :mod:`repro.serve.kv_pool`), each block head-major like the dense
        cache. With identity block tables (block i of sequence b = b *
        max_blocks + i) a block holds positions ``[i*bs, (i+1)*bs)`` of
        every head of ``init_cache(B, max_blocks * block_size)``'s row b —
        paging adds an indirection, not a new tile layout. Positional-KV
        families only (``has_positional_kv``).

        ``kv_dtype`` adds quantized-row storage exactly as in
        :meth:`init_cache`: scale leaves ``[L, num_blocks, KV,
        block_size]`` f32 are pool-shaped, so block-granular ownership (COW,
        prefix sharing, re-homing) carries the scales with their blocks
        for free."""
        cfg, dt = self.cfg, self.cache_dtype
        if not self.has_positional_kv:
            raise ValueError(
                f"family {self.family_tag!r} has no positional KV to page"
            )
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        if kv_dtype is not None:
            qdt = jnp.dtype(kv_dtype)
            L = cfg.n_layers
            return {
                "k": jnp.zeros((L, num_blocks, kv, block_size, hd), qdt),
                "v": jnp.zeros((L, num_blocks, kv, block_size, hd), qdt),
                "k_scale": jnp.ones((L, num_blocks, kv, block_size), jnp.float32),
                "v_scale": jnp.ones((L, num_blocks, kv, block_size), jnp.float32),
            }
        return {
            "k": jnp.zeros((cfg.n_layers, num_blocks, kv, block_size, hd), dt),
            "v": jnp.zeros((cfg.n_layers, num_blocks, kv, block_size, hd), dt),
        }

    def cache_specs(self, batch: int, max_len: int) -> Any:
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def cache_shardings(self, cache_shape: Any, mesh_info: MeshInfo) -> Any:
        """Cache placement. Axis 0 is L (or n_inv) — never sharded. Axis 1
        (batch) goes on the DP axes when divisible; otherwise (long_500k's
        B=1) the SEQUENCE dim of attention caches is sharded on the DP axes
        instead (sequence parallelism for the KV sweep). The widest remaining
        trailing dim divisible by the model axis is model-sharded (KV
        head_dim / MLA latent / SSM state)."""
        dp = mesh_info.data_size

        def leaf_spec(path, leaf):
            parts: list[Any] = [None] * leaf.ndim
            used = None
            name = getattr(path[-1], "key", None) if path else None
            # head-major K/V [L, B, KV, S, hd] and their scales [L, B, KV, S]
            # keep heads on 2 and positions on 3; MLA latents [L, B, S, r]
            # and SSM state keep the position-major reading of dim 2
            head_major = name in HEAD_MAJOR_CACHE_KEYS
            seq = 3 if head_major else 2
            if leaf.shape[1] % dp == 0 and leaf.shape[1] >= dp:
                parts[1] = mesh_info.batch_axes
            elif (
                leaf.ndim >= 4
                and leaf.shape[seq] % dp == 0
                and leaf.shape[seq] >= dp
            ):
                parts[seq] = mesh_info.batch_axes
                used = seq
            # model-axis placement preference for attention caches
            # [L, B, KV, S, hd]: KV heads first (clean head parallelism),
            # then the SEQUENCE dim (flash-decoding-style split-K: the
            # scores/AV contractions run shard-local + one psum, and the
            # scatter is a masked local write — no resharding copies),
            # then head_dim as the last resort.
            if head_major:
                order = [2, 3, leaf.ndim - 1]
            elif leaf.ndim >= 4:
                order = [3, 2, leaf.ndim - 1]
            else:
                order = list(range(leaf.ndim - 1, 1, -1))
            for i in order:
                if i == used or i >= leaf.ndim or parts[i] is not None:
                    continue
                if leaf.shape[i] % mesh_info.model_size == 0 and leaf.shape[i] >= mesh_info.model_size:
                    parts[i] = "model"
                    break
            return mesh_info.named(jax.sharding.PartitionSpec(*parts))

        return jax.tree_util.tree_map_with_path(leaf_spec, cache_shape)

    # ---------------------------------------------------------------- prefill

    def prefill(
        self, params: Params, batch: dict, max_len: int
    ) -> tuple[jax.Array, Params]:
        """Full-sequence forward that also fills the decode cache.

        Returns (logits [B,S,V], cache padded to ``max_len``). The caller
        continues with ``decode_step(..., cur_len=S)``.
        """
        cfg = self.cfg
        if "embeds" in batch:
            x = batch["embeds"].astype(self.dtype)
        else:
            x = embed_tokens(params["embed"], batch["tokens"])
        b, s = x.shape[:2]
        assert s <= max_len, (s, max_len)
        positions = jnp.arange(s, dtype=jnp.int32)

        def pad_seq(t):  # [B,S,...] -> [B,max_len,...]
            widths = [(0, 0), (0, max_len - s)] + [(0, 0)] * (t.ndim - 2)
            return jnp.pad(t, widths)

        if cfg.family in ("dense", "moe"):

            def body(xx, blk):
                xx, piece = self._block_forward_capture(blk, xx, positions)
                return xx, piece

            if cfg.family == "dense":
                x, pieces = jax.lax.scan(body, x, params["blocks"])
            else:
                pieces_list = []
                if cfg.first_k_dense:
                    x, pd = jax.lax.scan(body, x, params["dense_blocks"])
                    pieces_list.append(pd)
                x, pm = jax.lax.scan(body, x, params["moe_blocks"])
                pieces_list.append(pm)
                pieces = jax.tree.map(
                    lambda *ts: jnp.concatenate(ts, axis=0), *pieces_list
                ) if len(pieces_list) > 1 else pieces_list[0]
            cache = jax.tree.map(
                lambda t: pad_seq_axis(t, t.ndim - 2, max_len), pieces
            )
        elif cfg.family == "ssm":

            def body(xx, blk):
                h = rms_norm(xx, blk["norm"], cfg.norm_eps)
                fn = (
                    ssm_mod.mamba1_apply
                    if cfg.ssm.variant == "mamba1"
                    else ssm_mod.mamba2_apply
                )
                out, st = fn(blk["mamba"], cfg, h, return_state=True)
                return xx + out, st

            x, cache = jax.lax.scan(body, x, params["blocks"])
        elif cfg.family == "hybrid":
            every = cfg.shared_attn_every
            n_groups = cfg.n_layers // every
            grouped = jax.tree.map(
                lambda p: p.reshape(n_groups, every, *p.shape[1:]), params["blocks"]
            )
            shared = params["shared"]

            def group_body(xx, gblk):
                def inner(xxx, blk):
                    h = rms_norm(xxx, blk["norm"], cfg.norm_eps)
                    out, st = ssm_mod.mamba2_apply(blk["mamba"], cfg, h, return_state=True)
                    return xxx + out, st

                xx, sts = jax.lax.scan(inner, xx, gblk)
                h = rms_norm(xx, shared["norm1"], cfg.norm_eps)
                a, (kc, vc) = attn_mod.attention_apply(
                    shared["attn"], cfg, h, positions, return_kv=True,
                    mesh_info=self.mesh_info,
                )
                xx = xx + a
                h = rms_norm(xx, shared["norm2"], cfg.norm_eps)
                xx = xx + mlp_apply(shared["mlp"], h)
                return xx, (sts, kc, vc)

            x, (mcache, ks, vs) = jax.lax.scan(group_body, x, grouped)
            cache = {
                "mamba": jax.tree.map(
                    lambda t: t.reshape(cfg.n_layers, *t.shape[2:]), mcache
                ),
                "attn_k": pad_seq_axis(ks, 3, max_len),
                "attn_v": pad_seq_axis(vs, 3, max_len),
            }
        else:
            raise ValueError(cfg.family)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params["embed"], x)
        return logits, cache

    def _block_forward_capture(self, blk, x, positions):
        """Dense/MoE block forward that also emits this layer's cache piece."""
        cfg = self.cfg
        h = rms_norm(x, blk["norm1"], cfg.norm_eps)
        if cfg.mla is not None:
            a, (ckv, krope) = mla_mod.mla_apply(
                blk["attn"], cfg, h, positions, return_kv=True
            )
            piece = {"ckv": ckv, "krope": krope}
        else:
            a, (k, v) = attn_mod.attention_apply(
                blk["attn"], cfg, h, positions, return_kv=True,
                mesh_info=self.mesh_info,
            )
            piece = {"k": k, "v": v}
        x = x + a
        h = rms_norm(x, blk["norm2"], cfg.norm_eps)
        if "moe" in blk:
            out, _ = moe_mod.moe_apply(blk["moe"], cfg, h, mesh_info=self.mesh_info)
            x = x + out
        else:
            x = x + mlp_apply(blk["mlp"], h)
        return x, piece

    # ------------------------------------------------------------ decode step

    def _block_decode(
        self, blk: Params, x: jax.Array, cache_l: Params, cur_len: jax.Array,
        block_tables: Optional[jax.Array] = None,
    ) -> tuple[jax.Array, Params]:
        """One layer's decode. cache_l leaves have NO leading L axis here."""
        cfg = self.cfg
        h = rms_norm(x, blk["norm1"], cfg.norm_eps)
        if cfg.mla is not None:
            a, ckv, krope = mla_mod.mla_decode(
                blk["attn"], cfg, h, cache_l["ckv"], cache_l["krope"], cur_len
            )
            new_cache = {"ckv": ckv, "krope": krope}
        elif "k_scale" in cache_l:  # quantized-row cache: scales ride along
            a, ck, cv, cks, cvs = attn_mod.attention_decode(
                blk["attn"], cfg, h, cache_l["k"], cache_l["v"], cur_len,
                mesh_info=self.mesh_info, block_tables=block_tables,
                k_scale=cache_l["k_scale"], v_scale=cache_l["v_scale"],
            )
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        else:
            a, ck, cv = attn_mod.attention_decode(
                blk["attn"], cfg, h, cache_l["k"], cache_l["v"], cur_len,
                mesh_info=self.mesh_info, block_tables=block_tables,
            )
            new_cache = {"k": ck, "v": cv}
        x = x + a
        h = rms_norm(x, blk["norm2"], cfg.norm_eps)
        if "moe" in blk:
            out, _ = moe_mod.moe_apply(blk["moe"], cfg, h, mesh_info=self.mesh_info)
            x = x + out
        else:
            x = x + mlp_apply(blk["mlp"], h)
        return x, new_cache

    def _mamba_decode(self, blk, x, cache_l):
        cfg = self.cfg
        h = rms_norm(x, blk["norm"], cfg.norm_eps)
        fn = ssm_mod.mamba1_decode if cfg.ssm.variant == "mamba1" else ssm_mod.mamba2_decode
        out, new_cache = fn(blk["mamba"], cfg, h, cache_l)
        return x + out, new_cache

    def _ssm_packed(
        self, params: Params, cache: Params, x: jax.Array,
        tok_pos: jax.Array, pack_slots: jax.Array, max_len: int,
    ) -> tuple[jax.Array, Params]:
        """Single-slot packed chunk for the recurrent-state family.

        x: [T, d] embedded tokens — ONE contiguous chunk of slot
        ``pack_slots[0]``'s stream (ascending positions, bucket padding
        after the real rows with the ``tok_pos >= max_len`` sentinel).
        Gathers that slot's (h, conv) state, runs the state-passing chunk
        scan per layer, and scatters the updated state back — O(chunk)
        work and O(1) state bytes regardless of how long the stream gets.
        A chunk that starts at position 0 recycles the state slot (zeros
        in, like a fresh sequence) — admission needs no separate cache
        wipe, mirroring how attention slots tolerate stale rows."""
        cfg = self.cfg
        pos = jnp.asarray(tok_pos, jnp.int32)
        slot = jnp.asarray(pack_slots, jnp.int32)[0]
        n_real = jnp.sum(pos < max_len).astype(jnp.int32)
        fresh = pos[0] == 0  # first chunk of a prompt
        fn = (
            ssm_mod.mamba1_packed
            if cfg.ssm.variant == "mamba1"
            else ssm_mod.mamba2_packed
        )

        def body(xx, xs):
            blk, cl = xs  # cl leaves: [B, ...] (no L axis)
            sl = jax.tree.map(
                lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=0),
                cl,
            )
            sl = jax.tree.map(
                lambda c: jnp.where(fresh, jnp.zeros_like(c), c), sl
            )
            h = rms_norm(xx, blk["norm"], cfg.norm_eps)
            out, new_sl = fn(blk["mamba"], cfg, h, sl, n_real)
            ncl = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_slice_in_dim(
                    c, n.astype(c.dtype), slot, axis=0
                ),
                cl, new_sl,
            )
            return xx + out, ncl

        xb, new_cache = jax.lax.scan(body, x[None], (params["blocks"], cache))
        return xb[0], new_cache

    # ------------------------------------------------------------ packed step

    @property
    def supports_packed(self) -> bool:
        """Whether the unified ragged prefill+decode dispatch applies.

        Dense/MoE attention scatters positional K/V rows, MLA scatters
        compressed latent rows (``mla_packed``), and SSM rides a
        state-passing single-slot chunk (``mamba{1,2}_packed`` — the
        engine packs recurrent-state admissions one slot per pack).
        Hybrid interleaves recurrent state with a shared attention cache
        and keeps the exact-length prefill + per-step decode path."""
        return self.cfg.family in ("dense", "moe", "ssm")

    @property
    def has_positional_kv(self) -> bool:
        """Whether the decode cache stores one K/V row per position — the
        precondition for paging (block pool indirection) and quantized-row
        storage. The MLA latent cache is positional but compressed-latent
        shaped (no per-head K/V rows for the quant/paged plumbing), and
        SSM state is constant-size — neither pages nor quantizes."""
        return self.cfg.family in ("dense", "moe") and self.cfg.mla is None

    @property
    def family_tag(self) -> str:
        """Human-readable family label for error messages ('moe+mla' when
        the attention is latent, else the bare family)."""
        if self.cfg.mla is not None:
            return f"{self.cfg.family}+mla"
        return self.cfg.family

    def _block_packed(
        self, blk: Params, x: jax.Array, cache_l: Params,
        tok_slot: jax.Array, tok_pos: jax.Array, valid: Optional[jax.Array],
        pack_slots: Optional[jax.Array],
        block_tables: Optional[jax.Array] = None,
    ) -> tuple[jax.Array, Params]:
        """One layer over a packed [T] token batch. cache_l has no L axis."""
        cfg = self.cfg
        h = rms_norm(x, blk["norm1"], cfg.norm_eps)
        if cfg.mla is not None:  # latent-space packed step (never paged)
            a, nckv, nkrope = mla_mod.mla_packed(
                blk["attn"], cfg, h, cache_l["ckv"], cache_l["krope"],
                tok_slot, tok_pos, valid, pack_slots,
            )
            new_cache = {"ckv": nckv, "krope": nkrope}
        elif "k_scale" in cache_l:  # quantized-row cache: scales ride along
            a, ck, cv, cks, cvs = attn_mod.attention_packed(
                blk["attn"], cfg, h, cache_l["k"], cache_l["v"],
                tok_slot, tok_pos, valid, pack_slots,
                mesh_info=self.mesh_info, block_tables=block_tables,
                k_scale=cache_l["k_scale"], v_scale=cache_l["v_scale"],
            )
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        else:
            a, ck, cv = attn_mod.attention_packed(
                blk["attn"], cfg, h, cache_l["k"], cache_l["v"],
                tok_slot, tok_pos, valid, pack_slots,
                mesh_info=self.mesh_info, block_tables=block_tables,
            )
            new_cache = {"k": ck, "v": cv}
        x = x + a
        h = rms_norm(x, blk["norm2"], cfg.norm_eps)
        if "moe" in blk:
            out, _ = moe_mod.moe_apply(
                blk["moe"], cfg, h[None], mesh_info=self.mesh_info
            )
            x = x + out[0]
        else:
            x = x + mlp_apply(blk["mlp"], h)
        return x, new_cache

    def packed_step(
        self,
        params: Params,
        cache: Params,
        tokens: jax.Array,
        tok_slot: jax.Array,
        tok_pos: jax.Array,
        out_rows: Optional[jax.Array] = None,
        pack_slots: Optional[jax.Array] = None,
        block_tables: Optional[jax.Array] = None,
        max_len: Optional[int] = None,
    ) -> tuple[jax.Array, Params]:
        """Unified ragged prefill+decode step: one flat [T] token batch where
        each token carries its own (cache slot, absolute position) — decode
        slots contribute one token, admitting prompts a prefill chunk.

        tokens/tok_slot/tok_pos: [T] int32. Requires ``supports_packed``.
        Returns (logits [T, V], new_cache) — or logits [len(out_rows), V]
        when ``out_rows`` selects the packed rows to unembed (the serving
        engine only samples a chunk's final token, so the [T, V] logits for
        every mid-chunk row are dead weight). With ``pack_slots`` ([P]
        int32), ``tok_slot`` holds indices into it and attention reads only
        those P cache rows (see ``attention_packed``). Padding tokens (a
        pack rounded up to its bucket) should use ``tok_pos >= max_len``:
        their cache writes are dropped and their logits rows are garbage to
        ignore.

        With ``block_tables`` ([B, max_blocks] int32), ``cache`` is a
        block-paged pool from :meth:`init_kv_pool` and every (slot, pos)
        resolves to (block, offset) through the slot's table row — the
        SAME step otherwise (same descriptors, same mask, same sampling
        rows), which is what keeps paged and dense serving bit-identical.

        SSM packs carry one slot per pack (``pack_slots[0]``; the engine
        enforces pack width 1 for recurrent families): the whole [T] batch
        is one contiguous chunk of that slot's stream, and ``max_len`` is
        required — tok_pos >= max_len identifies the bucket padding whose
        rows must be state-identities rather than merely masked.
        """
        cfg = self.cfg
        assert self.supports_packed, cfg.family
        x = embed_tokens(params["embed"], tokens)  # [T, d]
        if cfg.family == "ssm":
            assert block_tables is None, "SSM state is never paged"
            assert max_len is not None, "SSM packed step needs max_len"
            assert pack_slots is not None, "SSM packs carry pack_slots"
            x, new_cache = self._ssm_packed(
                params, cache, x, tok_pos, pack_slots, max_len
            )
            if out_rows is not None:
                x = x[out_rows]
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            return unembed(params["embed"], x), new_cache
        # the attention mask depends only on the pack descriptors — compute
        # it once and share it across every layer
        from repro.kernels import ref as _ref

        if block_tables is None:
            # [L, B, KV, S_max, hd] head-major rows or [L, B, S_max, r]
            # compressed latents — positions sit on ndim-2 of both
            k_leaf = cache["ckv"] if cfg.mla is not None else cache["k"]
            n_rows = k_leaf.shape[1] if pack_slots is None else len(pack_slots)
            s_max = k_leaf.shape[-2]
        else:  # pool leaf [L, NB, KV, bs, hd]: S_max = table width * block
            n_rows = (
                block_tables.shape[0] if pack_slots is None else len(pack_slots)
            )
            s_max = block_tables.shape[1] * cache["k"].shape[3]
        valid = _ref.ragged_valid_mask(
            tok_slot, tok_pos, n_rows, s_max, cfg.sliding_window
        )

        def body(xx, xs):
            blk, cl = xs
            xx, ncl = self._block_packed(
                blk, xx, cl, tok_slot, tok_pos, valid, pack_slots,
                block_tables,
            )
            return xx, ncl

        if cfg.family == "moe" and cfg.first_k_dense:
            kd = cfg.first_k_dense
            dense_cache = jax.tree.map(lambda c: c[:kd], cache)
            moe_cache = jax.tree.map(lambda c: c[kd:], cache)
            x, nd = jax.lax.scan(body, x, (params["dense_blocks"], dense_cache))
            x, nm = jax.lax.scan(body, x, (params["moe_blocks"], moe_cache))
            new_cache = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), nd, nm
            )
        else:
            blocks = params["blocks"] if cfg.family == "dense" else params["moe_blocks"]
            x, new_cache = jax.lax.scan(body, x, (blocks, cache))

        if out_rows is not None:
            x = x[out_rows]
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params["embed"], x)
        return logits, new_cache

    # ------------------------------------------------------------ decode step

    def decode_step(
        self, params: Params, cache: Params, batch: dict, cur_len: jax.Array,
        block_tables: Optional[jax.Array] = None,
    ) -> tuple[jax.Array, Params]:
        """One token for every sequence.

        batch: {'tokens': [B,1]} or {'embeds': [B,1,d]}. cur_len: scalar int32
        (tokens already cached). Returns (logits [B,1,V], new_cache).
        With ``block_tables``, ``cache`` is a paged pool (see
        :meth:`packed_step`) — dense/moe positional-KV families only.
        """
        cfg = self.cfg
        if block_tables is not None and not self.has_positional_kv:
            raise ValueError(f"family {self.family_tag!r} has no paged path")
        if "embeds" in batch:
            x = batch["embeds"].astype(self.dtype)
        else:
            x = embed_tokens(params["embed"], batch["tokens"])

        if cfg.family in ("dense", "moe"):
            if cfg.family == "moe" and cfg.first_k_dense:
                kd = cfg.first_k_dense
                dense_cache = jax.tree.map(lambda c: c[:kd], cache)
                moe_cache = jax.tree.map(lambda c: c[kd:], cache)

                def body_d(xx, xs):
                    blk, cl = xs
                    xx, ncl = self._block_decode(blk, xx, cl, cur_len, block_tables)
                    return xx, ncl

                x, nd = jax.lax.scan(body_d, x, (params["dense_blocks"], dense_cache))
                x, nm = jax.lax.scan(body_d, x, (params["moe_blocks"], moe_cache))
                new_cache = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], axis=0), nd, nm
                )
            else:
                blocks = params["blocks"] if cfg.family == "dense" else params["moe_blocks"]

                def body(xx, xs):
                    blk, cl = xs
                    xx, ncl = self._block_decode(blk, xx, cl, cur_len, block_tables)
                    return xx, ncl

                x, new_cache = jax.lax.scan(body, x, (blocks, cache))
        elif cfg.family == "ssm":

            def body(xx, xs):
                blk, cl = xs
                xx, ncl = self._mamba_decode(blk, xx, cl)
                return xx, ncl

            x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
        elif cfg.family == "hybrid":
            every = cfg.shared_attn_every
            n_groups = cfg.n_layers // every
            grouped_blocks = jax.tree.map(
                lambda p: p.reshape(n_groups, every, *p.shape[1:]), params["blocks"]
            )
            grouped_mcache = jax.tree.map(
                lambda c: c.reshape(n_groups, every, *c.shape[1:]), cache["mamba"]
            )
            shared = params["shared"]

            def group_body(xx, xs):
                gblk, gmc, ak, av = xs

                def inner(xxx, ys):
                    blk, cl = ys
                    xxx, ncl = self._mamba_decode(blk, xxx, cl)
                    return xxx, ncl

                xx, ngmc = jax.lax.scan(inner, xx, (gblk, gmc))
                h = rms_norm(xx, shared["norm1"], cfg.norm_eps)
                a, nak, nav = attn_mod.attention_decode(
                    shared["attn"], cfg, h, ak, av, cur_len,
                    mesh_info=self.mesh_info,
                )
                xx = xx + a
                h = rms_norm(xx, shared["norm2"], cfg.norm_eps)
                xx = xx + mlp_apply(shared["mlp"], h)
                return xx, (ngmc, nak, nav)

            x, (ngm, nak, nav) = jax.lax.scan(
                group_body,
                x,
                (grouped_blocks, grouped_mcache, cache["attn_k"], cache["attn_v"]),
            )
            new_cache = {
                "mamba": jax.tree.map(
                    lambda c: c.reshape(cfg.n_layers, *c.shape[2:]), ngm
                ),
                "attn_k": nak,
                "attn_v": nav,
            }
        else:
            raise ValueError(cfg.family)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params["embed"], x)
        return logits, new_cache


def pad_seq_axis(t: jax.Array, axis: int, max_len: int) -> jax.Array:
    """Pad axis ``axis`` (the cache sequence axis) up to max_len with zeros."""
    cur = t.shape[axis]
    if cur == max_len:
        return t
    widths = [(0, 0)] * t.ndim
    widths[axis] = (0, max_len - cur)
    return jnp.pad(t, widths)


# =============================================================================
# input specs (dry-run stand-ins; ShapeDtypeStruct only, no allocation)
# =============================================================================


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """Model inputs for one (arch × shape) cell as ShapeDtypeStructs.

    * train:   {'tokens': (B,S), 'labels': (B,S)}           (int32)
    * prefill: {'tokens': (B,S)}
    * decode:  {'tokens': (B,1)}  (+ cache/cur_len supplied by the caller)
    Audio archs replace 'tokens' with precomputed frame embeddings
    (B, S, d_model) per the modality-stub rule; labels stay int32 codes.
    """
    B = shape.global_batch
    S = shape.seq_len
    dt_tok = jnp.int32
    dt_emb = jnp.dtype(cfg.dtype)

    def tok_or_embed(s: int) -> dict:
        if cfg.modality == "audio":
            return {"embeds": jax.ShapeDtypeStruct((B, s, cfg.d_model), dt_emb)}
        return {"tokens": jax.ShapeDtypeStruct((B, s), dt_tok)}

    if shape.kind == "train":
        specs = tok_or_embed(S)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), dt_tok)
        return specs
    if shape.kind == "prefill":
        return tok_or_embed(S)
    if shape.kind == "decode":
        return tok_or_embed(1)
    raise ValueError(shape.kind)

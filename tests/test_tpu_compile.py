"""The serving path's attention kernels compile for a TPU v5e at the widths
the repo serves, without a chip: the TPU compiler is installed, and it
compiles for a described ``v5e:2x2`` topology. Interpret mode cannot catch
what this catches — block shapes the chip's (sublane, lane) tiling refuses,
or a kernel that falls back to plain XLA. Every compile must contain the
Pallas ``tpu_custom_call``.

Shapes: codeqwen1.5-7b in bf16 (H=32, KV=4, hd=128) on a 16-slot,
4096-position cache, packs of T=64 tokens, and the paged pool at
``kv_block_size=16``; the MLA kernel at minicpm3-4b widths.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops

B, S_MAX, T, BLOCK = 16, 4096, 64, 16
CFG = get_arch("codeqwen1.5-7b")
H, KV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _kv(dtype, quant, *lead):
    """(k, k_scale, v, v_scale) shapes for a head-major cache or pool."""
    payload = ((*lead[:1], KV, *lead[1:], HD), dtype)
    scale = ((*lead[:1], KV, *lead[1:]), F32)
    return (payload, scale, payload, scale) if quant else (payload, payload)


def _call(op, quant, **static):
    """Adapt ``ops.<op>(q, k, v, *desc, k_scale=, v_scale=)`` to positional
    args in the (q, k, [ks], v, [vs], *desc) order of ``_kv``."""
    def fn(q, *rest):
        if quant:
            k, ks, v, vs, *desc = rest
        else:
            (k, v, *desc), ks, vs = rest, None, None
        return op(q, k, v, *desc, mode="pallas", k_scale=ks, v_scale=vs, **static)

    return fn


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_decode_attention_compiles(one_chip, kv_dtype):
    quant = kv_dtype == "int8"
    _compile(
        _call(ops.decode_attention, quant), one_chip,
        ((B, H, HD), BF16), *_kv(jnp.dtype(kv_dtype), quant, B, S_MAX), ((B,), I32),
    )


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_stacked_decode_attention_compiles(one_chip, kv_dtype):
    # the decode layer loop's carried [L, B, KV, S, hd] cache, read at a
    # traced layer index (L = 16, the codeqwen cut the benchmark serves)
    quant = kv_dtype == "int8"
    k, *rest = _kv(jnp.dtype(kv_dtype), quant, B, S_MAX)
    stack = lambda spec: ((16, *spec[0]), spec[1])  # noqa: E731
    layered = _call(ops.decode_attention, quant)

    def fn(q, *args):
        *cache, cur, layer = args
        return layered(q, *cache, cur, layer)

    _compile(
        fn, one_chip,
        ((B, H, HD), BF16), *map(stack, (k, *rest)), ((B,), I32), ((), I32),
    )


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_carried_cache_layer_loop_copies_no_cache(one_chip, kv_dtype):
    """The decode layer loop as the model runs it: the stacked cache is the
    carry, each layer writes one row per slot (``_scatter_step`` at a layer)
    and the kernel reads that layer in place. The compiled loop holds no
    copy of a stacked leaf: a write the chip's tiles cannot take in place
    makes the compiler relayout the whole cache around every kernel call."""
    from repro.models.attention import _scatter_step

    quant = kv_dtype == "int8"
    n_layers = 4
    leaves = [((n_layers, *s), d) for s, d in _kv(jnp.dtype(kv_dtype), quant, B, S_MAX)]

    def loop(q, new, cur, *cache):
        def body(cache, layer):
            rows = [new if c.ndim == 5 else new[..., 0] for c in cache]
            cache = [_scatter_step(c, r, cur, 2, layer) for c, r in zip(cache, rows)]
            k, ks, v, vs = cache if quant else (cache[0], None, cache[1], None)
            o = ops.decode_attention(
                q, k, v, cur, layer, mode="pallas", k_scale=ks, v_scale=vs
            )
            return cache, o

        return jax.lax.scan(body, list(cache), jnp.arange(n_layers, dtype=I32))

    hlo = _compile(
        loop, one_chip, ((B, H, HD), BF16), ((B, KV, 1, HD), F32), ((B,), I32),
        *leaves, donate=tuple(range(3, 3 + len(leaves))),
    )
    stacked = {s for s, _ in leaves}
    copies = [
        tuple(int(d) for d in m.split(","))
        for m in re.findall(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", hlo)
    ]
    assert not stacked & set(copies), copies


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_ragged_attention_compiles(one_chip, kv_dtype):
    quant = kv_dtype == "int8"
    _compile(
        _call(ops.ragged_attention, quant), one_chip,
        ((T, H, HD), BF16), *_kv(jnp.dtype(kv_dtype), quant, B, S_MAX),
        ((T,), I32), ((T,), I32),
    )


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_ragged_attention_compiles(one_chip, kv_dtype):
    quant = kv_dtype == "int8"
    nb, maxb = B * S_MAX // BLOCK, S_MAX // BLOCK
    _compile(
        _call(ops.paged_ragged_attention, quant), one_chip,
        ((T, H, HD), BF16), *_kv(jnp.dtype(kv_dtype), quant, nb, BLOCK),
        ((T,), I32), ((T,), I32), ((B, maxb), I32),
    )


def test_gqa_flash_attention_compiles(one_chip):
    # fused admission: one B=1 prompt of the prefill budget
    _compile(
        lambda q, k, v: ops.gqa_flash_attention(q, k, v, mode="pallas"), one_chip,
        ((1, H, 64, HD), BF16), ((1, KV, 64, HD), BF16), ((1, KV, 64, HD), BF16),
    )


def test_mla_ragged_attention_compiles(one_chip):
    m = get_arch("minicpm3-4b")
    r, rope, h = m.mla.kv_lora_rank, m.mla.rope_head_dim, m.n_heads
    _compile(
        lambda qe, qr, ckv, kr, sl, po: ops.mla_ragged_attention(
            qe, qr, ckv, kr, sl, po, scale=0.1, mode="pallas"
        ),
        one_chip,
        ((T, h, r), BF16), ((T, h, rope), BF16), ((B, S_MAX, r), BF16),
        ((B, S_MAX, rope), BF16), ((T,), I32), ((T,), I32),
    )

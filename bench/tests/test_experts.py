"""An expert model with a leading dense layer (``tiny.MOE``, shaped as
DeepSeek-V2-Lite) through the benchmark on the CPU: seeded weights per
layer stack, the ``deepseek_v2`` reference against the program, the
harness's key checks, planted faults in the program's expert layer, and
the expert share."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, weights
from bench.reference import deepseek_v2
from bench.tests import test_faults, tiny

SEED = 2**33 + 4321
LIMIT = test_faults.LIMIT  # float32 program against float32 reference
EXPERT_LEAVES = ("moe_blocks/moe/w_in", "moe_blocks/moe/w_gate", "moe_blocks/moe/w_out")


def _program(cell):
    from repro.models import LM

    model = LM(harness.arch_config(cell.conf))
    return model, weights.build(model.param_specs(), SEED)


def _leaves(tree) -> dict:
    return {weights._path_str(p): leaf for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_stacks_regenerate_bit_for_bit():
    """Every layer of ``dense_blocks`` and ``moe_blocks`` comes back from
    ``layer_leaf`` as served, the float32 router among them."""
    cell = tiny.cell("moe")
    cell.conf["deployment"]["dtype"] = "bfloat16"
    hf = harness.as_run(cell.conf)
    _, params = _program(cell)
    leaves = _leaves(params)
    assert leaves["moe_blocks/moe/router"].dtype == jnp.float32
    assert leaves["moe_blocks/moe/w_in"].dtype == jnp.bfloat16
    seen = set()
    for l in range(hf["num_hidden_layers"]):
        w = check.layer_weights(deepseek_v2, hf, SEED, l, "bfloat16")
        for k, (path, i, _, dtype) in deepseek_v2.layer_leaves(hf, l).items():
            assert (dtype or "bfloat16") == leaves[path].dtype, path
            served = np.asarray(leaves[path][i].astype(jnp.float32))
            assert np.array_equal(np.asarray(w[k]), served), (path, l)
            seen.add(path)
    assert seen == {p for p in leaves if weights.stacked(p)}
    # the router regenerated in the deployment's dtype would not be the one served
    shape = leaves["moe_blocks/moe/router"].shape[1:]
    rounded = weights.layer_leaf(SEED, "moe_blocks/moe/router", 0, shape, "bfloat16")
    assert not np.array_equal(np.asarray(rounded), np.asarray(leaves["moe_blocks/moe/router"][0]))


@pytest.mark.parametrize("path,fan_in", [(EXPERT_LEAVES[0], 64), (EXPERT_LEAVES[1], 64),
                                         (EXPERT_LEAVES[2], 32)])
def test_expert_leaf_std_follows_its_fan_in(path, fan_in):
    """An expert stack ``[E, fan_in, ...]`` takes its fan-in after the
    expert axis (d for w_in and w_gate, f for w_out), not E."""
    _, params = _program(tiny.cell("moe"))
    std = float(np.std(np.asarray(_leaves(params)[path])))
    assert abs(std * fan_in**0.5 - 1.0) < 0.03, std


# CRC32 over (path, bytes) of every leaf of the tiny trees at seed 2**33 +
# 12345, as the generator drew them before it knew more than one stack
PARENT_CRC = {("qwen", "float32"): 0xCED07588, ("qwen", "bfloat16"): 0x20AE4137,
              ("mla", "float32"): 0xA31AEC52, ("mla", "bfloat16"): 0x467993C6}


@pytest.mark.parametrize("family,dtype", sorted(PARENT_CRC))
def test_dense_weight_trees_are_unchanged(family, dtype):
    from repro.models import LM

    cell = tiny.cell(family)
    cell.conf["deployment"]["dtype"] = dtype
    tree = weights.build(LM(harness.arch_config(cell.conf)).param_specs(), 2**33 + 12345)
    crc = 0
    for path, leaf in _leaves(tree).items():
        crc = zlib.crc32(np.asarray(leaf).tobytes(), zlib.crc32(path.encode(), crc))
    assert crc == PARENT_CRC[(family, dtype)], hex(crc)


def _greedy(cell, prompt, n_new):
    """The program's logits at every position and its greedy tokens: the
    prompt through one ``packed_step``, then one ``decode_step`` a token."""
    model, params = _program(cell)
    cache = model.init_cache(1, cell.conf["deployment"]["max_len"])
    n = len(prompt)
    logits, cache = model.packed_step(params, cache, jnp.asarray(prompt),
                                      jnp.zeros(n, jnp.int32), jnp.arange(n, dtype=jnp.int32))
    rows, served = [np.asarray(logits)], [int(np.argmax(logits[-1]))]
    step = jax.jit(model.decode_step)
    for p in range(n, n + n_new - 1):
        tok = {"tokens": jnp.asarray([[served[-1]]], jnp.int32)}
        lg, cache = step(params, cache, tok, jnp.int32(p))
        rows.append(np.asarray(lg[:, 0]))
        served.append(int(np.argmax(rows[-1][0])))
    return np.concatenate(rows).astype(np.float64), np.asarray(served, np.int32)


def _reference_logits(cell, tokens):
    hf = harness.as_run(cell.conf)
    with jax.default_matmul_precision("highest"):
        plain = {k: weights.plain_leaf(SEED, p, s, "float32")
                 for k, (p, s) in deepseek_v2.plain_leaves(hf).items()}
        n = check._pad(len(tokens))
        x = deepseek_v2.embed(plain, jnp.asarray(np.pad(tokens, (0, n - len(tokens)))), hf)
        for l in range(hf["num_hidden_layers"]):
            x = deepseek_v2.layer(x, check.layer_weights(deepseek_v2, hf, SEED, l, "float32"),
                                  hf, "f32")
        return np.asarray(deepseek_v2.logits(x[: len(tokens)], plain, hf, "f32"), np.float64)


def test_reference_matches_prefill_then_decode():
    cell = tiny.cell("moe")
    prompt = np.random.default_rng(1).integers(0, 512, size=24).astype(np.int32)
    got, served = _greedy(cell, prompt, 16)
    want = _reference_logits(cell, np.concatenate([prompt, served[:-1]]))
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-4, err  # the tolerance of test_reference.py
    assert (got.argmax(-1) == want.argmax(-1)).mean() == 1.0
    g = check.gaps(cell.conf, SEED, [(prompt, served)])
    assert g["tokens"] == 16 and g["max_gap"] <= LIMIT, g


def _run(traffic="reasoning"):
    cell = tiny.cell("moe", traffic, max_gap=LIMIT)
    return harness.run(cell, SEED, 2.0, False, require_chip=False, log=lambda m: None)


def test_sound_expert_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]


def _expert_left_out(monkeypatch):
    from repro.models import moe

    real = moe._moe_shard

    def _moe_shard(x, router, w_in, w_gate, w_out, cfg, model_axis):
        return real(x, router, w_in, w_gate, w_out.at[0].set(0), cfg, model_axis)

    monkeypatch.setattr(moe, "_moe_shard", _moe_shard)


def _unnormalised(monkeypatch):
    from repro.models import moe

    real = moe._moe_shard

    def _moe_shard(x, router, w_in, w_gate, w_out, cfg, model_axis):
        out, aux = real(x, router, w_in, w_gate, w_out, cfg, model_axis)
        probs = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
        total = jax.lax.top_k(probs, cfg.moe.top_k)[0].sum(-1, keepdims=True)
        # the routed sum weighs each expert by p / total: times total, by p
        return (out.astype(jnp.float32) * total).astype(out.dtype), aux

    monkeypatch.setattr(moe, "_moe_shard", _moe_shard)


@pytest.mark.parametrize("fault", [_expert_left_out, _unnormalised,
                                   test_faults._state_unchanged, test_faults._half_batch,
                                   test_faults._token_altered])
def test_expert_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]
    if fault in (_expert_left_out, _unnormalised):  # the served tokens' gap shows it
        assert res["checks"]["max_gap"]["value"] > LIMIT, res["checks"]


def test_expert_shares_sum_to_the_uncut_layer():
    """8 experts cut into two shares of 4: the shares' routed parts, with
    the shared experts once, are the uncut layer's feed-forward."""
    cell = tiny.cell("moe")
    hf = harness.as_run(cell.conf)
    w = check.layer_weights(deepseek_v2, hf, SEED, 1, "float32")  # the first expert layer
    x = jax.random.normal(jax.random.key(5), (48, hf["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        routed, shared = deepseek_v2.experts(x, w, hf, "f32")
        parts = []
        for first in (0, 4):
            cell.conf["config"]["n_routed_experts"] = 4
            cell.conf["deployment"]["experts"] = {"published": 8, "shards": 2, "first": first}
            hf_share = harness.as_run(cell.conf)
            leaves = deepseek_v2.layer_leaves(hf_share, 1)
            assert leaves["e_in"][2][0] == 4 and leaves["router"][2] == (64, 8)
            held = {k: w[k][first:first + 4] for k in ("e_in", "e_gate", "e_out")}
            r, s = deepseek_v2.experts(x, {**w, **held}, hf_share, "f32")
            np.testing.assert_array_equal(np.asarray(s), np.asarray(shared))
            assert float(jnp.max(jnp.abs(r - routed))) > 1e-2  # a share is not the whole
            parts.append(r)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(routed + shared), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("config,experts,ok", [
    ({}, None, True),
    ({"q_lora_rank": 0}, None, True),  # null and 0: no query LoRA
    ({}, {"published": 8, "shards": 1, "first": 0}, True),
    ({"q_lora_rank": 16}, None, False),
    ({"intermediate_size": 32}, None, False),  # the dense width, not the expert's
    ({"moe_intermediate_size": 128}, None, False),
    ({"num_experts_per_tok": 3}, None, False),
    ({"n_shared_experts": 2}, None, False),
    ({"first_k_dense_replace": 0}, None, False),
    ({"n_routed_experts": 4}, {"published": 8, "shards": 2, "first": 0}, False),  # holds 8
    ({}, {"published": 16, "shards": 2, "first": 0}, False),  # routes over 8
    ({"n_routed_experts": 4}, {"published": 8, "shards": 2, "first": 2}, False),  # no block
])
def test_arch_config_checks_the_expert_keys(config, experts, ok):
    cell = tiny.cell("moe")
    cell.conf["config"].update(config)
    if experts:
        cell.conf["deployment"]["experts"] = experts
    if ok:
        assert harness.arch_config(cell.conf).first_k_dense == 1
    else:
        with pytest.raises(ValueError):
            harness.arch_config(cell.conf)


@pytest.mark.parametrize("key,value", [("rope_scaling", tiny.MOE["rope_scaling"]),
                                       ("topk_method", "group_limited_greedy"),
                                       ("scoring_func", "sigmoid")])
def test_reference_refuses_what_it_does_not_implement(key, value):
    hf = {**harness.as_run(tiny.cell("moe").conf), key: value}
    with pytest.raises(NotImplementedError):
        deepseek_v2.layer_leaves(hf, 0)


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(j, jax.extend.core.ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, jax.extend.core.Jaxpr):
                yield j


def _scans(jaxpr, inside=False):
    """(scan equation, whether it lies in another loop) of ``jaxpr``, and the
    size of the largest value made inside any loop's body."""
    found, biggest = [], 0
    for eqn in jaxpr.eqns:
        if inside:
            biggest = max([biggest] + [int(np.prod(v.aval.shape)) for v in eqn.outvars])
        is_scan = eqn.primitive.name == "scan"
        if is_scan:
            found.append(eqn)
        for sub in _subjaxprs(eqn):
            f, b = _scans(sub, inside or is_scan)
            found += f
            biggest = max(biggest, b)
    return found, biggest


def test_full_size_weights_are_drawn_one_layer_at_a_time():
    """DeepSeek-V2-Lite's whole tree as ``build`` would make it, traced and
    not run: each stacked leaf is one loop of one layer an iteration, and
    no loop makes a value larger than one layer of the largest stack."""
    from repro.configs import get_arch
    from repro.models import LM

    specs = _leaves(LM(get_arch("deepseek-v2-lite-16b")).param_specs())
    jaxpr = jax.make_jaxpr(lambda: weights.build(specs, SEED))()
    scans, biggest = _scans(jaxpr.jaxpr)
    layer = max(int(np.prod(s.shape[1:])) for p, s in specs.items() if weights.stacked(p))
    assert layer == 64 * 2048 * 1408  # one layer of an expert stack
    assert biggest <= layer, biggest
    stacks = sorted(s.shape for p, s in specs.items() if weights.stacked(p))
    drawn = sorted(tuple(v.aval.shape) for e in scans for v in e.outvars
                   if tuple(v.aval.shape) in stacks)
    assert drawn == stacks
    for e in scans:
        if tuple(e.outvars[0].aval.shape) in stacks:
            assert e.params["length"] == e.outvars[0].aval.shape[0]

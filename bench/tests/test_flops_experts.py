"""The operation counts of ``bench/flops`` for an expert model, worked out
by hand, and the counts for the two published configurations pinned as
they were before expert layers were counted."""

import pytest

from bench import harness
from bench.flops import mla, model
from bench.tests import tiny

# d 64, 4 heads, nope 16, rope 8, v 16, kv_lora 16, no query LoRA; dense
# width 128; expert layers: 8 experts of width 32, top 2, 1 shared
MOE = harness.as_run(tiny.cell("moe").conf)
ATTN = 64 * 4 * 24 + 64 * (16 + 8) + 16 * 4 * (16 + 16) + 4 * 16 * 64
ATTN_QLORA = 64 * 32 + 32 * 4 * 24 + 64 * (16 + 8) + 16 * 4 * (16 + 16) + 4 * 16 * 64


def _conf(name):
    return harness.as_run(harness.load_json(harness.HERE / "configs" / f"{name}.json"))


# (weight_flops, attn_flops_per_key, head_flops, tokens_flops(0, 256, 1),
# tokens_flops(1023, 1024, 1), tokens_flops(100, 612, 0)), as counted
# before expert layers were
PINNED = {
    "codeqwen1.5-7b-16L": (405798912.0, 16384.0, 757071872.0, 1671532904448.0,
                           7518289920.0, 3372153307136.0),
    "minicpm3-4b": (125337600.0, 12800.0, 376053760.0, 2015840706560.0,
                    8959631360.0, 4123570995200.0),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_published_configurations_count_as_before(name):
    hf = _conf(name)
    got = (model.weight_flops(hf), model.attn_flops_per_key(hf), model.head_flops(hf),
           model.tokens_flops(hf, 0, 256, 1), model.tokens_flops(hf, 1023, 1024, 1),
           model.tokens_flops(hf, 100, 612, 0))
    assert got == PINNED[name]


def test_mla_decode_call_counts_as_before():
    assert mla.mla_decode_call(_conf("minicpm3-4b"), [257, 1000, 4096]) == (
        232962560.0, 3213888.0)


@pytest.mark.parametrize("q_lora,attn", [(None, ATTN), (32, ATTN_QLORA)])
def test_expert_model_layers_by_hand(q_lora, attn):
    hf = {**MOE, "q_lora_rank": q_lora}
    assert model.weight_flops(hf) == 2 * (attn + 3 * 64 * 128)  # the dense layer
    # router over 8, one shared expert and the token's 2 routed ones
    assert model.expert_weight_flops(hf) == 2 * (attn + 64 * 8 + 3 * 64 * 32 * (1 + 2))
    # a chip holding 4 of the 8: a token's expected 2 x 4 / 8 routed experts here
    share = {**hf, "n_routed_experts": 4, "n_routed_experts_published": 8}
    assert model.expert_weight_flops(share) == 2 * (attn + 64 * 8 + 3 * 64 * 32 * (1 + 1))


def test_expert_model_tokens_count_each_kind_of_layer():
    hf = MOE
    assert ATTN == 13824
    dense, expert = 2 * (ATTN + 24576), 2 * (ATTN + 512 + 18432)
    per_key = 2 * 4 * (16 + 8 + 16)
    # positions 2, 3, 4 attend 3 + 4 + 5 = 12 keys; one dense layer, two
    # expert layers, one head row
    want = (3 * dense + 12 * per_key) + 2 * (3 * expert + 12 * per_key) + 2 * 64 * 512
    assert model.tokens_flops(hf, 2, 5, with_head=1) == want
    assert model.tokens_flops({**hf, "first_k_dense_replace": 0}, 2, 5, with_head=0) == \
        3 * (3 * expert + 12 * per_key)

"""Config registry + parameter accounting sanity."""

import pytest

from repro.configs import (
    ARCHS,
    SHAPES,
    all_cells,
    applicable,
    get_arch,
    get_shape,
)

EXPECTED_PARAMS = {  # name -> (label_count, tolerance)
    "mistral-large-123b": (123e9, 0.05),
    "qwen3-32b": (32.8e9, 0.10),
    "codeqwen1.5-7b": (7.25e9, 0.01),  # GQA kv=4 (kv=32 would give 8.19e9)
    "minicpm3-4b": (4.0e9, 0.15),
    "musicgen-large": (3.3e9, 0.15),
    "deepseek-v2-lite-16b": (15.7e9, 0.10),
    "llama4-scout-17b-a16e": (109e9, 0.10),
    "zamba2-2.7b": (2.7e9, 0.20),
    "falcon-mamba-7b": (7.3e9, 0.10),
    "chameleon-34b": (34e9, 0.10),
}


def test_registry_complete():
    assert len(ARCHS) == 10
    assert set(EXPECTED_PARAMS) == set(ARCHS)


@pytest.mark.parametrize("name", sorted(EXPECTED_PARAMS))
def test_param_counts(name):
    target, tol = EXPECTED_PARAMS[name]
    got = get_arch(name).num_params()
    assert abs(got - target) / target < tol, (name, got, target)


def test_active_params_moe():
    ds = get_arch("deepseek-v2-lite-16b")
    assert ds.num_active_params() < 0.25 * ds.num_params()
    l4 = get_arch("llama4-scout-17b-a16e")
    assert abs(l4.num_active_params() - 17.2e9) / 17.2e9 < 0.1


def test_cells_and_applicability():
    cells = all_cells()
    # 10 archs × 3 shapes + 2 long_500k (ssm + hybrid)
    assert len(cells) == 32
    long_archs = {a for a, s in cells if s == "long_500k"}
    assert long_archs == {"falcon-mamba-7b", "zamba2-2.7b"}
    for a in ARCHS.values():
        assert applicable(a, SHAPES["train_4k"])


def test_reduced_configs_families_preserved():
    for name, cfg in ARCHS.items():
        red = cfg.reduced()
        assert red.family == cfg.family
        assert (red.mla is None) == (cfg.mla is None)
        assert (red.moe is None) == (cfg.moe is None)
        assert (red.ssm is None) == (cfg.ssm is None)
        assert red.num_params() < 10e6, name


def test_shapes():
    assert get_shape("train_4k").tokens_per_step == 4096 * 256
    assert get_shape("decode_32k").tokens_per_step == 128
    assert get_shape("long_500k").seq_len == 524288

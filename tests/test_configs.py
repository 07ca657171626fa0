"""Config registry: published sizes, divisibility for the production mesh."""
import pytest

from repro.configs.base import ModelConfig
from repro.configs.registry import CONFIGS, get_config, list_archs
from repro.configs.shapes import SHAPES, all_cells, cell_is_applicable

EXPECTED_PARAMS_B = {
    "mamba2-1.3b": (1.2, 1.5),
    "tinyllama-1.1b": (1.0, 1.2),
    "stablelm-12b": (11.5, 12.8),
    "qwen3-14b": (13.5, 15.5),
    "stablelm-3b": (2.5, 3.1),
    "jamba-v0.1-52b": (49.0, 54.0),
    "chameleon-34b": (32.0, 36.0),
    "seamless-m4t-large-v2": (1.8, 2.6),
    "moonshot-v1-16b-a3b": (15.5, 16.5),   # Moonlight-16B-A3B, 27 layers
    "kimi-k2-1t-a32b": (1000.0, 1090.0),
}


def test_ten_archs_present():
    assert len(CONFIGS) == 10
    assert set(EXPECTED_PARAMS_B) == set(CONFIGS)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_counts_match_published(name):
    lo, hi = EXPECTED_PARAMS_B[name]
    total, active = CONFIGS[name].param_counts()
    assert lo <= total / 1e9 <= hi, f"{name}: {total/1e9:.2f}B"
    assert active <= total


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tp_divisibility_for_model_axis_16(name):
    cfg = CONFIGS[name]
    assert cfg.padded_vocab % 16 == 0
    if cfg.d_ff:
        assert cfg.d_ff % 16 == 0
    if cfg.is_moe:
        assert cfg.num_experts % 16 == 0
        assert cfg.moe_d_ff % 16 == 0
    assert cfg.d_model % 16 == 0 or cfg.family == "encdec"
    if cfg.family in ("ssm", "hybrid"):
        assert cfg.ssm_d_inner % 16 == 0
        assert cfg.ssm_num_heads % 16 == 0


def test_active_params_for_moe():
    k = CONFIGS["kimi-k2-1t-a32b"]
    total, active = k.param_counts()
    assert active < 0.05 * total  # 34.8B of 1T


def test_cell_matrix_is_40():
    cells = all_cells(CONFIGS)
    assert len(cells) == 40
    skipped = [c for c in cells if not c[2]]
    # long_500k applicable only to ssm/hybrid => 8 skipped
    assert len(skipped) == 8
    assert all(c[1] == "long_500k" for c in skipped)


def test_long_context_applicability():
    assert cell_is_applicable(CONFIGS["mamba2-1.3b"], SHAPES["long_500k"])[0]
    assert cell_is_applicable(CONFIGS["jamba-v0.1-52b"], SHAPES["long_500k"])[0]
    assert not cell_is_applicable(CONFIGS["qwen3-14b"], SHAPES["long_500k"])[0]


def test_get_config_aliases():
    assert get_config("mamba2_1_3b").name == "mamba2-1.3b"
    with pytest.raises(KeyError):
        get_config("nonexistent-model")


def test_reduced_configs_are_tiny():
    for cfg in CONFIGS.values():
        r = cfg.reduced()
        total, _ = r.param_counts()
        assert total < 5e6, f"{r.name} too big: {total}"
        assert r.family == cfg.family

"""Per-cell best-config selection (distributed/autotune.py) and the
roofline-guided kernel autotuner (kernels/autotune.py)."""
import json

import pytest

from repro.configs.registry import CONFIGS
from repro.distributed.autotune import best_batch_size, best_hints
from repro.kernels import autotune


def test_moe_train_uses_shardmap():
    h, remat = best_hints(CONFIGS["kimi-k2-1t-a32b"], "train")
    assert h["moe_impl"] == "shardmap"
    assert remat == "dots"


def test_moe_decode_stays_scatter_with_int8():
    h, _ = best_hints(CONFIGS["kimi-k2-1t-a32b"], "decode")
    assert "moe_impl" not in h           # shardmap regressed 70x on decode
    assert h["kv_cache_dtype"] == "int8"


def test_qwen3_never_repeat_kv():
    # 40 heads % 16 != 0: repeat_kv only multiplies KV bytes (measured -13%)
    for kind in ("train", "prefill"):
        h, _ = best_hints(CONFIGS["qwen3-14b"], kind)
        assert h.get("attn_impl") != "repeat_kv"


def test_chameleon_train_gets_dots_and_repeat_kv():
    h, remat = best_hints(CONFIGS["chameleon-34b"], "train")
    assert remat == "dots"
    assert h.get("attn_impl") == "repeat_kv"   # 64 heads divisible by 16


def test_encdec_keeps_baseline():
    h, remat = best_hints(CONFIGS["seamless-m4t-large-v2"], "train")
    assert remat == "full" and "attn_logits_bf16" not in h


def test_ssm_decode_no_kv_quant():
    h, _ = best_hints(CONFIGS["mamba2-1.3b"], "long_decode")
    assert "kv_cache_dtype" not in h     # no KV cache to quantize


def test_hints_are_known_keys():
    from repro.distributed import hints as H
    for arch in CONFIGS.values():
        for kind in ("train", "prefill", "decode", "long_decode"):
            h, remat = best_hints(arch, kind)
            for k, v in h.items():
                H.set_hint(k, v)  # raises on unknown keys
            H.reset()
            assert remat in ("full", "dots", "none")


# ---------------------------------------------------------------- kernels
# roofline-guided block autotuner (kernels/autotune.py)

@pytest.fixture()
def _tuner_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.reset()
    yield tmp_path / "at.json"
    autotune.reset()


def test_best_config_valid_and_persisted(_tuner_cache):
    blocks = autotune.best_config(
        "decode_attention", {"b": 4, "kv": 4, "g": 2, "s": 2048, "d": 64})
    assert blocks["s_block"] >= 64
    doc = json.loads(_tuner_cache.read_text())
    assert doc["version"] == autotune.SCHEMA_VERSION
    (key, entry), = doc["configs"].items()
    assert key.startswith("decode_attention|")
    assert entry["blocks"] == blocks
    assert entry["source"] == "roofline"


def test_best_config_prefers_measurement(_tuner_cache):
    """With a measure callable, the measured winner beats the roofline pick
    and is persisted as source=measured."""
    shape = {"m": 4, "q": 64, "h": 16, "p": 32, "n": 64}
    cands = autotune.candidates("ssd_chunk_scan", shape)
    worst = min(c["head_block"] for c in cands)  # roofline prefers big hb

    def measure(blocks):  # pretend the smallest block is fastest on-device
        return float(blocks["head_block"])

    blocks = autotune.best_config("ssd_chunk_scan", shape, measure=measure,
                                  top_k=len(cands))
    assert blocks["head_block"] == worst
    doc = json.loads(_tuner_cache.read_text())
    (entry,) = doc["configs"].values()
    assert entry["source"] == "measured"


def test_best_config_cache_hit_skips_sweep(_tuner_cache):
    shape = {"b": 1, "kv": 2, "g": 2, "s": 512, "d": 64}
    first = autotune.best_config("decode_attention", shape)
    calls = []
    second = autotune.best_config("decode_attention", shape,
                                  measure=lambda b: calls.append(b) or 1.0)
    assert second == first and not calls  # hit: measure never invoked


def test_candidates_respect_vmem_budget():
    for kernel, shape in [
        ("decode_attention", {"b": 1, "kv": 8, "g": 4, "s": 1 << 16, "d": 128}),
        ("flash_attention", {"b": 1, "h": 8, "kv": 4, "sq": 1 << 14,
                             "skv": 1 << 14, "d": 128, "causal": True}),
        ("ssd_chunk_scan", {"m": 4, "q": 256, "h": 64, "p": 64, "n": 128}),
    ]:
        bucket_fn, _, vmem_fn, _ = autotune._KERNELS[kernel]
        for cand in autotune.candidates(kernel, shape):
            assert vmem_fn(bucket_fn(shape), cand) <= autotune.VMEM_BUDGET_BYTES


def test_roofline_estimate_monotone_in_shape():
    small = autotune.roofline_estimate(
        "decode_attention", {"b": 1, "kv": 4, "g": 2, "s": 1024, "d": 64},
        {"s_block": 256})
    big = autotune.roofline_estimate(
        "decode_attention", {"b": 1, "kv": 4, "g": 2, "s": 8192, "d": 64},
        {"s_block": 256})
    assert big > small > 0


def test_roofline_batch_size_sane():
    """Folded batch-size selection: small dense models saturate at a real
    batch; a 1T-param model can't amortize on one 16GB chip."""
    assert best_batch_size(CONFIGS["tinyllama-1.1b"]) >= 8
    assert best_batch_size(CONFIGS["kimi-k2-1t-a32b"]) == 1
    assert best_batch_size(CONFIGS["mamba2-1.3b"]) >= 8


def test_best_config_reads_and_writes_no_file_by_default(tmp_path,
                                                        monkeypatch):
    """Without REPRO_AUTOTUNE_CACHE the tuner keeps winners in the process:
    nothing outside the checkout can change which blocks a kernel uses."""
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    autotune.reset()
    try:
        assert autotune.cache_path() is None
        blocks = autotune.best_config(
            "decode_attention", {"b": 2, "kv": 2, "g": 2, "s": 1024, "d": 64})
        assert blocks["s_block"] >= 64
        assert list(tmp_path.rglob("*")) == []
    finally:
        autotune.reset()


def test_target_chip_off_tpu_is_the_v5e():
    from repro.roofline.hw import TPU_V5E
    assert autotune.target_chip() is TPU_V5E


def test_chip_for_device_kind():
    from repro.roofline.hw import TPU_V5E, chip_for_device_kind
    assert chip_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="TPU v9"):
        chip_for_device_kind("TPU v9")


def test_ssd_head_blocks_tile_the_second_minor_dim():
    """Head blocks are multiples of 8 (or every head when there are
    fewer), the only blocks the TPU lowering takes for dt/cum."""
    for h in (4, 8, 24, 64, 80):
        for c in autotune.candidates("ssd_chunk_scan",
                                     {"m": 4, "q": 64, "h": h, "p": 32,
                                      "n": 64}):
            hb = c["head_block"]
            assert h % hb == 0 and (hb % 8 == 0 or hb == h)

"""The latent-attention expert block (``blocks/mla_moe.py``) at a tiny size
on the CPU, joined to a tiny checkout by new files alone: a configuration,
the block, and the readers that the benchmark already has. The harness
judges it ``correct`` against the block's float32 reference, the float8
control comes out not correct, ``work()`` matches a count by hand, and the
per-layer readings of the block read something."""
import json

import pytest

import chipbench_tiny as tiny
from chipbench import harness, spec, trace

import control

CELL = "tiny-mla.mix"
#: the tiny cell's limit, between its readings: the program reads at most
#: 0.0017 over seeds 11-15 (bf16 rounding), the float8 control 0.0061 to
#: 0.0179 on seeds 11-13
LIMIT = 0.004
MLA = {"name": "tiny-mla", "family": "moe", "block": "mla_moe",
       "num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
       "d_ff": 128, "vocab_size": 250, "vocab_pad_multiple": 16,
       "rope_theta": 10000.0, "norm_eps": 1e-05, "tie_embeddings": False,
       "num_experts": 8, "num_experts_per_token": 2, "moe_d_ff": 32,
       "num_shared_experts": 1, "scoring_func": "sigmoid",
       "routed_scaling_factor": 2.446, "experts_held": 4,
       "first_k_dense_replace": 1,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "source": "test", "reduced": ["experts_held"],
       "check": {"max_logit_gap": LIMIT}}
KERNELS = ("paged_mla_prefill_attention", "paged_mla_decode_attention")
PER_LAYER = ("paged_mla_prefill_attention_roofline",
             "paged_mla_decode_attention_roofline", "expert_held_share",
             "expert_load_peak", "step_mfu")


@pytest.fixture
def cpu(monkeypatch):
    monkeypatch.setattr(harness, "check_device", tiny.cpu_device)


def _with_mla_kernels(extract):
    """A CPU trace has no device plane: this one stands in for it, each
    engine prefill or decode phase a step program that runs the block's
    kernel."""
    def fake(profile):
        plain = extract(profile)
        for span, kernel in (("engine.prefill", KERNELS[0]),
                             ("engine.decode", KERNELS[1])):
            for n, s, e in list(plain["host"]):
                if n == span:
                    plain["modules"].append([f"jit({span})", s, e])
                    plain["ops"].append([f"{kernel}.1", s, e])
        return plain
    return fake


def _checkout(tmp_path):
    root = tiny.checkout(tmp_path)
    (root / "bench" / "configs" / "tiny-mla.json").write_text(
        json.dumps(MLA))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny-mla", "source": "test",
                           "file": "bench/configs/tiny-mla.json",
                           "reduced": ["experts_held"], "why": "test"})
    doc["workloads"].append({"name": CELL, "config": "tiny-mla",
                             "traffic": "mix", "chips": 1, "why": "test"})
    for name in PER_LAYER:
        doc["per_layer"].append({"name": name, "unit": "%",
                                 "better": "higher", "source": "device_trace",
                                 "layer": "kernels", "moves": "tokens_per_s",
                                 "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def test_the_block_is_judged_and_its_layers_read(cpu, tmp_path,
                                                 monkeypatch):
    monkeypatch.setattr(trace, "extract", _with_mla_kernels(trace.extract))
    root = _checkout(tmp_path)
    res = tiny.run(root, CELL, trace=True)
    c = res["compared"]
    assert res["correct"] is True, c
    assert c["no_sample"]["value"] == 0 and c["fed_faults"]["value"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(PER_LAYER) <= set(m), m
    assert m["paged_mla_prefill_attention_roofline"] > 0
    assert m["paged_mla_decode_attention_roofline"] > 0
    # 4 of 8 experts held: about half the picks land here
    assert 20 < m["expert_held_share"] < 80
    assert m["expert_load_peak"] >= 1.0
    ops = dict(res["breakdown"]["device_ops"])
    assert ops[f"decode:{KERNELS[1]}"] > 0
    assert ops[f"prefill:{KERNELS[0]}"] > 0


def test_a_tree_without_latent_attention_is_refused(tmp_path):
    root = _checkout(tmp_path)
    cell = spec.load_cell(CELL, root, root / "bench")
    dense = dict(MLA, kv_lora_rank=0)
    import jax.numpy as jnp
    from chipbench import weights
    from repro.models.factory import build_model
    abstract = build_model(harness.model_config(dense)).abstract_params(
        jnp.bfloat16)
    with pytest.raises(ValueError, match="latent-attention expert block"):
        cell.block.check_tree(weights.leaf_specs(abstract))


def test_the_control_reads_wider_than_the_program(cpu, tmp_path):
    root = _checkout(tmp_path)
    rows = list(control.readings(CELL, [11, 12, 13], 1.5, 3,
                                 checkout=root, bench_dir=root / "bench"))
    for r in rows:
        assert r["program"]["correct"] is True, r
        assert r["program"]["compared"]["no_sample"]["value"] == 0
    for r in rows:
        assert r["control"]["correct"] is False, r


@pytest.mark.parametrize("rows,lengths", [
    ([(0, 5)], []),
    ([], [7]),
    ([(3, 4), (0, 2)], [9, 1]),
])
def test_work_matches_a_count_by_hand(rows, lengths):
    blk = spec.load_block("mla_moe")
    step, kernels = blk.work(MLA, rows, lengths)
    d, h, nope, rope, r, v = 64, 4, 16, 8, 32, 16
    w = r + rope
    attn = d * h * (nope + rope) + d * w + h * nope * r + h * r * v + h * v * d
    routed = d * 8 + 1 * 3 * d * 32 + 3 * d * 32 * 2 * 4 / 8
    per_token = 3 * attn + 1 * 3 * d * 128 + 2 * routed
    keys_p = sum(c * s + c * (c + 1) // 2 for s, c in rows)
    keys_d = sum(lengths)
    tokens = sum(c for _, c in rows) + len(lengths)
    attn_fl = 2 * h * (w + r) * 3
    want = (2 * per_token * tokens + attn_fl * (keys_p + keys_d)
            + 2 * d * 250 * len(lengths))
    assert step == pytest.approx(want)
    pf, pb = kernels["paged_mla_prefill_attention"]
    df, db = kernels["paged_mla_decode_attention"]
    assert pf == pytest.approx(attn_fl * keys_p)
    assert df == pytest.approx(attn_fl * keys_d)
    queries_p = sum(c for _, c in rows)
    read_p = sum(s + c for s, c in rows)
    assert pb == pytest.approx(3 * (2 * w * read_p
                                    + 4 * h * (w + r) * queries_p))
    assert db == pytest.approx(3 * (2 * w * keys_d
                                    + 4 * h * (w + r) * len(lengths)))

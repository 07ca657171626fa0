"""The harness end to end on the CPU at tiny sizes: a clean run is correct
and its served tokens agree with the float32 reference over what the engine
dispatched; the warm-up covers every prefill width the window dispatches; a
cell, a metric and a block added as files only are found; a configuration
whose block is missing, unknown or not its tree is refused; no chip, no
result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import chipbench_tiny as tiny

from chipbench import (correct, harness, spec, trace, traffic,  # noqa: E402
                       weights)


@pytest.fixture
def cpu(monkeypatch):
    monkeypatch.setattr(harness, "check_device", tiny.cpu_device)


@pytest.mark.parametrize("cfg", sorted(tiny.CONFIGS))
def test_clean_run_matches_the_reference(cfg, cpu, tmp_path, capsys):
    res = tiny.run(tiny.checkout(tmp_path), f"{cfg}.mix")
    c = res["compared"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True, c
    assert c["fed_faults"]["value"] == 0 and c["no_sample"]["value"] == 0
    assert c["failed_requests"]["value"] == 0
    assert 0 <= c["max_logit_gap"]["value"] <= tiny.TINY_GAP
    assert res["attempted"] >= 9 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"setup_s", "itl_p99_s", "tokens_per_s"}
    assert m["tokens_per_s"]["unit"] == "tokens/s"
    assert all(v["value"] > 0 for v in m.values())
    err = capsys.readouterr().err
    assert "0 programs lowered inside the window" in err
    assert f"{res['attempted']} requests due, " in err
    assert " unfinished at the close, 0 failed" in err


def test_a_request_the_engine_drops_is_failed(cpu, tmp_path, monkeypatch):
    from repro.serving.engine import InferenceEngine
    submit = InferenceEngine.submit

    def drop_third(self, req):
        if req.request_id != 3:
            submit(self, req)

    monkeypatch.setattr(InferenceEngine, "submit", drop_third)
    res = tiny.run(tiny.checkout(tmp_path), "tiny-mha.mix")
    assert res["failed"] == 1
    assert res["compared"]["failed_requests"] == {"value": 1, "limit": 0}
    assert res["correct"] is False


def test_warm_up_covers_every_dispatched_width(cpu, tmp_path):
    root = tiny.checkout(tmp_path, streams=(tiny.CHAT,))
    cell = spec.load_cell("tiny-gqa.mix", root, root / "bench")
    _, _, engine = harness.build_engine(cell, 3)
    streams = traffic.generate(cell.traffic, 1.5, 3, 250)
    rec = correct.Dispatches(engine)
    warmed = harness.warm_up(engine, streams)
    mark = rec.mark()
    harness.drive(engine, streams, 1.5, False)
    dispatched = {np.shape(c.tokens)[1] for c in rec.calls[mark:]
                  if c.kind == "prefill"}
    assert warmed == len(dispatched)
    assert sorted(dispatched) == traffic.prefill_widths(
        streams, engine.prefill_chunk)


def test_cell_and_metric_added_as_files_are_found(cpu, tmp_path):
    root = tiny.checkout(tmp_path)
    bench = root / "bench"
    (bench / "traffic" / "solo.json").write_text(json.dumps(
        {"engine": tiny.ENGINE, "streams": [tiny.AGENT]}))
    (bench / "metrics" / "calls_done.py").write_text(
        "def read(ctx):\n"
        "    return sum(x.done for x in ctx.window.timed.values())\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "tiny-mha.solo", "config": "tiny-mha",
                             "traffic": "solo", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "calls_done", "unit": "count",
                             "better": "higher", "source": "host_clock",
                             "layer": "scheduler", "moves": "setup_s",
                             "workloads": ["tiny-mha.solo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    res = tiny.run(root, "tiny-mha.solo", trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["calls_done"]["value"] >= 1
    assert "prefill_programs" in res["metrics"]
    assert "decode_rows_mean" not in res["metrics"]   # listed for another
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("metric, reader", [
    ("decode_rows_mean", "decode_rows_mean"),
    ("decode_rows_mean.research", "decode_rows_mean"),
    ("tokens_per_s.chat", "tokens_per_s"),
])
def test_a_metric_split_by_cell_kind_is_read_by_its_reader(metric, reader):
    assert spec.load_reader(metric).__module__ == spec.load_reader(
        reader).__module__ == f"chipbench_metric_{reader}"


def test_a_metric_with_no_reader_is_refused():
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.load_reader("nope.chat")


def test_metrics_follow_workloads_and_moves():
    doc = {"end_to_end": [tiny.e2e("a", "s", "lower"),
                          tiny.e2e("b", "s", "lower", ["x"])],
           "per_layer": [{"name": "p", "moves": "b", "workloads": ["x"]},
                         {"name": "q", "moves": "a", "workloads": ["x", "y"]}]}
    e2e, per = spec.metrics_for(doc, "y")
    assert [m["name"] for m in e2e] == ["a"] and [m["name"] for m in per] == [
        "q"]
    e2e, per = spec.metrics_for(doc, "x")
    assert [m["name"] for m in per] == ["p", "q"]
    doc["per_layer"].append({"name": "r", "moves": "a"})
    with pytest.raises(spec.SpecError, match="'r' lists no workloads"):
        spec.metrics_for(doc, "x")


@pytest.mark.parametrize("event, counts", [
    (harness.CompileCounter.HIT, (0, 1, 0)),
    (harness.CompileCounter.MISS, (0, 0, 1)),
    ("/jax/compilation_cache/compile_requests_use_cache", (0, 0, 0)),
])
def test_compile_counter_tells_cache_reads_from_compiles(event, counts):
    import jax
    c = harness.CompileCounter()
    jax.monitoring.record_event(event)
    assert (c.n, c.hits, c.misses) == counts


def test_unknown_cell_is_refused(tmp_path):
    root = tiny.checkout(tmp_path)
    with pytest.raises(spec.SpecError, match="no cell"):
        spec.load_cell("nope", root, root / "bench")


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "stablelm-3b.chat", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_cpu_is_refused():
    with pytest.raises(harness.NoChip, match="not a TPU"):
        harness.check_device(1)
    p = _run_py(tiny.ROOT)
    assert p.returncode == 2 and p.stdout == "", p.stderr
    assert "not a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def _leaf(params, name):
    for k in name.split("/"):
        params = params[k]
    return np.asarray(params, np.float32)


def test_weights_drawn_per_layer_equal_the_stacked_tree():
    import jax.numpy as jnp
    from repro.models.factory import build_model
    dense = spec.load_block("dense")
    model = build_model(harness.model_config(tiny.config("tiny-gqa")))
    abstract = model.abstract_params(jnp.bfloat16)
    params = weights.make_params(abstract, 2**31 + 9, 250, dense.STACKED)
    specs = weights.leaf_specs(abstract)
    dense.check_tree(specs)
    key = weights.base_key(2**31 + 9)
    for name in ("layers/attn/wk", "layers/ln2", "layers/ffn/w_down"):
        shape, dtype = specs[name]
        got = _leaf(params, name)
        for layer in range(shape[0]):
            np.testing.assert_array_equal(got[layer], np.asarray(
                weights.draw_leaf(key, name, shape, dtype, 250, layer)))
    head = np.asarray(params["lm_head"], np.float32)
    assert not head[:, 250:].any() and head[:, :250].any()
    assert 0.02 < head[:, :250].std() < 0.025
    ln = np.asarray(params["ln_f"])
    assert abs(ln.mean() - 1) < 0.05 and 0.09 < ln.std() < 0.13


def test_a_group_stacked_under_another_name_is_drawn_per_layer():
    """The reduced hybrid stacks its periods under ``blocks/sub<i>/``: a
    block that lists ``blocks/`` as stacked has them drawn one layer at a
    time, as its reference draws them; left unlisted they are drawn whole,
    and the values differ."""
    import jax.numpy as jnp
    from repro.configs.jamba_v0_1_52b import CONFIG
    from repro.models.factory import build_model
    cfg = CONFIG.reduced()
    abstract = build_model(cfg).abstract_params(jnp.bfloat16)
    seed, vocab = 2**31 + 21, cfg.vocab_size
    params = weights.make_params(abstract, seed, vocab, ("blocks/",))
    whole = weights.make_params(abstract, seed, vocab, ("layers/",))
    specs = weights.leaf_specs(abstract)
    key = weights.base_key(seed)
    names = ("blocks/sub0/ssm/w_x", "blocks/sub0/ln1", "blocks/sub1/attn/wk",
             "blocks/sub1/moe/w_up")
    for name in names:
        shape, dtype = specs[name]
        got = _leaf(params, name)
        for layer in range(shape[0]):
            np.testing.assert_array_equal(got[layer], np.asarray(
                weights.draw_leaf(key, name, shape, dtype, vocab, layer)))
        assert not np.array_equal(got, _leaf(whole, name))
    np.testing.assert_array_equal(_leaf(params, "lm_head"),
                                  _leaf(whole, "lm_head"))


def _set_block(root, config, block):
    path = root / "bench" / "configs" / f"{config}.json"
    doc = json.loads(path.read_text())
    if block is None:
        del doc["block"]
    else:
        doc["block"] = block
    path.write_text(json.dumps(doc))
    return path


@pytest.mark.parametrize("block", [None, "nope", "../blocks/dense"])
def test_a_configuration_without_a_known_block_is_refused(block, tmp_path):
    root = tiny.checkout(tmp_path)
    path = _set_block(root, "tiny-mha", block)
    with pytest.raises(spec.SpecError) as e:
        spec.load_cell("tiny-mha.mix", root, root / "bench")
    named = {None: path, "nope": root / "bench" / "blocks" / "nope.py"}
    if block in named:
        assert str(named[block]) in str(e.value)
    else:
        assert "not a plain name" in str(e.value)


@pytest.mark.parametrize("change", [{"tie_embeddings": True},
                                    {"use_qk_norm": True}])
def test_a_tree_that_is_not_the_block_is_refused(change, cpu, tmp_path):
    root = tiny.checkout(tmp_path)
    path = root / "bench" / "configs" / "tiny-mha.json"
    doc = json.loads(path.read_text())
    doc.update(change)
    path.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="not the dense decoder"):
        tiny.run(root, "tiny-mha.mix")


#: a second block, as files only: the dense block's tree and reference under
#: a kernel table and a count of work of its own
TWIN = '''"""The dense layer under kernels of its own."""
import pathlib

from chipbench import spec

_dense = spec.load_block("dense", pathlib.Path(__file__).resolve().parents[1])
STACKED = _dense.STACKED
check_tree = _dense.check_tree
logits_at = _dense.logits_at
KERNELS = {"twin_prefill": "prefill", "twin_decode": "decode"}


def work(cfg, prefill_rows, decode_lengths):
    step, kernels = _dense.work(cfg, prefill_rows, decode_lengths)
    return step, {"twin_prefill": kernels["paged_prefill_attention"],
                  "twin_decode": kernels["paged_decode_attention"]}
'''


def _with_twin_kernels(extract):
    """A CPU trace has no device plane: this one stands in for it, each
    engine prefill or decode phase a step program that runs the twin's
    kernel."""
    def fake(profile):
        plain = extract(profile)
        for span, kernel in (("engine.prefill", "twin_prefill"),
                             ("engine.decode", "twin_decode")):
            for n, s, e in list(plain["host"]):
                if n == span:
                    plain["modules"].append([f"jit({span})", s, e])
                    plain["ops"].append([f"{kernel}.1", s, e])
        return plain
    return fake


def test_a_block_added_as_files_is_judged_and_counted(cpu, tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(trace, "extract", _with_twin_kernels(trace.extract))
    root = tiny.checkout(tmp_path)
    bench = root / "bench"
    (bench / "blocks" / "twin.py").write_text(TWIN)
    cfg = dict(tiny.config("tiny-mha"), name="tiny-twin", block="twin")
    (bench / "configs" / "tiny-twin.json").write_text(json.dumps(cfg))
    for kernel in ("twin_prefill", "twin_decode"):
        (bench / "metrics" / f"{kernel}_roofline.py").write_text(
            "from chipbench import derived\n\n\n"
            "def read(ctx):\n"
            f"    return derived.roofline_pct(ctx, {kernel!r})\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny-twin", "source": "test",
                           "file": "bench/configs/tiny-twin.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "tiny-twin.mix", "config": "tiny-twin",
                             "traffic": "mix", "chips": 1, "why": "test"})
    for name in ("step_mfu", "twin_prefill_roofline", "twin_decode_roofline",
                 "paged_decode_attention_roofline"):
        doc["per_layer"].append({"name": name, "unit": "%",
                                 "better": "higher", "source": "device_trace",
                                 "layer": "kernels", "moves": "tokens_per_s",
                                 "workloads": ["tiny-twin.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    res = tiny.run(root, "tiny-twin.mix", trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["compared"]["no_sample"]["value"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["step_mfu"] > 0
    # only the twin's work() counts the twin's kernels
    assert m["twin_prefill_roofline"] > 0 and m["twin_decode_roofline"] > 0
    assert "paged_decode_attention_roofline" not in m
    ops = dict(res["breakdown"]["device_ops"])
    assert ops["decode:twin_decode"] > 0 and ops["prefill:twin_prefill"] > 0

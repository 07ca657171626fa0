"""The engine's phase spans and counters as the benchmark reads them: idle
time charged to the engine phase it falls in, the phases on the harness's
thread line inside ``bench.step``, and the readers of the engine's
counters."""
import json
import pathlib
import tempfile
from types import SimpleNamespace

import pytest

import chipbench_tiny as tiny

from chipbench import harness, spec, trace, traffic  # noqa: E402

US = 1000

#: two harness steps, each around one engine step and its phases; idle
#: stretches [0, 6] (mid 3, in engine.admit), [31, 44] (mid 37.5, in
#: engine.retire) and [87, 100] (mid 93.5, after every span)
STEPS = {
    "host": [["bench.window", 0, 100],
             ["bench.step", 0, 50], ["engine.step", 1, 48],
             ["engine.admit", 1, 4], ["engine.decode", 4, 10],
             ["engine.sample", 10, 30], ["engine.retire", 30, 46],
             ["bench.step", 52, 90], ["engine.step", 53, 89],
             ["engine.admit", 53, 54], ["engine.decode", 54, 60],
             ["engine.sample", 60, 88], ["engine.retire", 88, 89]],
    "modules": [["jit(decode_step_paged)", 6, 31],
                ["jit(decode_step_paged)", 44, 87]],
    "ops": [["paged_decode_attention.1", 6, 31],
            ["paged_decode_attention.1", 44, 87]],
}

SPANS = {"engine.step", "engine.admit", "engine.prefill", "engine.decode",
         "engine.sample", "engine.retire"}


def test_idle_inside_an_engine_phase_is_charged_to_the_phase():
    plain = {k: [[n, s * US, e * US] for n, s, e in v]
             for k, v in STEPS.items()}
    r = trace.reduce(plain, spec.load_block("dense").KERNELS)
    assert r.idle_pending_s == pytest.approx(32e-6)
    gaps = dict(r.breakdown["idle_gaps"])
    assert gaps == pytest.approx({"engine.retire": 13e-6,
                                  "engine.admit": 6e-6,
                                  "(no span)": 13e-6})
    assert "bench.step" not in gaps
    assert r.program_s == pytest.approx({"decode": 68e-6})


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def test_engine_spans_are_on_the_harness_line_inside_bench_step(tmp_path):
    root = tiny.checkout(tmp_path)
    cell = spec.load_cell("tiny-gqa.mix", root, root / "bench")
    _, _, engine = harness.build_engine(cell, 3)
    streams = traffic.generate(cell.traffic, 1.0, 3, 250)
    harness.warm_up(engine, streams)
    rec = trace.Recording(tempfile.mkdtemp(dir=tmp_path))
    rec.start()
    try:
        harness.drive(engine, streams, 1.0, True)
    finally:
        rec.stop()
    host = rec.load()["host"]
    names = {n for n, _, _ in host}
    assert SPANS <= names
    steps = [h for h in host if h[0] == "bench.step"]
    engine_steps = [h for h in host if h[0] == "engine.step"]
    assert len(engine_steps) == len(steps) > 0
    assert all(any(_inside(e, s) for s in steps) for e in engine_steps)
    phases = [h for h in host if h[0] in SPANS - {"engine.step"}]
    assert all(any(_inside(p, e) for e in engine_steps) for p in phases)


def test_a_traced_run_reports_the_engine_counters(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "check_device", tiny.cpu_device)
    root = tiny.checkout(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    for name, unit in (("host_gap_ms", "ms"), ("prefill_row_use", "%"),
                       ("kv_pool_use", "%")):
        doc["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": "program_counter", "layer": "engine",
            "moves": "tokens_per_s", "workloads": ["tiny-mha.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    res = tiny.run(root, "tiny-mha.mix", trace=True)
    assert res["correct"] is True, res["compared"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # chunked prefill dispatches one live row of the engine's four
    assert m["prefill_row_use"] == 100.0 / tiny.ENGINE["slots"]
    assert 0 < m["kv_pool_use"] <= 100
    assert m["host_gap_ms"] > 0


READERS = {
    "host_gap_ms": ({"host_gap_s": 0.3, "host_gaps": 200}, 1.5,
                    {"host_gap_s": 0.0, "host_gaps": 0}),
    "prefill_row_use": ({"prefill_tokens": 512, "prefill_row_tokens": 4096},
                        12.5, {"prefill_tokens": 0, "prefill_row_tokens": 0}),
    "kv_pool_use": ({"kv_live_tokens": 3072, "kv_pool_tokens": 12288}, 25.0,
                    {"kv_live_tokens": 0, "kv_pool_tokens": 0}),
}


@pytest.mark.parametrize("reader", sorted(READERS))
@pytest.mark.parametrize("kind", ["chat", "research"])
def test_counter_readers(reader, kind):
    read = spec.load_reader(f"{reader}.{kind}")
    stats, want, empty = READERS[reader]
    assert read(SimpleNamespace(stats=stats)) == pytest.approx(want)
    assert read(SimpleNamespace(stats=empty)) is None
    # a program that keeps no such counter: nothing to read
    assert read(SimpleNamespace(stats={"decode_syncs": 9})) is None


def test_the_benchmark_lists_each_counter_metric_per_cell():
    doc = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in doc["per_layer"]}
    cells = {"chat": "stablelm-3b.chat",
             "research": "stablelm-12b-l10.research"}
    for reader in READERS:
        for kind, cell in cells.items():
            m = per[f"{reader}.{kind}"]
            assert m["workloads"] == [cell]
            assert m["moves"] == f"tokens_per_s.{kind}"
            assert pathlib.Path(spec.load_reader(m["name"]).__code__
                                .co_filename).stem == reader

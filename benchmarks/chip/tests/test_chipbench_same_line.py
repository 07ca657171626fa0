"""A configuration's block is its own data, and the dense block reads what
the harness read when it knew no other: the tiny checkout's whole result
line at a fixed seed is the one that harness printed, recorded in
``data/tiny_closed_loop_lines.json``, but for its timings. The traffic is
one closed-loop client, so what is served does not hang on the clock."""
import json
import pathlib

import pytest

import chipbench_tiny as tiny

from chipbench import harness  # noqa: E402

RECORDED = pathlib.Path(__file__).parent / "data" / \
    "tiny_closed_loop_lines.json"
SEED = 2**31 + 77
#: what the host clock sets: the traced window's length and its idle gaps
TIMINGS = (("device", "window_s"), ("breakdown", "idle_gaps"))
#: engine counters, on top of the tiny checkout's own per-layer metrics
COUNTERS = ("prefill_row_use", "kv_pool_use")


def _untimed(line: dict) -> dict:
    line = json.loads(json.dumps(line))
    for group, key in TIMINGS:
        del line[group][key]
    return line


@pytest.mark.parametrize("cfg", sorted(tiny.CONFIGS))
def test_the_result_line_is_the_dense_only_harness_line(cfg, monkeypatch,
                                                         tmp_path):
    monkeypatch.setattr(harness, "check_device", tiny.cpu_device)
    root = tiny.checkout(tmp_path, streams=(tiny.AGENT,))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    for name in COUNTERS:
        doc["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "engine",
            "moves": "tokens_per_s",
            "workloads": [f"{c}.mix" for c in tiny.CONFIGS]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    res = tiny.run(root, f"{cfg}.mix", seed=SEED, seconds=3.0, trace=True)
    want = json.loads(RECORDED.read_text())[cfg]
    assert list(res) == list(want)
    assert list(res["metrics"]) == list(want["metrics"])
    assert _untimed(res) == _untimed(want)

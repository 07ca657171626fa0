"""The control at a size a test run holds: over three seeds the reference
with its projections in float8 reads a served-token gap at least three times
the program's widest, while the program stays inside the limit, and the
harness's own comparison, with the control in the program's place, comes
out not correct. (At this size a seed's sample can read 0 under the
control; the readings that set a cell's limit come from the chip, at the
cell's own size.)"""
import json
import sys

import chipbench_tiny as tiny

sys.path.insert(0, str(tiny.BENCH))
import control  # noqa: E402
from chipbench import harness  # noqa: E402

#: the tiny GQA cell's limit for this test (see below)
CONTROL_LIMIT = 0.005


def _gap(reading):
    return reading["compared"]["max_logit_gap"]["value"]


def test_control_reads_wider_than_the_program(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "check_device", tiny.cpu_device)
    root = tiny.checkout(tmp_path)
    # the limit set as a cell's is, between the readings at this size: the
    # program reads at most 0.0007 on these seeds, the control up to 0.018
    cfg = root / "bench" / "configs" / "tiny-gqa.json"
    doc = json.loads(cfg.read_text())
    doc["check"]["max_logit_gap"] = CONTROL_LIMIT
    cfg.write_text(json.dumps(doc))
    rows = list(control.readings("tiny-gqa.mix", [11, 12, 13], 1.5, 3,
                                 checkout=root, bench_dir=root / "bench"))
    assert len(rows) == 3
    for r in rows:
        assert r["program"]["correct"] is True, r
        assert r["program"]["compared"]["no_sample"]["value"] == 0
    assert max(_gap(r["control"]) for r in rows) > 3 * max(
        _gap(r["program"]) for r in rows)
    # the harness's own comparison, with the control in the program's place
    assert any(r["control"]["correct"] is False for r in rows)


def test_a_window_with_nothing_finished_is_not_correct(monkeypatch,
                                                       tmp_path):
    monkeypatch.setattr(harness, "check_device", tiny.cpu_device)
    root = tiny.checkout(tmp_path)
    row, = control.readings("tiny-mha.mix", [5], 0.0, 1, checkout=root,
                            bench_dir=root / "bench")
    for name in ("program", "control"):
        assert row[name]["correct"] is False
        assert row[name]["compared"]["no_sample"]["value"] == 1


def test_seed_ranges():
    assert control.seeds("3-5,9") == [3, 4, 5, 9]

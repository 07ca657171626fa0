"""The reduction from a trace to device metrics, with the dense block's
kernel table, on a hand-made trace whose answers are counted by hand and on
a recorded one."""
import sys
import pathlib

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import spec, trace  # noqa: E402

KERNELS = spec.load_block("dense").KERNELS

US = 1000  # the hand-made trace counts in microseconds

HAND = {
    "host": [["bench.window", 0, 100], ["bench.step", 0, 30],
             ["bench.wait", 10, 20], ["bench.step", 32, 52],
             ["bench.submit", 55, 58], ["bench.step", 58, 64],
             ["bench.wait", 70, 100]],
    "modules": [["jit(<lambda>)", 4, 26], ["jit(<lambda>)", 40, 50],
                ["jit(<lambda>)", 60, 62], ["jit(<lambda>)", 90, 110]],
    "ops": [["fusion.1", 4, 5], ["paged_prefill_attention.3", 5, 8],
            ["fusion.2", 8, 25], ["paged_decode_attention.9", 41, 44],
            ["fusion.2", 44, 49], ["convert.7", 60, 62],
            ["paged_decode_attention.9", 90, 110]],
}


def _scaled():
    return {k: [[n, s * US, e * US] for n, s, e in v]
            for k, v in HAND.items()}


def test_hand_counted_trace():
    r = trace.reduce(_scaled(), KERNELS)
    assert r.window_s == pytest.approx(100e-6)
    # busy: [4, 25] + [41, 49] + [60, 62] + [90, 100] (clipped at the close)
    assert r.busy_s == pytest.approx(41e-6)
    # pending: the window less the waits [10, 20] and [70, 100]
    assert r.pending_s == pytest.approx(60e-6)
    # idle while pending: [0, 4], [25, 41], [49, 60], [62, 70]
    assert r.idle_pending_s == pytest.approx(39e-6)
    assert r.program_s == pytest.approx(
        {"prefill": 22e-6, "decode": 20e-6, "other": 2e-6})
    assert r.kernel_s == pytest.approx(
        {"paged_prefill_attention": 3e-6, "paged_decode_attention": 13e-6})
    gaps = dict(r.breakdown["idle_gaps"])
    # [0,4] in the first step; [25,41] mid 33 in the second step; [49,60]
    # mid 54.5 in no span; [62,70] mid 66 after every span
    assert gaps == pytest.approx({"bench.step": 20e-6, "(no span)": 19e-6})
    ops = dict(r.breakdown["device_ops"])
    assert ops["prefill:fusion"] == pytest.approx(18e-6)
    assert ops["decode:paged_decode_attention"] == pytest.approx(13e-6)
    assert len(r.breakdown["device_ops"]) <= trace.TOP


def test_no_window_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce({"host": [], "modules": [], "ops": []}, KERNELS)


def test_saved_trace_reads_back(tmp_path):
    p = tmp_path / "t.json.gz"
    trace.save(_scaled(), p)
    assert trace.read(p) == _scaled()


def test_recorded_chip_trace():
    """A 250 ms slice of a traced `stablelm-3b.chat` window on one TPU v5e:
    decode steps only, as the chip names them (`%name.N = <hlo>` cut to
    the instruction name)."""
    plain = trace.read(pathlib.Path(__file__).parent / "data" /
                       "chat_trace_slice.json.gz")
    r = trace.reduce(plain, KERNELS)
    assert r.window_s == pytest.approx(0.25)
    assert r.busy_s == pytest.approx(0.241517742)
    assert r.idle_pending_s == pytest.approx(0.008482258)
    assert r.pending_s == pytest.approx(0.25)
    assert set(r.program_s) == {"decode", "other"}
    assert r.program_s["decode"] == pytest.approx(0.241508941)
    assert r.kernel_s == pytest.approx(
        {"paged_decode_attention": 0.049296399})
    assert r.kernel_s["paged_decode_attention"] < r.program_s["decode"]
    assert dict(r.breakdown["idle_gaps"])["np.asarray(jax.Array)"] == \
        pytest.approx(0.008482229)
    assert r.breakdown["device_ops"][2] == \
        ["decode:paged_decode_attention", pytest.approx(0.049296399)]


def test_chip_op_names_are_cut_to_the_instruction():
    assert trace._kernel_of("%paged_prefill_attention.6 = bf16[8,32] "
                            "custom-call(%a)", KERNELS) == \
        "paged_prefill_attention"
    assert trace._base("%fusion.124 = (f32[8]) fusion(%x)") == "fusion"

"""The traffic generator: the same seed gives the same requests, every seed
the same work in another order, and the prefill widths the warm-up
enumerates."""
import sys
import pathlib

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import spec, traffic  # noqa: E402

MIX = {"streams": [
    {"name": "chat", "loop": "open", "rate_per_s": 3.0,
     "prompt": {"median": 160, "sigma": 1.0, "min": 16, "max": 1024},
     "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 512}},
    {"name": "research", "loop": "closed", "clients": 2, "calls": 16,
     "prompt": {"median": 2048, "sigma": 0.5, "min": 512, "max": 3456},
     "output": {"median": 256, "sigma": 0.6, "min": 64, "max": 512}}]}
BIG_SEED = 2**31 + 977


def _flat(streams):
    return [(s.name, c.due_s, c.max_new, c.prompt.tolist())
            for s in streams for c in s.calls]


def test_same_seed_same_requests():
    a = traffic.generate(MIX, 20.0, BIG_SEED, 50304)
    b = traffic.generate(MIX, 20.0, BIG_SEED, 50304)
    assert _flat(a) == _flat(b)


def test_seeds_share_the_work_and_its_order():
    a = traffic.generate(MIX, 20.0, 1, 50304)
    b = traffic.generate(MIX, 20.0, 2, 50304)
    assert _flat(a) != _flat(b)
    for sa, sb in zip(a, b):
        assert [len(c.prompt) for c in sa.calls] == \
            [len(c.prompt) for c in sb.calls]
        assert [c.max_new for c in sa.calls] == [c.max_new for c in sb.calls]
        assert [c.due_s for c in sa.calls] == [c.due_s for c in sb.calls]
        assert any((ca.prompt != cb.prompt).any()
                   for ca, cb in zip(sa.calls, sb.calls))
    assert traffic.prefill_widths(a, 256) == traffic.prefill_widths(b, 256)


def test_open_stream_fills_the_window():
    (chat, _) = traffic.generate(MIX, 20.0, 5, 50304)
    assert len(chat.calls) == 60
    due = [c.due_s for c in chat.calls]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 22.0


def test_lengths_follow_the_file():
    spec_ = MIX["streams"][0]["prompt"]
    x = traffic.lognormal_lengths(1000, spec_)
    assert x.min() >= 16 and x.max() <= 1024
    assert np.median(x) == pytest.approx(160, rel=0.02)
    tokens = traffic.generate(MIX, 20.0, 3, 100)[0].calls[0].prompt
    assert tokens.dtype == np.int32 and tokens.max() < 100


def test_prefill_widths_are_chunks_and_remainders():
    s = traffic.Stream("x", "open", [
        traffic.Call(np.zeros(n, np.int32), 1, 0.0)
        for n in [5, 256, 300, 512, 513]])
    assert traffic.prefill_widths([s], 256) == [1, 5, 44, 256]


def test_unknown_loop_is_refused():
    with pytest.raises(ValueError, match="loop"):
        traffic.generate({"streams": [dict(MIX["streams"][0], loop="x")]},
                         5.0, 0, 10)


@pytest.mark.parametrize("name", ["chat", "research"])
def test_committed_mixes_generate(name):
    mix = spec._read_json(spec.BENCH_DIR / "traffic" / f"{name}.json")
    streams = traffic.generate(mix, 30.0, BIG_SEED, 50304)
    assert all(s.calls for s in streams)
    for s in streams:
        for c in s.calls:
            assert len(c.prompt) + 1 + c.max_new < mix["engine"]["max_seq"]

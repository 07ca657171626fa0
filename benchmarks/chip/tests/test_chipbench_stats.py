"""Percentiles and window arithmetic: a failed or unfinished request is not
done, a gap counts once its later token is returned, and the unfinished share
counts the requests still held at the close but not the failed ones."""
import sys
import pathlib

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import spec, stats  # noqa: E402
from types import SimpleNamespace  # noqa: E402


def _req(due, tokens, max_new=None, failed=False):
    r = stats.Timed("chat", due, len(tokens) if max_new is None
                    else max_new, list(tokens))
    r.failed = failed
    return r


def test_percentile_interpolates_like_numpy():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(101)), 90) == 90
    assert stats.percentile([], 90) is None


def test_gaps_count_until_the_close():
    r = _req(2.0, [2.5, 2.6, 2.8])
    assert r.gaps() == pytest.approx([0.1, 0.2])
    assert r.gaps(until_s=2.7) == pytest.approx([0.1])


@pytest.mark.parametrize("req, done", [
    (_req(0.0, [0.5, 0.6, 0.7]), True),
    (_req(0.0, [0.1, 0.2], max_new=5), False),        # unfinished
    (_req(0.0, [0.1, 0.2], failed=True), False),      # failed
    (_req(0.0, [], max_new=1), False),                # never served
])
def test_done_needs_every_token(req, done):
    assert req.done is done


@pytest.mark.parametrize("reqs, share", [
    ([_req(0.0, [0.5]), _req(0.0, [0.5], max_new=3)], 50.0),
    ([_req(0.0, [0.5]), _req(0.0, [], max_new=1, failed=True)], 0.0),
    ([_req(0.0, [0.5]), _req(0.0, [0.5]), _req(0.0, [], max_new=2),
      _req(0.0, [0.1], max_new=4)], 50.0),
    ([], None),
])
def test_unfinished_share_counts_held_requests(reqs, share):
    read = spec.load_reader("unfinished_share")
    ctx = SimpleNamespace(
        window=SimpleNamespace(timed=dict(enumerate(reqs))),
        slo={"chat": {"ttft_s": 1.0}})
    assert read(ctx) == share

"""Rebuilding what each request was fed from the recorded calls, the
sample, and the gaps that decide ``correct``."""
import sys
import pathlib
from types import SimpleNamespace

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import correct  # noqa: E402


def test_rebuild_follows_the_calls():
    rows = [("prefill", 7, 0, np.array([5, 6, 7])),
            ("decode", 7, 3, np.array([7])),      # the prompt's last again
            ("decode", 7, 4, np.array([9]))]
    fed = correct.rebuild(rows)[7]
    assert fed.tokens.tolist() == [5, 6, 7, 7, 9]
    assert fed.out_positions == [3, 4] and fed.faults == []
    req = SimpleNamespace(request_id=7, prompt=np.array([5, 6, 7]),
                          tokens_out=[9, 4])
    assert correct.check_sample([req], {7: fed}) == []


def test_rebuild_finds_holes_and_conflicts():
    rows = [("prefill", 1, 0, np.array([1, 2])),
            ("decode", 1, 3, np.array([4])),      # position 2 never fed
            ("prefill", 2, 0, np.array([1, 2])),
            ("prefill", 2, 1, np.array([3]))]     # position 1 fed twice
    fed = correct.rebuild(rows)
    assert "never fed" in fed[1].faults[0]
    assert "fed 2 and then 3" in fed[2].faults[0]


def test_check_sample_flags_what_cannot_be_compared():
    fed = correct.rebuild([("prefill", 1, 0, np.array([1, 2])),
                           ("decode", 1, 2, np.array([2]))])
    served_twice = SimpleNamespace(request_id=1, prompt=np.array([1, 2]),
                                   tokens_out=[3, 3])
    other_prompt = SimpleNamespace(request_id=1, prompt=np.array([1, 9]),
                                   tokens_out=[3])
    lost = SimpleNamespace(request_id=5, prompt=np.array([1]), tokens_out=[])
    assert "2 tokens served from 1 decode calls" in correct.check_sample(
        [served_twice], fed)[0]
    assert "not fed as sent" in correct.check_sample([other_prompt], fed)[0]
    assert "never dispatched" in correct.check_sample([lost], fed)[0]


def test_sample_holds_the_longest_and_follows_the_seed():
    reqs = [SimpleNamespace(request_id=i, prompt=np.zeros(i % 7 + 1),
                            tokens_out=[0] * (i % 5)) for i in range(20)]
    a = correct.sample(reqs, 3)
    assert len(a) == correct.SAMPLE
    longest = max(reqs, key=lambda r: len(r.prompt) + len(r.tokens_out))
    assert len(a[0].prompt) + len(a[0].tokens_out) == \
        len(longest.prompt) + len(longest.tokens_out)
    assert [r.request_id for r in a] == \
        [r.request_id for r in correct.sample(reqs, 3)]
    assert correct.sample([], 3) == []


def test_gaps_of_served_and_control_tokens():
    ref = [np.array([[0.0, 2.0, 1.5], [3.0, 1.0, 2.9]])]
    req = SimpleNamespace(tokens_out=[2, 0])
    (g,) = correct.served_gaps([req], ref)
    assert g.tolist() == [0.5, 0.0]
    low = [np.array([[0.0, 1.0, 2.0], [0.0, 5.0, 0.0]])]
    (c,) = correct.control_gaps(ref, low)
    assert c.tolist() == [0.5, 2.0]

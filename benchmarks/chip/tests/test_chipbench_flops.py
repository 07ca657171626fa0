"""FLOP and byte counts against hand counts at the cells' shapes; only live
rows at their true lengths count; the table of peaks refuses a device kind
it does not hold."""
import json
import sys
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import correct, derived, flops  # noqa: E402


def _dense(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return flops.Dense.of(json.load(f))


def test_layer_params_by_hand():
    # q, o: d*h*hd; k, v: d*kv*hd; MLP: 3*d*ff
    assert _dense("stablelm-3b").layer_matmul_params == (
        2560 * 32 * 80 * 2 + 2 * 2560 * 32 * 80 + 3 * 2560 * 6912) \
        == 79_298_560
    assert _dense("stablelm-12b-l10").layer_matmul_params == (
        5120 * 32 * 160 * 2 + 2 * 5120 * 8 * 160 + 3 * 5120 * 13824) \
        == 277_872_640


def test_decode_attention_by_hand():
    fl, by = flops.decode_attention(_dense("stablelm-3b"), [1000])
    assert fl == 4 * 32 * 80 * 1000 == 10_240_000
    assert by == 2 * (2 * 1000 * 32 * 80 + 2 * 32 * 80) == 10_250_240


def test_prefill_attention_by_hand():
    # 256 new queries after 256 cached: each attends up to its own place
    fl, by = flops.prefill_attention(_dense("stablelm-12b-l10"), [(256, 256)])
    keys = 256 * 256 + 256 * 257 // 2
    assert fl == 4 * 32 * 160 * keys == 2_015_887_360
    assert by == 2 * (2 * 512 * 8 * 160 + 2 * 256 * 32 * 160) == 7_864_320


def test_step_flops_by_hand():
    m = _dense("stablelm-3b")
    assert flops.step_flops(m, decode_lengths=[1000]) == (
        2 * 79_298_560 * 32 + 32 * 10_240_000 + 2 * 2560 * 50304)
    # prefill tokens pay no unembedding: their logits are never used
    assert flops.step_flops(m, prefill_rows=[(0, 16)]) == (
        2 * 79_298_560 * 32 * 16 + 32 * flops.prefill_attention(
            m, [(0, 16)])[0])


def test_unknown_device_kind_raises():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v99")


class _Engine:
    """Stands in for the engine: the two step programs and the slots."""

    def __init__(self, slots):
        self._jit_prefill_paged = lambda *a: None
        self._jit_decode_paged = lambda *a: None
        self.lengths = np.zeros(slots, np.int32)
        self.active = [None] * slots


def test_masked_rows_and_unread_pages_are_not_counted():
    m = _dense("stablelm-3b")
    eng = _Engine(4)
    rec = correct.Dispatches(eng)
    # four rows, two live: their lengths, not the pool's pages, count
    eng.lengths = np.array([10, 500, 20, 0], np.int32)
    eng.active = [SimpleNamespace(request_id=i) for i in (1, 2, 3, 4)]
    eng._jit_decode_paged(None, None, np.zeros((4, 1), np.int32),
                          None, None, np.array([True, False, True, False]))
    # one live prefill row of 8 real tokens in a 16-wide dispatch
    eng._jit_prefill_paged(None, None, np.zeros((4, 16), np.int32), None,
                           None, np.array([False, True, False, False]),
                           np.array([0, 8, 0, 0], np.int32))
    ctx = SimpleNamespace(dims=m, dispatches=rec, first_call=0,
                          close_call=rec.mark())
    w = derived.window_work(ctx)
    assert w["decode_tokens"] == 2 and w["prefill_tokens"] == 8
    assert w["decode_attention"] == tuple(
        m.layers * x for x in flops.decode_attention(m, [11, 21]))
    assert w["prefill_attention"] == tuple(
        m.layers * x for x in flops.prefill_attention(m, [(500, 8)]))
    assert w["step_flops"] == flops.step_flops(
        m, prefill_rows=[(500, 8)], decode_lengths=[11, 21])

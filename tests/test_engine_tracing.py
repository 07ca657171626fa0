"""The engine's own measurement: phase spans on the profiler's clock, the
always-on host-gap, prefill-row and KV-pool counters, token stamps taken
after the fetch, and step programs named after what they run."""
import contextlib
import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import CONFIGS
from repro.models.factory import build_model
from repro.serving import engine as engine_mod
from repro.serving.engine import InferenceEngine
from repro.serving.request import Request
from repro.telemetry.recorder import TraceRecorder

PHASES = ("engine.admit", "engine.prefill", "engine.decode", "engine.sample",
          "engine.retire")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(CONFIGS["tinyllama-1.1b"].reduced(),
                              num_layers=2)
    m = build_model(cfg)
    return m, m.init(jax.random.key(0)), cfg


def _engine(tiny_model, **kw):
    m, params, _ = tiny_model
    kw = {"max_slots": 2, "max_seq": 64, "policy": "chunked",
          "prefill_chunk": 4, **kw}
    eng = InferenceEngine(m, **kw)
    eng.load_params(params)
    return eng


def _requests(cfg, lengths=((6, 3), (9, 4), (5, 2)), arrivals=None):
    rng = np.random.default_rng(1)
    arrivals = arrivals or [0.0] * len(lengths)
    return [Request(i, rng.integers(0, cfg.vocab_size, p).astype(np.int32),
                    n, arrival_s=a)
            for i, ((p, n), a) in enumerate(zip(lengths, arrivals))]


def _busy(eng):
    return eng.waiting or any(r is not None for r in eng.active)


# ------------------------------------------------------------------ spans
@pytest.mark.parametrize("policy", ["chunked", "mixed"])
def test_phase_spans_nest_in_the_step_in_order(tiny_model, monkeypatch,
                                               policy):
    opened = []    # (depth, name, step_num)
    depth = [0]

    @contextlib.contextmanager
    def span(name, step_num=None):
        opened.append((depth[0], name, step_num))
        depth[0] += 1
        try:
            yield
        finally:
            depth[0] -= 1

    monkeypatch.setattr(engine_mod, "TraceAnnotation", span)
    monkeypatch.setattr(engine_mod, "StepTraceAnnotation", span)
    eng = _engine(tiny_model, policy=policy)
    for r in _requests(tiny_model[2]):
        eng.submit(r)
    eng.run()
    steps = [i for i, (d, _, _) in enumerate(opened) if d == 0]
    assert [opened[i][1] for i in steps] == ["engine.step"] * len(steps)
    assert [opened[i][2] for i in steps] == list(
        range(1, eng.stats.steps + 1))
    assert max(d for d, _, _ in opened) == 1   # phases never nest
    seen = set()
    for a, b in zip(steps, steps[1:] + [len(opened)]):
        names = " ".join(n for _, n, _ in opened[a + 1:b])
        assert re.fullmatch(r"engine\.admit( engine\.prefill)* engine\.decode"
                            r"( engine\.sample engine\.retire)?", names), names
        seen.update(names.split())
    assert seen == set(PHASES)
    assert sum(n == "engine.prefill" for _, n, _ in opened) == \
        eng.stats.prefill_dispatches


# --------------------------------------------------------------- counters
@pytest.mark.parametrize("paged", [True, False])
def test_counters_on_a_chunked_engine(tiny_model, paged):
    eng = _engine(tiny_model, paged=paged)
    for r in _requests(tiny_model[2]):
        eng.submit(r)
    gaps_expected = 0
    while _busy(eng):
        syncs = eng.stats.decode_syncs
        eng.step()
        if eng.stats.decode_syncs > syncs and _busy(eng):
            gaps_expected += 1
    st = eng.stats
    assert st.prefill_row_tokens == eng.max_slots * st.prefill_tokens > 0
    assert 0 < st.kv_live_tokens <= st.kv_pool_tokens
    pool = (eng.kv_pages * eng.page_size if paged
            else eng.max_slots * eng.max_seq)
    assert st.kv_pool_tokens == st.decode_syncs * pool
    assert st.host_gaps == gaps_expected > 0
    assert st.host_gap_s >= 0


def test_host_gap_times_the_host_and_skips_idle_waits(tiny_model):
    m, params, cfg = tiny_model
    eng = _engine(tiny_model)
    first, later = _requests(cfg, lengths=((6, 3), (5, 2)))
    eng.submit(first)
    while not eng.stats.decode_syncs:
        eng.step()
    assert _busy(eng) and eng.stats.host_gaps == 0
    time.sleep(0.02)             # host work the chip would wait for
    eng.step()
    assert eng.stats.host_gaps == 1 and eng.stats.host_gap_s >= 0.02
    eng.run()
    n, total = eng.stats.host_gaps, eng.stats.host_gap_s
    time.sleep(0.02)             # no work held: an arrival wait, not a gap
    eng.submit(later)
    eng.step()
    assert (eng.stats.host_gaps, eng.stats.host_gap_s) == (n, total)


def test_prefill_rows_count_the_widest_piece_of_a_batched_dispatch(
        tiny_model):
    eng = _engine(tiny_model, policy="mixed", max_slots=3)
    for r in _requests(tiny_model[2]):
        eng.submit(r)
    eng.run()
    st = eng.stats
    assert st.prefill_row_tokens % eng.max_slots == 0
    # several live rows share a dispatch, so more than one row in three
    # is live, and every dispatch is at most a chunk wide
    assert st.prefill_row_tokens < eng.max_slots * st.prefill_tokens
    assert st.prefill_tokens < st.prefill_row_tokens <= \
        eng.max_slots * eng.prefill_chunk * st.prefill_dispatches


# ----------------------------------------------------------------- stamps
def test_wall_clock_tokens_are_stamped_after_the_fetch(tiny_model,
                                                       monkeypatch):
    """Each fetch sleeps before it returns: a stamp taken before the fetch
    would read earlier than its return."""
    eng = _engine(tiny_model, recorder=TraceRecorder())
    returned = []

    class Fetch:
        def __init__(self, x):
            self.x = x

        def __array__(self, dtype=None, copy=None):
            a = np.asarray(self.x)
            time.sleep(0.005)
            returned.append(eng.now())
            return a

    class Jnp:
        def __getattr__(self, name):
            return getattr(jnp, name)

        def argmax(self, *a, **kw):
            return Fetch(jnp.argmax(*a, **kw))

    monkeypatch.setattr(engine_mod, "jnp", Jnp())
    reqs = _requests(tiny_model[2])
    for r in reqs:
        eng.submit(r)
    fetched = {}   # request id -> fetch return of each of its tokens
    while _busy(eng):
        dispatched = eng.now()
        n = len(returned)
        for rid, _ in eng.step():
            fetched.setdefault(rid, []).append(returned[-1])
        if len(returned) > n:
            spans = [e for e in eng._recorder.events if e.kind == "decode"
                     and e.t1 > dispatched]
            assert min(e.t0 for e in spans) <= returned[-1] - 0.005
            assert max(e.t1 for e in spans) >= returned[-1]
    for r in reqs:
        assert len(r.t_tokens) == len(fetched[r.request_id]) == \
            r.max_new_tokens
        assert all(t >= f for t, f in zip(r.t_tokens, fetched[r.request_id]))
        assert r.t_first_token >= fetched[r.request_id][0]
        assert r.t_done >= fetched[r.request_id][-1]


def test_virtual_clock_stamps_are_unchanged(tiny_model):
    """Stamps of a costed run: on the virtual clock the fetch takes no
    time, so stamps taken after it equal those taken before it."""
    eng = _engine(tiny_model, step_cost_s=lambda kind, n: (
        0.125 if kind == "prefill" else 0.5) * n)
    for r in _requests(tiny_model[2], arrivals=[0.0, 0.0, 1.0]):
        eng.submit(r)
    got = {r.request_id: [r.t_prefill, r.t_tokens, r.t_first_token, r.t_done]
           for r in eng.run()}
    assert got == {
        0: [[0.5, 0.75], [1.25, 2.25, 3.25], 1.25, 3.25],
        1: [[1.75, 2.75, 4.5], [5.5, 6.0, 6.5, 7.0], 5.5, 7.0],
        2: [[3.75, 3.875], [4.375, 5.5], 4.375, 5.5]}
    assert eng.stats.max_decode_gap_s == 1.125


# ----------------------------------------------------------- named programs
@pytest.mark.parametrize("key, name", [
    ("decode", "decode_step"), ("prefill", "prefill_chunk"),
    ("decode_paged", "decode_step_paged"),
    ("prefill_paged", "prefill_chunk_paged"),
    ("set_slice", "set_cache_slice"), ("copy_page", "copy_page")])
def test_each_program_lowers_under_its_own_name(tiny_model, key, name):
    m, params, _ = tiny_model
    paged = _engine(tiny_model)
    flat = _engine(tiny_model, paged=False)
    b = paged.max_slots
    tok1 = jnp.zeros((b, 1), jnp.int32)
    tok4 = jnp.zeros((b, 4), jnp.int32)
    lens = jnp.zeros((b,), jnp.int32)
    act = jnp.ones((b,), bool)
    tables = jnp.asarray(paged.allocator.tables)
    args = {
        "decode": (params, flat.cache, tok1, lens, act),
        "prefill": (params, flat.cache, tok4, lens, act, None),
        "decode_paged": (params, paged.cache, tok1, lens, tables, act),
        "prefill_paged": (params, paged.cache, tok4, lens, tables, act,
                          None),
        "set_slice": (paged.cache, 0, paged._fresh_slot),
        "copy_page": (paged.cache, jnp.int32(0), jnp.int32(1)),
    }[key]
    program = getattr(paged, f"_jit_{key}")
    assert program is getattr(flat, f"_jit_{key}")   # one per model
    text = program.lower(*args).as_text()
    assert re.search(rf"module @jit_{name} ", text), text[:200]

"""Stall-free mixed batching: the ``step_budget`` hook end to end.

Pins the tentpole contracts:

* ``MixedBatchPolicy`` arithmetic + registry wiring, and the base-policy
  default (``step_budget`` is None → legacy step path byte-for-byte);
* multi-slot batched ``prefill_chunk`` (per-row ``valid`` counts, pads at
  the tail) matches per-row sequential prefill for every batchable family;
* engine token streams under the budget are BIT-IDENTICAL to the legacy
  chunked path — contiguous, paged (with evictions), and prefix-cache
  admissions alike;
* ``prefill_dispatches`` drops >= 2x when several slots are mid-prefill
  (the one-dispatch-advances-several-slots claim);
* the prefix-hit flooring used by router probes and real admissions is
  the SAME rule (``_floor_to_chunk``);
* an engine built without an explicit ``prefill_chunk`` consults the
  roofline autotuner;
* the schema-1.7 ``batching`` block is ALWAYS present, and the analytic
  simulator's stall accounting agrees with the real engine's (<= 0.05
  absolute decode-stall-fraction gap on budget-enabled rows).
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench import Scenario, ScenarioApp
from repro.bench.policy import (ChunkedPolicy, MixedBatchPolicy,
                                SchedulingPolicy, get_policy)
from repro.configs.registry import CONFIGS
from repro.core.simulator import empty_batching_block
from repro.models.factory import build_model
from repro.serving.engine import InferenceEngine
from repro.serving.request import Request, chat_trace


@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(CONFIGS["tinyllama-1.1b"].reduced(),
                              num_layers=2)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    return m, params, cfg


# ------------------------------------------------------------- policy unit
def test_mixed_policy_registered():
    pol = get_policy("mixed")
    assert isinstance(pol, MixedBatchPolicy)
    assert isinstance(pol, ChunkedPolicy)    # inherits chunk behaviour
    assert pol.name == "mixed"


def test_mixed_policy_share_validation():
    with pytest.raises(ValueError, match="prefill_share"):
        MixedBatchPolicy(prefill_share=-0.1)
    with pytest.raises(ValueError, match="prefill_share"):
        MixedBatchPolicy(prefill_share=1.5)


def test_mixed_policy_budget_arithmetic():
    pol = MixedBatchPolicy(step_tokens=32, prefill_share=0.25)
    assert pol.step_budget(8, prefilling=2, decoding=3) == (8, 3)
    # default total budget: 2 * default_chunk
    assert MixedBatchPolicy().step_budget(8, 1, 5) == (8, 5)
    # no prefill work -> the whole budget is decode's
    assert MixedBatchPolicy(step_tokens=32).step_budget(8, 0, 5) == (0, 5)
    # share 0 throttles prefill but must not deadlock it
    assert MixedBatchPolicy(step_tokens=32,
                            prefill_share=0.0).step_budget(8, 2, 5) == (1, 5)


def test_legacy_policies_opt_out_of_the_budget():
    for pol in (SchedulingPolicy(), get_policy("fcfs"), get_policy("chunked"),
                get_policy("slo_aware"), get_policy("drr")):
        assert pol.step_budget(8, 2, 3) is None


# ------------------------------------- multi-slot batched prefill (models)
PARITY_ARCHS = ["tinyllama-1.1b", "mamba2-1.3b", "jamba-v0.1-52b",
                "moonshot-v1-16b-a3b", "seamless-m4t-large-v2"]


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_multi_slot_prefill_matches_sequential(arch, rng_key):
    """ONE prefill_chunk dispatch with per-row ``valid`` counts must match
    per-row sequential prefill (the legacy valid=None path) — logits at
    each row's last real token AND the cache, for every family the engine
    batches (all of them: served expert layers drop no token)."""
    cfg = CONFIGS[arch].reduced()
    cfg = dataclasses.replace(cfg, num_layers=min(cfg.num_layers, 2))
    if cfg.family == "hybrid":   # period constraint: keep one full period
        cfg = CONFIGS[arch].reduced()
    m = build_model(cfg)
    params = m.init(rng_key)
    b, width, max_seq = 3, 5, 32
    counts = [5, 3, 2]           # per-row REAL chunk tokens, pads at tail
    toks = jax.random.randint(rng_key, (b, width), 0, cfg.vocab_size)
    start = jnp.zeros((b,), jnp.int32)
    mask = jnp.ones((b,), bool)

    # batched: one dispatch, per-row valid counts
    cache = m.init_cache(b, max_seq)
    logits_b, cache_b = m.prefill_chunk(params, cache, toks, start, mask,
                                        jnp.asarray(counts, jnp.int32))

    # sequential oracle: per-row dispatch at the row's exact width,
    # valid=None (the legacy single-slot path)
    cache_s = m.init_cache(b, max_seq)
    last = {}
    for i, c in enumerate(counts):
        row_mask = jnp.arange(b) == i
        logits_i, cache_s = m.prefill_chunk(params, cache_s, toks[:, :c],
                                            start, row_mask)
        last[i] = np.asarray(logits_i, np.float32)[i, -1]

    for i, c in enumerate(counts):
        np.testing.assert_allclose(
            np.asarray(logits_b, np.float32)[i, c - 1], last[i],
            atol=2e-4, rtol=2e-4, err_msg=f"{arch} row {i}")
    for wl, gl in zip(jax.tree.leaves(cache_s), jax.tree.leaves(cache_b)):
        assert wl.dtype == gl.dtype
        scale = float(jnp.max(jnp.abs(wl.astype(jnp.float32)))) + 1e-9
        err = float(jnp.max(jnp.abs(wl.astype(jnp.float32) -
                                    gl.astype(jnp.float32))))
        assert err / scale < 2e-4, (arch, wl.shape, err / scale)


# --------------------------------------------- engine stream bit-identity
def _run_engine(m, cfg, params, policy, *, n=6, max_new=5, seed=11, **kw):
    reqs = chat_trace(n, cfg.vocab_size, mean_prompt=14, max_new=max_new,
                      seed=seed)
    eng = InferenceEngine(m, max_slots=4, max_seq=64, policy=policy,
                          prefill_chunk=4, **kw)
    eng.load_params(params)
    for r in reqs:
        eng.submit(r)
    done = {r.request_id: r.tokens_out for r in eng.run()}
    assert len(done) == n
    return done, eng.stats


def test_mixed_stream_bit_identical_contiguous(tiny_model):
    m, params, cfg = tiny_model
    want, st_chunked = _run_engine(m, cfg, params, "chunked")
    got, st_mixed = _run_engine(m, cfg, params,
                                MixedBatchPolicy(prefill_share=0.5))
    assert got == want
    assert st_mixed.budget_enabled and not st_chunked.budget_enabled
    assert st_mixed.mixed_steps > 0 and st_chunked.mixed_steps == 0


def test_mixed_stream_bit_identical_paged_with_evictions(tiny_model):
    """Paged cache under page pressure: the budget path must evict and
    recompute exactly like the legacy chunked path (same streams)."""
    m, params, cfg = tiny_model
    kw = dict(paged=True, page_size=4, kv_pages=24)
    want, st_c = _run_engine(m, cfg, params, "chunked", **kw)
    got, st_m = _run_engine(m, cfg, params,
                            MixedBatchPolicy(prefill_share=0.5), **kw)
    assert got == want
    assert st_m.evictions == st_c.evictions
    assert st_m.recompute_tokens == st_c.recompute_tokens


def test_mixed_stream_bit_identical_prefix_cache(tiny_model):
    """Prefix-cache admissions (floored hits, CoW pages) under the budget
    path: streams and hit accounting match the legacy chunked path."""
    m, params, cfg = tiny_model
    kw = dict(paged=True, page_size=4, kv_pages=64, prefix_cache=True)
    want, st_c = _run_engine(m, cfg, params, "chunked", **kw)
    got, st_m = _run_engine(m, cfg, params,
                            MixedBatchPolicy(prefill_share=0.5), **kw)
    assert got == want
    assert st_m.prefix_hit_tokens == st_c.prefix_hit_tokens


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "jamba-v0.1-52b"])
def test_mixed_stream_bit_identical_families(arch, rng_key):
    """SSM and hybrid (expert sublayers served dropless) both keep streams
    identical under multi-slot batched prefill."""
    cfg = CONFIGS[arch].reduced()
    cfg = dataclasses.replace(cfg, num_layers=min(cfg.num_layers, 2))
    if cfg.family == "hybrid":
        cfg = CONFIGS[arch].reduced()
    m = build_model(cfg)
    params = m.init(rng_key)
    want, _ = _run_engine(m, cfg, params, "chunked", n=3, max_new=4)
    got, st = _run_engine(m, cfg, params,
                          MixedBatchPolicy(prefill_share=0.5), n=3, max_new=4)
    assert got == want
    assert st.budget_enabled


# -------------------------------------------------- dispatch-count claim
def test_multi_slot_prefill_cuts_dispatches(tiny_model):
    """With >= 2 slots mid-prefill, one batched dispatch advances several
    slots: prefill_dispatches must drop >= 2x vs the per-slot path at the
    same chunk size."""
    m, params, cfg = tiny_model
    _, st_chunked = _run_engine(m, cfg, params, "chunked")
    _, st_mixed = _run_engine(m, cfg, params,
                              MixedBatchPolicy(step_tokens=32))
    assert st_mixed.prefill_dispatches * 2 <= st_chunked.prefill_dispatches
    assert st_mixed.prefill_tokens == st_chunked.prefill_tokens


# ----------------------------------------------- prefix-hit flooring rule
def test_prefix_flooring_shared_by_probe_and_admission(tiny_model):
    """prefix_peek (router probe) and _prefix_lookup (real admission) must
    floor a hit with the SAME rule — regression for the duplicated
    flooring logic that _floor_to_chunk deduplicated."""
    m, params, cfg = tiny_model
    eng = InferenceEngine(m, max_slots=2, max_seq=64, policy="chunked",
                          prefill_chunk=4, paged=True, page_size=4,
                          kv_pages=64, prefix_cache=True)
    eng.load_params(params)
    prompt = np.arange(14, dtype=np.int32) % cfg.vocab_size
    eng.submit(Request(0, prompt, 3, arrival_s=0.0))
    eng.run()
    probe = np.concatenate([prompt, [1, 2, 3]]).astype(np.int32)
    peek = eng.prefix_peek(probe)
    raw = eng.prefix.peek([int(t) for t in probe])
    assert peek == eng._floor_to_chunk(raw)
    assert peek % eng.prefill_chunk == 0
    hit, _pages = eng._prefix_lookup(probe)
    assert hit == peek                  # probe and admission agree exactly


# ------------------------------------------------- autotuned default chunk
def test_engine_default_prefill_chunk_is_autotuned(tiny_model):
    from repro.kernels import autotune
    m, params, cfg = tiny_model
    eng = InferenceEngine(m, max_slots=2, max_seq=64, policy="chunked")
    assert eng.prefill_chunk == autotune.engine_prefill_chunk(cfg,
                                                              max_seq=64)
    assert eng.prefill_chunk >= 1


# ----------------------------------------- schema-1.7 batching block
def _bat_scenario(policy, substrate="simulator", tag=""):
    return Scenario(
        name=f"mb-{tag}-{substrate}", mode="concurrent", policy=policy,
        total_chips=16, substrate=substrate, seed=7,
        apps=[ScenarioApp("chatbot", num_requests=4),
              ScenarioApp("deep_research", num_requests=1)])


def test_batching_block_always_present_and_zero_when_no_steps():
    blk = empty_batching_block()
    assert blk == {"enabled": False, "mixed_steps": 0, "steps": 0,
                   "prefill_tokens": 0, "decode_tokens": 0,
                   "prefill_share": 0.0, "decode_stall_fraction": 0.0}


def test_batching_block_shape_on_both_substrates():
    for substrate in ("simulator", "engine"):
        doc = _bat_scenario("fcfs", substrate, "fcfs").run().to_json()
        assert doc["schema_version"] == "1.8"
        blk = doc["results"]["concurrent"]["batching"]
        assert set(blk) == set(empty_batching_block())
        assert not blk["enabled"]
        assert blk["mixed_steps"] == 0       # no budget -> no mixed steps
        assert blk["steps"] > 0
        # 1.7 per-app token-latency percentiles ride along
        chat = doc["results"]["concurrent"]["apps"]["chatbot"]
        for key in ("ttft_p50", "ttft_p99", "tpot_p50", "tpot_p99",
                    "itl_p99"):
            assert key in chat, key


def test_budget_kills_decode_stalls_cross_substrate():
    """Budget-enabled rows: mixed_steps > 0, stall fraction collapses vs
    exclusive prefill, and the two substrates agree to <= 0.05 absolute."""
    stall = {}
    for substrate in ("simulator", "engine"):
        pol = MixedBatchPolicy(prefill_share=0.5)
        blk = _bat_scenario(pol, substrate, "mixed").run() \
            .to_json()["results"]["concurrent"]["batching"]
        assert blk["enabled"]
        assert blk["mixed_steps"] > 0
        assert blk["prefill_share"] == 0.5
        assert 0.0 <= blk["decode_stall_fraction"] <= 1.0
        stall[substrate] = blk["decode_stall_fraction"]
    assert abs(stall["simulator"] - stall["engine"]) <= 0.05
    fcfs = _bat_scenario("fcfs", "simulator", "fcfs2").run() \
        .to_json()["results"]["concurrent"]["batching"]
    assert stall["simulator"] < fcfs["decode_stall_fraction"]


def test_mixed_scenario_to_json_deterministic():
    """Two runs of the same (scenario, seed) under the budget serialize
    byte-identically — the schema-1.7 determinism pin."""
    for substrate in ("simulator", "engine"):
        docs = [json.dumps(_bat_scenario(MixedBatchPolicy(prefill_share=0.5),
                                         substrate, "det").run().to_json(),
                           sort_keys=True)
                for _ in range(2)]
        assert docs[0] == docs[1], substrate
